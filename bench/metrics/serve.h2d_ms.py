"""Mean milliseconds of one ``serve.h2d`` span: the dense batch's copy to
the device, inside ``serve.batch``."""


def read(ctx):
    spans = ctx["spans"].get("serve.h2d", [])
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)
