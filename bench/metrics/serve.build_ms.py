"""Mean milliseconds of one ``serve.build`` span: zeroing the dense batch
and scattering its documents into it, on the collector thread."""


def read(ctx):
    spans = ctx["spans"].get("serve.build", [])
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)
