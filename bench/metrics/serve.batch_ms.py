"""Mean milliseconds of one ``serve.batch`` span: projection of one padded
batch, scores back on the host, futures resolved."""


def read(ctx):
    spans = ctx["spans"].get("serve.batch", [])
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)
