"""Seconds per fit in the Gram pass (``ingest.gram_pass`` spans)."""


def read(ctx):
    return ctx["span_s"]("ingest.gram_pass") / ctx["units"] if ctx["units"] else None
