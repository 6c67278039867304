"""Seconds per fit in the screen pass (``ingest.screen_pass`` spans, which
end on a device sync while tracing)."""


def read(ctx):
    return ctx["span_s"]("ingest.screen_pass") / ctx["units"] if ctx["units"] else None
