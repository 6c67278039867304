"""Seconds per fit in the blocking host-to-device copies of the megabatches
(``ingest.h2d`` spans)."""


def read(ctx):
    if not ctx["units"] or not ctx["spans"].get("ingest.h2d"):
        return None
    return ctx["span_s"]("ingest.h2d") / ctx["units"]
