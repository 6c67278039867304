"""Seconds per fit the pass thread waited for the next megabatch from the
reader thread (``ingest.feed_wait`` spans)."""


def read(ctx):
    if not ctx["units"] or not ctx["spans"].get("ingest.feed_wait"):
        return None
    return ctx["span_s"]("ingest.feed_wait") / ctx["units"]
