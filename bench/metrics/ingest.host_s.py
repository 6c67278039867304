"""Seconds per fit of the feed's host work on the pass thread: the padding
check and support remap before each copy (``ingest.prep``) and the screen
pass's float64 fold after it (``ingest.readback``); overlaps count once."""

NAMES = ("ingest.prep", "ingest.readback")


def read(ctx):
    if not ctx["units"] or not any(ctx["spans"].get(n) for n in NAMES):
        return None
    return ctx["span_s"](*NAMES) / ctx["units"]
