"""Mean milliseconds a request waited in the batcher's queue, from submit
until the collector took it (``serve.queue_wait_s``)."""


def read(ctx):
    h = ctx["histogram"]("serve.queue_wait_s")
    if not h or not h["count"]:
        return None
    return 1e3 * h["total"] / h["count"]
