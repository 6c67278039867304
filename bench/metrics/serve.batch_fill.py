"""Mean live rows per served batch over ``max_batch`` (``serve.batch_size``)."""


def read(ctx):
    h = ctx["histogram"]("serve.batch_size")
    if not h or not h["count"]:
        return None
    return 100.0 * h["total"] / h["count"] / ctx["max_batch"]
