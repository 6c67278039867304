"""Fused solves re-solved on the jnp path per fit (``solver.fallbacks``)."""


def read(ctx):
    return ctx["counter"]("solver.fallbacks") / ctx["units"] if ctx["units"] else None
