"""Seconds per fit inside solver spans (``solver.solve``, ``solve_many``,
``device_grid``, ``fallback``; nested spans count once)."""

NAMES = ("solver.solve", "solver.solve_many", "solver.device_grid",
         "solver.fallback")


def read(ctx):
    return ctx["span_s"](*NAMES) / ctx["units"] if ctx["units"] else None
