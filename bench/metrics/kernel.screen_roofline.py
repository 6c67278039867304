"""Share of the roofline of the screen kernel: the least time the chip
needs for the pass's work (bench/work/csr_stats.py) over the device time
of the kernels that started inside the screen pass."""


def read(ctx):
    return ctx["roofline"]("bench.screen", "screen")
