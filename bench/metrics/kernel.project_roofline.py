"""Share of the roofline of the serving projection (bench/work/project.py)
over the kernels inside serving batches."""


def read(ctx):
    return ctx["roofline"]("bench.batch", "project")
