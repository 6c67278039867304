"""Share of the roofline of the Gram kernel (bench/work/csr_gram.py) over
the kernels inside the Gram pass."""


def read(ctx):
    return ctx["roofline"]("bench.gram", "gram")
