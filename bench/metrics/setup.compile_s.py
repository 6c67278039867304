"""Backend compile seconds during set-up (JAX monitoring events); 0 when
every program came from the persistent cache."""


def read(ctx):
    return ctx["setup"]["compile_s"]
