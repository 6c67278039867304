"""Device profiles with the program spans on them.

`trace_device` runs a `jax.profiler` trace over a block.  The program's
own `obs.trace` spans open a `jax.profiler.TraceAnnotation` of the same
name while a tracer is installed, so they land on the profile's host
planes beside the device ops; `trace_device` installs a tracer for the
block when none is, so a profile always carries them.  ``jax`` is
imported lazily, and a ``None`` directory is a no-op.
"""
from __future__ import annotations

import contextlib

from . import trace


@contextlib.contextmanager
def trace_device(log_dir: str | None):
    """``with profile.trace_device(dir):`` — run a `jax.profiler` device
    trace over the block (TensorBoard/Perfetto-loadable).  ``None`` is a
    no-op, so callers can pass an optional CLI flag straight through."""
    if not log_dir:
        yield
        return
    import jax

    own = trace.active() is None
    if own:
        trace.install(trace.Tracer())
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        if own:
            trace.install(None)
