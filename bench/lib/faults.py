"""Faults planted under the timed path, to show that ``correct`` catches
them: the CPU tests (bench/tests/test_bench_control.py) plant them at a
small size, and bench/readings.py ``--fault <name>`` on the chip at the
cell's size.  Each takes ``setattr`` (or pytest's ``monkeypatch.setattr``)
to plant itself.
"""
from __future__ import annotations


def solve_returns_start(setattr_=setattr) -> None:
    """Every solve (one problem or a batch) hands back its warm start, the
    identity where it had none: a solver that does no work."""
    import jax.numpy as jnp

    from repro.core import bcd

    def unsolved(res, Sigma, X0):
        X = jnp.eye(Sigma.shape[0], dtype=res.X.dtype) if X0 is None \
            else jnp.asarray(X0, res.X.dtype)
        return res._replace(X=X, Z=X / jnp.trace(X))

    one, many = bcd.solve_bcd, bcd.solve_bcd_many

    def solve(Sigma, lam, **kw):
        return unsolved(one(Sigma, lam, **kw), Sigma, kw.get("X0"))

    def solve_many(Sigmas, lams, **kw):
        X0s = kw.get("X0s") or [None] * len(Sigmas)
        return [unsolved(r, S, X0) for r, S, X0
                in zip(many(Sigmas, lams, **kw), Sigmas, X0s)]

    setattr_(bcd, "solve_bcd", solve)
    setattr_(bcd, "solve_bcd_many", solve_many)


FAULTS = {"solve_returns_start": solve_returns_start}
