"""Distributed statistics for sparse PCA over the (pod, data) mesh axes.

The paper notes the screen "only requires the computation of each feature's
variance, and that this task is easy to parallelize".  Here that observation
becomes a collective program: documents are sharded across the combined
(pod, data) axes, each shard reduces its row block locally, and a single
psum finishes the job.  The reduced gram matrix after elimination is the
same pattern with a local matmul — so the *only* cross-chip traffic for the
whole sparse-PCA preprocessing is two psums of size O(n) and O(n_hat^2).

The BCD solve itself runs on n_hat <= ~1k reduced problems — replicated (it
fits in a single core's VMEM; see kernels/bcd_sweep.py).  Cross-problem
parallelism (lambda grid, deflation rounds) uses vmap instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .elimination import Screen


def data_axes_of(mesh: Mesh) -> tuple[str, ...]:
    """All mesh axes that shard documents (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


@functools.lru_cache(maxsize=None)
def _pooled_fn(mesh: Mesh, axes: tuple, ndims: tuple):
    """Jitted psum pooling, cached on (mesh, axes, leaf ranks).  Eager
    shard_map retraces AND recompiles on every call (~hundreds of ms on
    CPU), which would tax every pass finalize; under this cache the
    compile is paid once per shape family."""

    def pool(*xs):
        return tuple(jax.lax.psum(x[0], axes) for x in xs)

    in_specs = tuple(P(axes, *(None,) * (nd - 1)) for nd in ndims)
    out_specs = tuple(P(*(None,) * (nd - 1)) for nd in ndims)
    return jax.jit(
        jax.shard_map(pool, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )


def psum_partials(partials, mesh: Mesh, *, axes=None):
    """Pool per-device partial reductions device-side — THE merge step.

    ``partials`` is a pytree of arrays whose leading axis is stacked one
    slot per device along ``axes`` (so a leaf is ``(D, ...)`` sharded or
    shardable to ``P(axes, None, ...)``).  Each device contributes its slot
    and one psum finishes the job; the result is the replicated sum with
    the leading device axis dropped.  This is the same math
    ``combine_screens`` / ``StreamingGram.merge`` guarantee on the host —
    every distributed pooling in the repo (the dense passes below, the
    sparse mesh passes in ``sparse/mesh_engine.py``) routes through here so
    there is exactly one implementation of partial pooling.
    """
    axes = data_axes_of(mesh) if axes is None else tuple(axes)
    flat, treedef = jax.tree_util.tree_flatten(partials)
    fn = _pooled_fn(mesh, axes, tuple(x.ndim for x in flat))
    return jax.tree_util.tree_unflatten(treedef, fn(*flat))


def distributed_variances(A, mesh: Mesh, *, center: bool = True) -> Screen:
    """Per-feature variances with documents sharded over the data axes.

    A: (m, n) global array (or anything shardable to P(data_axes, None)).
    Returns a replicated Screen.
    """
    axes = data_axes_of(mesh)
    spec_in = P(axes, None)

    def local(a):
        # Stack this device's partial moments in its slot of the (D, ...)
        # partials; pooling happens once in psum_partials.
        s = jnp.sum(a, axis=0)[None]
        ss = jnp.sum(a * a, axis=0)[None]
        cnt = jnp.full((1, 1), a.shape[0], a.dtype)
        return s, ss, cnt

    shard_fn = jax.shard_map(
        local, mesh=mesh, in_specs=(spec_in,),
        out_specs=(P(axes, None), P(axes, None), P(axes, None)),
    )
    s, ss, cnt = psum_partials(shard_fn(A), mesh, axes=axes)
    m = cnt[0]
    mean = s / m if center else jnp.zeros_like(s)
    var = jnp.maximum(ss / m - mean * mean, 0.0)
    return Screen(variances=var, means=mean, count=m)


def distributed_gram(A_red, mesh: Mesh, *, means=None) -> jax.Array:
    """Reduced covariance Sigma_hat = sum_k A_k^T A_k / m with document shards.

    ``A_red`` is (m, n_hat) — the surviving columns only.  If ``means`` is
    given the gram is centred: (A-mu)^T(A-mu) = A^T A - m mu mu^T.
    """
    axes = data_axes_of(mesh)
    spec_in = P(axes, None)

    def local(a):
        g = (a.T @ a)[None]
        cnt = jnp.full((1, 1), a.shape[0], a.dtype)
        return g, cnt

    shard_fn = jax.shard_map(
        local, mesh=mesh, in_specs=(spec_in,),
        out_specs=(P(axes, None, None), P(axes, None)),
    )
    g, cnt = psum_partials(shard_fn(A_red), mesh, axes=axes)
    m = cnt[0]
    if means is not None:
        g = g - m * jnp.outer(means, means)
    return g / m


def distributed_screen_and_gram(
    A, mesh: Mesh, lam: float, *, center: bool = True, max_reduced: int = 2048
):
    """Fused end-to-end preprocessing: one variance pass, host-side support
    selection (tiny), one gram pass.  Returns (Sigma_hat, support, screen)."""
    from .elimination import select_support

    screen = distributed_variances(A, mesh, center=center)
    support = select_support(screen.variances, lam, max_reduced)
    idx = jnp.asarray(support)
    axes = data_axes_of(mesh)
    cols = jax.jit(
        lambda a: jnp.take(a, idx, axis=1),
        in_shardings=NamedSharding(mesh, P(axes, None)),
        out_shardings=NamedSharding(mesh, P(axes, None)),
    )(A)
    means = jnp.take(screen.means, idx) if center else None
    Sigma_hat = distributed_gram(cols, mesh, means=means)
    return Sigma_hat, support, screen
