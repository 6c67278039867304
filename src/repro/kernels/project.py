"""Pallas TPU kernel: sparse-projection gather-matvec (the serving hot path).

Online topic serving projects a batch of BOW count vectors onto k fitted
sparse components.  Dense algebra would read all B*n elements per batch, but
the components' total support is ~k*card << n (Tables 1-2 of the paper show
card ~ 5 on a 102,660-word vocabulary), so the right primitive is a *gather*
matvec: touch only the supported columns.

Layout (built by ``repro.kernels.ops.sparse_project`` from the packed
``repro.serve.projector.ProjectorPack``):

  X     (B, n)      the batch of docs, untouched: no transpose, no pad row.
  idx   (P,) int32  flat gather slots, component-major: slot p belongs to
                    component p // cap and reads word idx[p].
  cid   (P,) int32  p // cap, the output lane slot p accumulates into.
  vals  (P,) f32    loading of component cid[p] at word idx[p]; 0 for pads.

Grid: (B/block_b, P) with the slot axis innermost.  Step (i, p) DMAs the
(block_b, 128) lane block of X that holds word idx[p] (block index
idx[p] // 128 — an (8, 128)-aligned tile, so any n works), picks lane
idx[p] % 128 with a masked lane reduction, and adds vals[p] times that
column into output lane cid[p] of the (block_b, 128) score block, which
stays VMEM-resident across the P slots.  HBM traffic is B*P*128*4 bytes —
proportional to the packed nnz, never to n.

Scalar prefetch (``PrefetchScalarGridSpec``) puts idx/cid in SMEM before
the body runs, which is what lets the index map steer the DMA to the
gathered block while the previous slot computes; the loadings ride along
in SMEM too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, cid_ref, vals_ref, x_ref, out_ref):
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lanes = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 1)
    col = jnp.sum(jnp.where(lanes == idx_ref[p] % 128,
                            x_ref[...].astype(jnp.float32), 0.0),
                  axis=1, keepdims=True)                # (block_b, 1)
    out_lanes = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] += jnp.where(out_lanes == cid_ref[p], vals_ref[p] * col, 0.0)


def sparse_project_pallas(
    X: jax.Array,
    idx: jax.Array,
    cid: jax.Array,
    vals: jax.Array,
    k: int,
    *,
    block_b: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Scores of shape (B, k): out[b, c] = sum_p vals[p] * X[b, idx[p]] over
    the slots p with cid[p] == c.

    ``idx``/``cid``/``vals`` are the flat component-major gather
    representation (P = k*cap slots); padded slots carry vals 0.
    """
    B, n = X.shape
    P = idx.shape[0]
    assert k <= 128, f"k={k} components exceed one 128-lane score block"
    block_b = min(block_b, -(-B // 8) * 8)
    pb = (-B) % block_b
    if pb:
        X = jnp.pad(X, ((0, pb), (0, 0)))
    Bp = B + pb
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bp // block_b, P),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(
                (block_b, 128), lambda i, p, idx_ref, cid_ref:
                (i, idx_ref[p] // 128)
            ),
        ],
        out_specs=pl.BlockSpec(
            (block_b, 128), lambda i, p, idx_ref, cid_ref: (i, 0)
        ),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bp, 128), jnp.float32),
        interpret=interpret,
        name="sparse_project",
        cost_estimate=pl.CostEstimate(
            flops=2 * Bp * P * 128,
            bytes_accessed=(Bp * P * 128 + P * 3 + Bp * 128) * 4,
            transcendentals=0,
        ),
    )(jnp.asarray(idx, jnp.int32), jnp.asarray(cid, jnp.int32),
      jnp.asarray(vals, jnp.float32), X)
    return out[:B, :k]
