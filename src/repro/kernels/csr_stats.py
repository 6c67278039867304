"""Pallas TPU kernel: segmented per-column sum/sumsq from CSR chunks.

The variance screen (Thm 2.1) over an out-of-core corpus must never
densify: a >99%-sparse (m, n) matrix read as dense blocks wastes 100x the
HBM bandwidth on zeros.  This kernel consumes the store's fixed-shape
``(chunk_nnz,)`` entry chunks directly and accumulates per-column
``(sum, sumsq)`` living in VMEM — one pass, O(nnz) work.

Vectorized scatter (PR 5): the accumulators are shaped ``(n_pad/128, 128)``
so column ``c`` maps to sublane-row ``c // 128``, lane ``c % 128``.  The
original kernel scattered one entry per step — a dynamic-sublane
read-modify-write with a one-hot lane mask, nnz *sequential* VPU ops.  The
rewrite processes entries in ``(8, 128)``-tiled blocks and turns the
scatter into a one-hot contraction the MXU executes: for each 128-entry
lane row, ``M[s, p] = v_p * [c_p // 128 == s]`` (a broadcast compare
against a sublane iota — no transpose needed) and
``L[l, p] = [c_p %% 128 == l]``, so

    acc[s, l] += sum_p M[s, p] * L[l, p]      (one dot_general, MXU)

deposits all 128 entries at once.  sum and sumsq share one matmul by
stacking their M blocks.  Padded slots (value 0, col 0) land on
accumulator (0, 0) with value 0 — additively harmless, no masking.

Batch dimension (PR 5): the grid is ``(C, E_pad/block_e)`` over a
megabatch of C chunks, both accumulators VMEM-resident across the WHOLE
batch — one ``pallas_call`` per megabatch instead of one per chunk,
mirroring the batched-solve launch economics of the BCD kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Entry tile geometry: lane rows of 128 entries, ``_TILE_ROWS`` rows per
# grid step (the (8, 128) VPU-native tile).
_TILE_ROWS = 8


def _kernel(vals_ref, cols_ref, sum_ref, sumsq_ref, *, tile_rows: int):
    c = pl.program_id(0)
    e = pl.program_id(1)

    @pl.when((c == 0) & (e == 0))
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sumsq_ref[...] = jnp.zeros_like(sumsq_ref)

    S = sum_ref.shape[0]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (S, 128), 0)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
    # Static unroll over the block's lane rows (tile_rows <= 8; Mosaic has
    # no lowering for a dynamic slice of a loaded value).  The rows'
    # one-hot blocks sit side by side on the lane axis, so the whole block
    # is ONE contraction over tile_rows * 128 entries.
    ms, ohls = [], []
    for a in range(tile_rows):
        va = vals_ref[0, a:a + 1, :].astype(jnp.float32)     # (1, 128)
        ca = cols_ref[0, a:a + 1, :]
        ohr = row_iota == ca // 128                          # (S, 128)
        ms.append(jnp.concatenate(
            [jnp.where(ohr, va, 0.0), jnp.where(ohr, va * va, 0.0)], axis=0
        ))                                                   # (2S, 128)
        ohls.append((lane_iota == ca % 128).astype(jnp.float32))
    # HIGHEST: the one-hot side is exact in bf16 but the values are not; a
    # default-precision pass would round count^2 sums past 256.
    d = jax.lax.dot_general(
        jnp.concatenate(ms, axis=1), jnp.concatenate(ohls, axis=1),
        dimension_numbers=(((1,), (1,)), ((), ())),          # contract p
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                        # (2S, 128)
    sum_ref[...] += d[:S]
    sumsq_ref[...] += d[S:]


def csr_column_stats_pallas(
    values: jax.Array,
    col_ids: jax.Array,
    n: int,
    *,
    block_e: int = 4096,
    interpret: bool = False,
):
    """Returns ``(col_sum, col_sumsq)`` of shape (n,) in f32 from CSR entry
    arrays.  ``values``/``col_ids`` are either flat ``(E,)`` (one chunk) or
    ``(C, E)`` (a megabatch of C chunks, reduced in ONE launch).
    ``col_ids`` must be in [0, n); padded slots must carry value 0 (their
    column is then irrelevant — see `ops.csr_column_stats` for the
    enforced contract).  ``block_e`` is the per-grid-step entry count; it
    is clamped to the (padded) entry count so a chunk smaller than one
    block never inflates the launch shape.
    """
    if values.ndim == 1:
        values = values.reshape(1, -1)
        col_ids = col_ids.reshape(1, -1)
    C, E = values.shape
    assert col_ids.shape == (C, E)
    # Entries tile as (rows, 128) lanes; rows group into tile_rows blocks.
    pe = (-E) % 128
    if pe:
        values = jnp.pad(values, ((0, 0), (0, pe)))
        col_ids = jnp.pad(col_ids, ((0, 0), (0, pe)))
    rows = (E + pe) // 128
    tile_rows = max(1, min(_TILE_ROWS, block_e // 128, rows))
    pr = (-rows) % tile_rows
    rows_p = rows + pr
    values = values.reshape(C, rows, 128)
    col_ids = jnp.asarray(col_ids, jnp.int32).reshape(C, rows, 128)
    if pr:
        values = jnp.pad(values, ((0, 0), (0, pr), (0, 0)))
        col_ids = jnp.pad(col_ids, ((0, 0), (0, pr), (0, 0)))
    # Accumulator rows S padded to the 8-sublane tile, so the stacked
    # (sum; sumsq) operand of the contraction stays tile-aligned.
    n_pad = ((n + 1023) // 1024) * 1024
    S = n_pad // 128
    out_shape = [
        jax.ShapeDtypeStruct((S, 128), jnp.float32),
        jax.ShapeDtypeStruct((S, 128), jnp.float32),
    ]
    Ep = C * rows_p * 128
    s, ss = pl.pallas_call(
        functools.partial(_kernel, tile_rows=tile_rows),
        grid=(C, rows_p // tile_rows),
        in_specs=[
            pl.BlockSpec((1, tile_rows, 128), lambda c, e: (c, e, 0)),
            pl.BlockSpec((1, tile_rows, 128), lambda c, e: (c, e, 0)),
        ],
        out_specs=[
            pl.BlockSpec((S, 128), lambda c, e: (0, 0)),
            pl.BlockSpec((S, 128), lambda c, e: (0, 0)),
        ],
        out_shape=out_shape,
        interpret=interpret,
        name="csr_column_stats",
        cost_estimate=pl.CostEstimate(
            # one (2S, 128) x (128, 128) MXU contraction per 128 entries
            flops=2 * 2 * S * 128 * Ep // 128,
            bytes_accessed=(2 * Ep + 2 * n_pad) * 4,
            transcendentals=0,
        ),
    )(values, col_ids)
    return s.reshape(n_pad)[:n], ss.reshape(n_pad)[:n]
