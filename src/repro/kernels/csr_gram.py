"""Pallas TPU kernels: gather-Gram — Sigma_hat numerator from CSR chunks.

After safe elimination only ``n_hat << n`` columns survive, but the
streaming dense path still reads every column of every row block to slice
out A_S.  These kernels build ``G += A_S^T A_S`` *directly from the CSR
entries*: entries are scatter-densified into a chunk-local
``(R, n_hat_pad)`` scratch (R = chunk row capacity) resident in VMEM,
then the Gram is an MXU contraction over R.  Work is O(nnz_S) scatter +
O(R n_hat^2) flops — never O(m n).

Support mapping happens upstream (``repro.sparse.engine``): ``local_cols``
holds each entry's position *within the support* and any value >= n_hat is
a sentinel meaning "entry not on the support, drop it" (matching the
oracle's ``mode='drop'`` scatter).

Two schemes (mirroring the fused-solver plan split):

* ``csr_gram_batched_pallas`` — the megabatch kernel: grid=(C, entry
  tiles) over a batch of C chunks, with BOTH the densify scratch and the
  full (n_pad, n_pad) Gram accumulator VMEM-resident; each (8, 128) entry
  tile densifies into the scratch as one-hot MXU contractions
  (`_densify_rows`), and the chunk's last tile accumulates one whole-chunk
  ``B^T B`` dot.  ONE ``pallas_call`` per megabatch instead of one per
  chunk; fits while its VMEM state stays under the budget (n_hat <= 640
  at R=512 in f32 — see `batched_gram_fits`).
* ``csr_gram_pallas`` — the single-chunk kernel, kept as the
  large-``n_hat`` fallback: (n_tiles, n_tiles) output-tile grid, scratch
  shaped (n_tiles, R, 128) so only 128-lane tiles are ever contracted.
  `csr_gram_megabatch_pallas` picks between the two.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM budget for the resident-G batched scheme: densify scratch + Gram
# accumulator + double-buffered entry blocks, against ~16 MB/core.
_BATCHED_VMEM_BUDGET_BYTES = 14 * 1024 * 1024
# Entry tile: lane rows of 128 entries, ``_TILE_ROWS`` rows per contraction.
_TILE_ROWS = 8


# Words of one-hot operand per densify contraction (see `_group_rows`).
_DENSIFY_OPERAND_WORDS = 1 << 18


def _group_rows(R: int, n_pad: int) -> int:
    """Entry lane rows per densify contraction: the (R + n_pad, 128 g)
    one-hot operands stay within `_DENSIFY_OPERAND_WORDS`; g divides
    ``_TILE_ROWS``."""
    g = _TILE_ROWS
    while g > 1 and g * 128 * (R + n_pad) > _DENSIFY_OPERAND_WORDS:
        g //= 2
    return g


def batched_gram_fits(n_hat: int, n_rows: int, chunk_nnz: int) -> bool:
    """Whether the one-launch megabatch scheme's VMEM state fits: the
    n_pad^2 Gram accumulator and the B^T B result, the R x n_pad densify
    scratch with its loaded/transposed/summed copies (~6 R n_pad), and the
    one-hot operands with their mask temporaries (~4x the operands).
    Calibrated against the scoped VMEM the v5e compiler reports for this
    kernel at R=512 (n_hat 384: 7.8 MiB, 768: 14.7, 1024: 20.2) — the
    model overestimates each by ~15%.  ``chunk_nnz`` does not enter:
    entries stream through fixed-size (8, 128) blocks."""
    del chunk_nnz
    n_pad = max(128, ((n_hat + 127) // 128) * 128)
    R = ((max(n_rows, 8) + 7) // 8) * 8
    g = _group_rows(R, n_pad)
    words = (2 * n_pad * n_pad + 6 * R * n_pad + 4 * g * 128 * (R + n_pad)
             + 3 * 2 * _TILE_ROWS * 128)
    return words * 4 <= _BATCHED_VMEM_BUDGET_BYTES


def _entry_rows(values, local_cols, seg_ids):
    """(C, E) entry arrays -> (C, rows, 128) lane rows, rows a multiple of
    ``_TILE_ROWS``; padded slots carry value 0 (additively harmless)."""
    C, E = values.shape
    rows = -(-E // 128)
    rows = -(-rows // _TILE_ROWS) * _TILE_ROWS
    pe = rows * 128 - E

    def lay(a, dtype):
        a = jnp.asarray(a, dtype)
        if pe:
            a = jnp.pad(a, ((0, 0), (0, pe)))
        return a.reshape(C, rows, 128)

    return (lay(values, jnp.float32), lay(local_cols, jnp.int32),
            lay(seg_ids, jnp.int32))


def _densify_rows(vals, cols, segs, *, R: int, n_pad: int, n_hat: int):
    """g lane rows of entries -> their (R, n_pad) contribution to the
    chunk's dense rows B, as ONE one-hot contraction on the MXU:
    ``B[r, c] += sum_p v_p [seg_p == r] [col_p == c]``.  Entries with
    ``col >= n_hat`` (off-support sentinel) get weight 0."""
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (R, 128), 0)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (n_pad, 128), 0)
    ms, ohs = [], []
    for a in range(vals.shape[0]):          # static unroll, <= _TILE_ROWS
        ca = cols[a:a + 1, :]
        va = jnp.where(ca < n_hat, vals[a:a + 1, :], 0.0)   # (1, 128)
        ms.append(jnp.where(row_iota == segs[a:a + 1, :], va, 0.0))
        ohs.append((col_iota == ca).astype(jnp.float32))
    # HIGHEST: every product is v * 1 and each (row, col) pair is hit once
    # per chunk, so the densified B is exact only at full f32 precision.
    return jax.lax.dot_general(
        jnp.concatenate(ms, axis=1), jnp.concatenate(ohs, axis=1),
        dimension_numbers=(((1,), (1,)), ((), ())),         # contract p
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                       # (R, n_pad)


def _densify_tile(vals, cols, segs, *, R: int, n_pad: int, n_hat: int):
    """A (_TILE_ROWS, 128) entry tile's contribution to B, contracted in
    groups of `_group_rows` lane rows."""
    g = _group_rows(R, n_pad)
    out = None
    for a in range(0, vals.shape[0], g):
        d = _densify_rows(vals[a:a + g], cols[a:a + g], segs[a:a + g],
                          R=R, n_pad=n_pad, n_hat=n_hat)
        out = d if out is None else out + d
    return out


def _batched_kernel(vals_ref, cols_ref, segs_ref, out_hbm, b_ref, acc_ref,
                    sem, *, n_hat: int):
    c = pl.program_id(0)
    e = pl.program_id(1)
    last_e = e == pl.num_programs(1) - 1

    @pl.when((c == 0) & (e == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(e == 0)
    def _fresh_chunk():
        b_ref[...] = jnp.zeros_like(b_ref)

    R, n_pad = b_ref.shape
    b_ref[...] += _densify_tile(vals_ref[0], cols_ref[0], segs_ref[0],
                                R=R, n_pad=n_pad, n_hat=n_hat)

    @pl.when(last_e)
    def _gram():
        b = b_ref[...]
        acc_ref[...] += jax.lax.dot_general(
            b, b,
            dimension_numbers=(((0,), (0,)), ((), ())),     # contract rows
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    # The accumulator is a single VMEM scratch written back once: an output
    # block would be double-buffered by the pipeline (2 n_pad^2 words).
    @pl.when(last_e & (c == pl.num_programs(0) - 1))
    def _writeback():
        cp = pltpu.make_async_copy(acc_ref, out_hbm, sem)
        cp.start()
        cp.wait()


def csr_gram_batched_pallas(
    values: jax.Array,
    local_cols: jax.Array,
    seg_ids: jax.Array,
    n_rows: int,
    n_hat: int,
    *,
    interpret: bool = False,
):
    """Megabatch Gram ``G = sum_c B_c^T B_c`` over C chunks in ONE launch.

    ``values``/``local_cols``/``seg_ids`` are (C, E); ``seg_ids`` are
    chunk-local rows in [0, n_rows); ``local_cols`` entries >= n_hat are
    dropped (off-support sentinel).  Returns (n_hat, n_hat) f32.
    """
    C, E = values.shape
    assert local_cols.shape == (C, E) and seg_ids.shape == (C, E)
    n_pad = max(128, ((n_hat + 127) // 128) * 128)
    R = ((max(n_rows, 8) + 7) // 8) * 8
    v, cols, segs = _entry_rows(values, local_cols, seg_ids)
    rows = v.shape[1]
    ent = pl.BlockSpec((1, _TILE_ROWS, 128), lambda c, e: (c, e, 0))
    G = pl.pallas_call(
        functools.partial(_batched_kernel, n_hat=n_hat),
        grid=(C, rows // _TILE_ROWS),
        in_specs=[ent, ent, ent],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((R, n_pad), jnp.float32),
            pltpu.VMEM((n_pad, n_pad), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
        name="csr_gram_megabatch",
        cost_estimate=pl.CostEstimate(
            flops=C * (2 * R * n_pad * n_pad + 2 * R * n_pad * rows * 128),
            bytes_accessed=(3 * C * rows * 128 + n_pad * n_pad) * 4,
            transcendentals=0,
        ),
    )(v, cols, segs)
    return G[:n_hat, :n_hat]


def _kernel(vals_ref, cols_ref, segs_ref, out_ref, b_ref, *, n_hat: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    n_tiles, R, _ = b_ref.shape

    @pl.when((i == 0) & (j == 0))
    def _scatter():
        b_ref[...] = jnp.zeros_like(b_ref)

        def body(g, _):
            r0 = pl.multiple_of(g * _TILE_ROWS, _TILE_ROWS)
            d = _densify_tile(
                vals_ref[0, pl.ds(r0, _TILE_ROWS), :],
                cols_ref[0, pl.ds(r0, _TILE_ROWS), :],
                segs_ref[0, pl.ds(r0, _TILE_ROWS), :],
                R=R, n_pad=n_tiles * 128, n_hat=n_hat,
            )
            for t in range(n_tiles):        # lane-aligned static splits
                b_ref[t] += d[:, t * 128:(t + 1) * 128]
            return 0

        jax.lax.fori_loop(0, vals_ref.shape[1] // _TILE_ROWS, body, 0)

    out_ref[...] = jax.lax.dot_general(
        b_ref[i], b_ref[j],
        dimension_numbers=(((0,), (0,)), ((), ())),   # contract rows
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def csr_gram_pallas(
    values: jax.Array,
    local_cols: jax.Array,
    seg_ids: jax.Array,
    n_rows: int,
    n_hat: int,
    *,
    interpret: bool = False,
):
    """Single-chunk Gram ``G[a, b] = sum_r B[r, a] B[r, b]`` where ``B`` is
    the (n_rows, n_hat) densification of the chunk on the support — the
    large-``n_hat`` fallback of the megabatch scheme (its tiled output
    never holds the full Gram in VMEM).

    ``seg_ids`` must be chunk-local rows in [0, n_rows); ``local_cols``
    entries >= n_hat are dropped (off-support sentinel).  Returns
    (n_hat, n_hat) f32.
    """
    (E,) = values.shape
    assert local_cols.shape == (E,) and seg_ids.shape == (E,)
    n_pad = max(128, ((n_hat + 127) // 128) * 128)
    n_tiles = n_pad // 128
    R = ((max(n_rows, 8) + 7) // 8) * 8
    v, cols, segs = _entry_rows(values.reshape(1, E), local_cols.reshape(1, E),
                                seg_ids.reshape(1, E))
    rows = v.shape[1]
    ent = pl.BlockSpec((1, rows, 128), lambda i, j: (0, 0, 0))
    G = pl.pallas_call(
        functools.partial(_kernel, n_hat=n_hat),
        grid=(n_tiles, n_tiles),
        in_specs=[ent, ent, ent],
        out_specs=pl.BlockSpec((128, 128), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_tiles, R, 128), jnp.float32)],
        interpret=interpret,
        name="csr_gram_chunk",
        cost_estimate=pl.CostEstimate(
            flops=2 * R * n_pad * n_pad + 2 * R * n_pad * rows * 128,
            bytes_accessed=(3 * rows * 128 + n_pad * n_pad) * 4,
            transcendentals=0,
        ),
    )(v, cols, segs)
    return G[:n_hat, :n_hat]


def csr_gram_megabatch_pallas(
    values: jax.Array,
    local_cols: jax.Array,
    seg_ids: jax.Array,
    n_rows: int,
    n_hat: int,
    *,
    interpret: bool = False,
):
    """Megabatch Gram on whichever scheme fits VMEM: the one-launch
    resident-G kernel (`batched_gram_fits`), else one tiled single-chunk
    launch per chunk (the pre-megabatch economics, correct at any n_hat)."""
    C, E = values.shape
    if batched_gram_fits(n_hat, n_rows, E):
        return csr_gram_batched_pallas(values, local_cols, seg_ids, n_rows,
                                       n_hat, interpret=interpret)
    G = None
    for c in range(C):
        g = csr_gram_pallas(values[c], local_cols[c], seg_ids[c], n_rows,
                            n_hat, interpret=interpret)
        G = g if G is None else G + g
    return G
