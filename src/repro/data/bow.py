"""Streaming bag-of-words statistics.

The corpora the paper targets don't fit in memory ("These data matrices are
so large that we cannot even load them into memory all at once"), so both
pipeline legs are streaming, single-pass, batch-at-a-time:

  StreamingStats  — per-word sum/sumsq for the Thm 2.1 variance screen
  StreamingGram   — A_S^T A_S on the post-elimination support

Each accumulator has two input legs sharing one accumulator state (the
`StreamingAccumulator` protocol, so the legs cannot drift apart):

  update(block)           — dense row blocks (what `Corpus.batches`
                            yields), routed through the dense Pallas
                            kernels;
  update_csr(chunk)       — fixed-shape padded `CSRChunk`s from the
                            sharded store (`repro.sparse.store`), routed
                            through the CSR Pallas kernels — O(nnz),
                            never densifying;
  update_csr_batch(mb)    — a `CSRMegaBatch` of C chunks folded in with
                            ONE kernel dispatch (the PR-5 ingestion hot
                            path: O(passes/C) launches per pass).

Both accumulators are trivially mergeable across hosts/pods — `merge`
(device-side for the Gram: jnp adds, one host transfer at finalize), or a
single psum at finalise time (see core.distributed), or
`core.elimination.combine_screens` on finalized Screens.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.elimination import Screen, select_support
from repro.kernels import ops
from repro.obs import metrics, trace


class SupportRemap:
    """Global column id -> position in a Gram support, through an int32
    table built once per support; entries off the support get the
    ``n_hat`` sentinel the kernel/oracle drop.  Vectorized over any
    entry-array shape (one chunk, a megabatch, or a mesh superbatch).  The
    single implementation behind ``StreamingGram`` and the mesh Gram pass.

    The table ends one past the largest support word, and the gather
    clips into it through a ``uint32`` view, so negative ids and ids past
    the support both land on the sentinel."""

    def __init__(self, support: np.ndarray):
        support = np.asarray(support)
        self.n_hat = int(support.size)
        top = int(support.max()) + 2 if support.size else 1
        self.table = np.full(top, self.n_hat, np.int32)
        self.table[support] = np.arange(self.n_hat, dtype=np.int32)

    def cols(self, col_ids: np.ndarray) -> np.ndarray:
        """Support positions of int32 ``col_ids`` (any shape)."""
        ids = np.asarray(col_ids, np.int32)
        return np.take(self.table, ids.view(np.uint32), mode="clip")

    def __call__(self, col_ids: np.ndarray, nnz) -> np.ndarray:
        """`cols`, counting the ``nnz`` live entries into
        ``ingest.gram_entries`` and those on the support into
        ``ingest.gram_support_entries``.  Padded slots hold col 0, so they
        are taken off the on-support count when word 0 is on the
        support."""
        local = self.cols(col_ids)
        live = int(np.sum(nnz))
        on = int(np.count_nonzero(local < self.n_hat))
        if self.table[0] < self.n_hat:
            on -= local.size - live
        metrics.counter("ingest.gram_entries").inc(live)
        metrics.counter("ingest.gram_support_entries").inc(on)
        return local


class StreamingAccumulator:
    """Shared update/merge/finalize protocol for one-pass reductions.

    Subclasses declare their summed state in ``_acc_fields`` (plus the
    always-present ``count``) and implement the two update legs; ``merge``
    is the one shared implementation, so the dense-block and CSR-chunk
    paths accumulate into — and pool — identical state.
    """

    _acc_fields: tuple[str, ...] = ()

    def update(self, batch) -> "StreamingAccumulator":
        """Fold in a dense (rows, n) row block."""
        raise NotImplementedError

    def update_csr(self, chunk) -> "StreamingAccumulator":
        """Fold in a `repro.sparse.store.CSRChunk` (fixed-shape, padded)."""
        raise NotImplementedError

    def update_csr_batch(self, mb) -> "StreamingAccumulator":
        """Fold in a `repro.sparse.store.CSRMegaBatch` of C chunks with a
        single kernel dispatch; the feed's spans carry its ``index`` as
        ``b``."""
        raise NotImplementedError

    def merge(self, other: "StreamingAccumulator") -> "StreamingAccumulator":
        assert type(self) is type(other), (type(self), type(other))
        self._check_mergeable(other)
        for f in self._acc_fields:
            setattr(self, f, getattr(self, f) + getattr(other, f))
        self.count += other.count
        return self

    def finalize(self, **kw):
        raise NotImplementedError

    def _check_mergeable(self, other) -> None:
        pass

    # -- resume support (sparse/resume.py) --------------------------------
    # The same summed moments `merge` pools, exported as host arrays so a
    # killed pass can checkpoint them at a megabatch boundary and a resumed
    # pass can re-load them — state_dict/load_state round-trip exactly, and
    # state_signature() is the JSON-able identity a checkpoint is only
    # valid against (same accumulator kind + shape + dtype).

    def state_dict(self) -> dict:
        """Summed state as np.savez-able host arrays."""
        raise NotImplementedError

    def load_state(self, state: dict) -> "StreamingAccumulator":
        """Restore state produced by an equal-signature ``state_dict``."""
        raise NotImplementedError

    def state_signature(self) -> dict:
        """JSON-able configuration identity; checkpoints from accumulators
        with a different signature must be ignored, not loaded."""
        raise NotImplementedError


class StreamingStats(StreamingAccumulator):
    """One-pass per-column mean/variance accumulator."""

    _acc_fields = ("sum", "sumsq")

    def __init__(self, n_features: int, *, impl: str = "auto"):
        self.n = n_features
        self.impl = impl
        self.sum = np.zeros(n_features, np.float64)
        self.sumsq = np.zeros(n_features, np.float64)
        self.count = 0

    def update(self, batch) -> "StreamingStats":
        s, ss = ops.column_stats(jnp.asarray(batch), impl=self.impl)
        self.sum += np.asarray(s, np.float64)
        self.sumsq += np.asarray(ss, np.float64)
        self.count += batch.shape[0]
        return self

    def update_csr(self, chunk) -> "StreamingStats":
        s, ss = ops.csr_column_stats(
            chunk.values, chunk.col_ids, n=self.n, impl=self.impl,
            nnz=chunk.nnz,
        )
        self.sum += np.asarray(s, np.float64)
        self.sumsq += np.asarray(ss, np.float64)
        self.count += chunk.n_rows   # empty rows count, padded slots don't
        return self

    def update_csr_batch(self, mb) -> "StreamingStats":
        """C chunks -> ONE kernel dispatch (and one host f64 fold).  The
        fold is an ``ingest.readback`` span, opened after the kernel has
        finished (while tracing), so it holds only the copy and the add."""
        s, ss = ops.csr_column_stats(
            mb.values, mb.col_ids, n=self.n, impl=self.impl, nnz=mb.nnz,
            b=mb.index,
        )
        trace.device_sync((s, ss))
        with trace.span("ingest.readback", b=mb.index):
            self.sum += np.asarray(s, np.float64)
            self.sumsq += np.asarray(ss, np.float64)
        self.count += int(np.sum(mb.n_rows))
        return self

    def _check_mergeable(self, other) -> None:
        assert self.n == other.n

    def state_dict(self) -> dict:
        return {
            "sum": self.sum.copy(),
            "sumsq": self.sumsq.copy(),
            "count": np.asarray(self.count, np.int64),
        }

    def load_state(self, state: dict) -> "StreamingStats":
        self.sum = np.asarray(state["sum"], np.float64).copy()
        self.sumsq = np.asarray(state["sumsq"], np.float64).copy()
        self.count = int(state["count"])
        return self

    def state_signature(self) -> dict:
        return {"acc": "stats", "n": int(self.n)}

    def finalize(self, *, center: bool = True) -> Screen:
        m = max(self.count, 1)   # guards the division only
        mean = self.sum / m if center else np.zeros(self.n)
        var = np.maximum(self.sumsq / m - mean**2, 0.0)
        # True count, host int64: an empty accumulator must pool with
        # weight 0, and jnp.asarray would overflow int32 past 2^31 rows
        # with x64 off.
        return Screen(
            variances=jnp.asarray(var),
            means=jnp.asarray(mean),
            count=np.asarray(self.count, np.int64),
        )


class StreamingGram(StreamingAccumulator):
    """One-pass reduced gram accumulator over the surviving columns.

    The summed state ``g`` is a DEVICE array: every update and every
    `merge` is a jnp add, so a pass never round-trips the (k, k) gram
    through host memory per chunk — the single host transfer happens in
    `finalize`, mirroring `combine_screens`' device-side moment merge.
    Under x64 the accumulator is f64 (matching the old host fold); when
    x64 is off it is f32 with Neumaier compensation (``_err`` carries the
    rounding loss of every add), so the error bound stays independent of
    the chunk count either way.
    """

    def __init__(self, support: np.ndarray, *, impl: str = "auto",
                 chunk_rows: int = 512, acc_dtype=None):
        self.support = np.asarray(support)
        k = self.support.size
        dtype = jax.dtypes.canonicalize_dtype(
            np.float64 if acc_dtype is None else acc_dtype
        )
        self.g = jnp.zeros((k, k), dtype)
        self._err = jnp.zeros((k, k), dtype) if dtype == jnp.float32 else None
        self.count = 0
        self.impl = impl
        self.chunk_rows = chunk_rows
        self._remap = SupportRemap(self.support)

    def _acc(self, delta) -> None:
        """Fold one partial gram into ``g`` — compensated when f32."""
        delta = jnp.asarray(delta, self.g.dtype)
        if self._err is None:
            self.g = self.g + delta
            return
        t = self.g + delta
        big = jnp.abs(self.g) >= jnp.abs(delta)
        self._err = self._err + jnp.where(
            big, (self.g - t) + delta, (delta - t) + self.g
        )
        self.g = t

    def update(self, batch) -> "StreamingGram":
        cols = jnp.asarray(batch)[:, self.support]
        self._acc(ops.gram(cols, impl=self.impl))
        self.count += batch.shape[0]
        return self

    def _check_rows(self, n_rows: int) -> None:
        if n_rows > self.chunk_rows:
            raise ValueError(
                f"chunk has {n_rows} rows > chunk_rows="
                f"{self.chunk_rows}; iterate the store with "
                f"chunk_rows <= the accumulator's"
            )

    def update_csr(self, chunk) -> "StreamingGram":
        self._check_rows(chunk.n_rows)
        if self.support.size == 0:
            self.count += chunk.n_rows
            return self
        self._acc(ops.csr_gram(
            chunk.values, self._remap(chunk.col_ids, chunk.nnz), chunk.seg_ids,
            n_rows=self.chunk_rows, n_hat=self.support.size, impl=self.impl,
            nnz=chunk.nnz,
        ))
        self.count += chunk.n_rows
        return self

    def update_csr_batch(self, mb) -> "StreamingGram":
        """C chunks -> ONE kernel dispatch, accumulated on device.  The
        support-column remap is an ``ingest.prep`` span."""
        self._check_rows(int(np.max(mb.n_rows, initial=0)))
        if self.support.size == 0:
            self.count += int(np.sum(mb.n_rows))
            return self
        with trace.span("ingest.prep", b=mb.index):
            local = self._remap(mb.col_ids, mb.nnz)
        self._acc(ops.csr_gram_batched(
            mb.values, local, mb.seg_ids,
            n_rows=self.chunk_rows, n_hat=self.support.size, impl=self.impl,
            nnz=mb.nnz, b=mb.index,
        ))
        self.count += int(np.sum(mb.n_rows))
        return self

    def merge(self, other: "StreamingGram") -> "StreamingGram":
        # Overrides the shared field-sum merge: the compensated fold must
        # route the other partial's gram through _acc (device-side adds
        # either way, matching the protocol contract).
        assert type(self) is type(other), (type(self), type(other))
        self._check_mergeable(other)
        if self._err is not None:       # dtypes match, so _err does too
            self._err = self._err + other._err
        self._acc(other.g)
        self.count += other.count
        return self

    def _check_mergeable(self, other) -> None:
        assert np.array_equal(self.support, other.support)
        # mixed accumulator dtypes would silently downcast one partial
        # (and drop its compensation) — fail loudly like every other
        # partial mismatch instead
        assert self.g.dtype == other.g.dtype, (self.g.dtype, other.g.dtype)

    def state_dict(self) -> dict:
        # np.asarray(g) blocks on the device value — a checkpoint is a
        # synchronization point by construction, so the saved moments are
        # exactly what the completed megabatches folded in.
        d = {
            "g": np.asarray(self.g),
            "count": np.asarray(self.count, np.int64),
        }
        if self._err is not None:
            d["err"] = np.asarray(self._err)
        return d

    def load_state(self, state: dict) -> "StreamingGram":
        self.g = jnp.asarray(np.asarray(state["g"]), self.g.dtype)
        if self._err is not None:
            self._err = (
                jnp.asarray(np.asarray(state["err"]), self.g.dtype)
                if "err" in state else jnp.zeros_like(self.g)
            )
        self.count = int(state["count"])
        return self

    def state_signature(self) -> dict:
        return {
            "acc": "gram",
            "n_hat": int(self.support.size),
            "support_crc": int(
                zlib.crc32(np.ascontiguousarray(self.support).tobytes())
                & 0xFFFFFFFF
            ),
            "dtype": str(self.g.dtype),
        }

    def finalize(self, *, means: np.ndarray | None = None) -> np.ndarray:
        m = max(self.count, 1)
        g = np.asarray(self.g, np.float64)   # the ONE host transfer
        if self._err is not None:            # re-inject the compensation
            g = g + np.asarray(self._err, np.float64)
        if means is not None:
            mu = np.asarray(means)[self.support]
            g = g - m * np.outer(mu, mu)
        return g / m


def screen_and_gram_streaming(batches, n_features: int, lam: float,
                              *, center: bool = True, impl: str = "auto",
                              max_reduced: int = 2048):
    """Two-pass pipeline over a re-iterable batch source.

    Pass 1: variance screen; pass 2: reduced gram.  Returns
    (Sigma_hat, support, screen)."""
    stats = StreamingStats(n_features, impl=impl)
    for b in batches():
        stats.update(b)
    screen = stats.finalize(center=center)
    support = select_support(screen.variances, lam, max_reduced)
    gram = StreamingGram(support, impl=impl)
    for b in batches():
        gram.update(b)
    Sigma_hat = gram.finalize(means=np.asarray(screen.means) if center else None)
    return Sigma_hat, support, screen
