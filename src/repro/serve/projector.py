"""Pack fitted sparse PCs into a gather representation and serve projections.

A fitted component is a sparse vector in R^n (n ~ 10^5) with card ~ 5
nonzeros.  Serving never touches n-sized dense loadings: ``pack_components``
extracts each component's (support, values) pair into padded (k, cap)
arrays — ``cap`` is the max cardinality rounded up so re-fits with slightly
different cardinalities reuse the same jitted program — and ``TopicProjector``
pushes batches through ``kernels.ops.sparse_project`` (the Pallas
gather-matvec on TPU, its jnp gather oracle elsewhere).

``TopicProjector.project`` follows its input's type.  A dense ``(B, n)``
array is copied as it is and gathered from.  A ``serve.batcher.SparseBatch``
(what the microbatcher hands it) is folded on the host into a
``(rows, ncols)`` matrix of the model's own support columns — ``ncols`` the
number of distinct support words rounded up to 128 — and the gather reads
the remapped slots there, so a batch's copy is ``rows * ncols`` floats
instead of ``rows * n`` and no n-wide matrix is built.  The scores are the
same sums in the same slot order on either path.

Luss & d'Aspremont (2008): sparse PCs double as feature selectors / cluster
assigners, so the projector also exposes ``assign_topics`` (argmax score)
and a sparse-document path ``project_docs`` that maps raw (word_id, count)
pairs straight into the packed coordinate system without materialising any
n-length vector — O(doc nnz) per document.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spca import PCResult
from repro.kernels import ops
from repro.obs import metrics, trace
from repro.serve.batcher import SparseBatch


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@dataclass(frozen=True)
class ProjectorPack:
    """Gather representation of k sparse components over an n-word vocab.

    ``support_idx[c, j]`` is the word id of component c's j-th loading and
    ``values[c, j]`` its weight; slots past a component's cardinality hold
    (0, 0.0) — index 0 with weight exactly 0.0, so padded slots contribute
    nothing whichever column they gather.
    """

    support_idx: np.ndarray  # (k, cap) int32
    values: np.ndarray       # (k, cap) float32
    n_features: int

    @property
    def k(self) -> int:
        return int(self.support_idx.shape[0])

    @property
    def cap(self) -> int:
        return int(self.support_idx.shape[1])

    @property
    def nnz(self) -> int:
        return int(np.count_nonzero(self.values))


def pack_components(
    results: list[PCResult], *, n_features: int | None = None,
    cap_multiple: int = 8,
) -> ProjectorPack:
    """Pack ``fit_components`` output into a ``ProjectorPack``.

    ``cap`` = max cardinality rounded up to ``cap_multiple`` so the packed
    shapes (and therefore every downstream jitted program) are stable across
    refits whose cardinalities wobble within the slack.
    """
    if not results:
        raise ValueError("cannot pack an empty component list")
    n = n_features if n_features is not None else int(results[0].x.shape[0])
    cap = _round_up(max(max(r.cardinality, 1) for r in results), cap_multiple)
    k = len(results)
    support_idx = np.zeros((k, cap), np.int32)
    values = np.zeros((k, cap), np.float32)
    for c, r in enumerate(results):
        s = np.asarray(r.support, np.int64)
        support_idx[c, : s.size] = s
        values[c, : s.size] = np.asarray(r.x)[s]
    return ProjectorPack(support_idx=support_idx, values=values, n_features=n)


class TopicProjector:
    """Jitted batched document->topic projection for one packed model.

    The projection function is jitted once per input shape; the
    microbatcher always presents one fixed ``SparseBatch`` shape, so
    steady-state serving never recompiles.  ``trace_count`` counts
    retraces (the shape-stability tests assert it stays at 1).

    The projector owns its support-column map (word id -> compact column,
    -1 off the support), so a hot swap never serves a batch with another
    model's columns.
    """

    def __init__(self, pack: ProjectorPack, *, impl: str = "auto"):
        self.pack = pack
        self.impl = impl
        self.trace_count = 0
        vals = jnp.asarray(pack.values)

        def _jit(sidx):
            def _project(X):
                self.trace_count += 1  # python side effect: per trace only
                return ops.sparse_project(X, sidx, vals, impl=impl)
            return jax.jit(_project)

        self._project = _jit(jnp.asarray(pack.support_idx))
        # Support columns: each distinct live support word owns one column
        # (shared when supports overlap); padded slots keep weight 0.0 and
        # read column 0.
        live_slot = pack.values != 0
        words = np.unique(pack.support_idx[live_slot])
        self._ncols = _round_up(max(words.size, 1), 128)
        self._word_col = np.full(pack.n_features, -1, np.int32)
        self._word_col[words] = np.arange(words.size, dtype=np.int32)
        col_idx = np.where(live_slot, self._word_col[pack.support_idx], 0)
        self._project_cols = _jit(jnp.asarray(col_idx.astype(np.int32)))
        # Word id -> packed slot(s), sorted-CSR style, for the sparse-doc
        # fast path.  A word may own several slots when component supports
        # overlap (Hotelling 'project' deflation does not guarantee the
        # disjoint supports 'remove' deflation produces).
        flat = pack.support_idx.reshape(-1)
        live = np.flatnonzero(pack.values.reshape(-1) != 0)
        order = np.argsort(flat[live], kind="stable")
        self._sorted_words = flat[live][order]   # (nnz,) ascending word ids
        self._sorted_slots = live[order]         # (nnz,) their flat slots

    def project(self, X) -> jax.Array:
        """(B, n) counts, or a ``SparseBatch`` of B rows -> (B, k) scores.
        The batch's copy to the device is a ``serve.h2d`` span, which ends
        on the landed copy while tracing (and on its dispatch otherwise)."""
        if isinstance(X, SparseBatch):
            return self._project_batch(X)
        with trace.span("serve.h2d"):
            X = trace.device_sync(jnp.asarray(X))
        return self._project(X)

    def _project_batch(self, batch: SparseBatch) -> jax.Array:
        """Fold the batch's entries into its support columns, copy that
        ``(rows, ncols)`` matrix and gather from it.  Entries off the
        support are dropped; ``serve.support_entries`` over
        ``serve.batch_entries`` is the share kept."""
        col = self._word_col[batch.word_ids]
        on = col >= 0
        Xc = np.zeros((batch.rows, self._ncols), np.float32)
        np.add.at(Xc, (batch.row_ids[on], col[on]), batch.counts[on])
        metrics.counter("serve.compact_batches").inc()
        metrics.counter("serve.batch_entries").inc(int(col.size))
        metrics.counter("serve.support_entries").inc(int(on.sum()))
        with trace.span("serve.h2d"):
            Xc = trace.device_sync(jnp.asarray(Xc))
        return self._project_cols(Xc)

    def project_docs(self, docs) -> np.ndarray:
        """Sparse path: ``docs`` is a list of (word_ids, counts) pairs.

        Work is O(total doc nnz + slot hits): each (word, count) lands in
        *every* packed slot that word owns (supports may overlap under
        'project' deflation) via binary search on the sorted slot table,
        then a (B, k*cap) x (k*cap,) weighted fold produces the scores.
        No n-length buffer anywhere.
        """
        k, cap = self.pack.k, self.pack.cap
        G = np.zeros((len(docs), k * cap), np.float32)
        for d, (wi, ct) in enumerate(docs):
            wi = np.asarray(wi, np.int64)
            lo = np.searchsorted(self._sorted_words, wi, side="left")
            hi = np.searchsorted(self._sorted_words, wi, side="right")
            reps = hi - lo                      # slots owned per doc word
            if not reps.any():
                continue
            total = int(reps.sum())
            starts = np.cumsum(reps) - reps
            # flat indices [lo_j, hi_j) for every doc word j, concatenated
            r = (np.arange(total) - np.repeat(starts, reps)
                 + np.repeat(lo, reps))
            np.add.at(G[d], self._sorted_slots[r],
                      np.repeat(np.asarray(ct, np.float32), reps))
        g = G.reshape(len(docs), k, cap)
        return np.einsum("bkc,kc->bk", g, self.pack.values)

    def assign_topics(self, scores) -> tuple[np.ndarray, np.ndarray]:
        """Cluster interpretation: (topic id, |score|) per document."""
        s = np.abs(np.asarray(scores))
        top = np.argmax(s, axis=1)
        return top, s[np.arange(s.shape[0]), top]
