"""Device-mesh parallel streaming passes — the data-parallel leg of the
out-of-core SPCA pipeline.

`engine.py` drains the megabatch stream into ONE accumulator on ONE
device.  This module partitions the same stream across the local device
mesh (`launch.mesh.make_data_mesh` — a 1-D pure data axis): D consecutive
megabatches are packed into a (D, C, chunk_nnz) *superbatch*, transferred
once, and folded by a single `shard_map` step in which every device
updates its own resident accumulator slot.  Nothing crosses the mesh
during the pass; the (D, ...) partial moments merge once at finalize via
`core.distributed.psum_partials` (device-side psum, one host transfer) —
the same math `combine_screens` / `StreamingGram.merge` already guarantee,
so a D-device pass reproduces the single-device moments to roundoff.

Pass economics: a pass over B megabatches costs ceil(B/D) dispatches
instead of B — on a real mesh the folds also run concurrently; off-TPU
(forced host devices) the win is dispatch/sync amortization, which is
exactly what the gated ``mesh_*`` bench rows measure.  Corpus passes stay
1 + 1 for a K-component fit (`mesh_sparse_stats` mirrors
`engine.sparse_stats`' (variances, build) contract, covariance cache
included).

Accumulator dtype mirrors `StreamingGram`: f64 under x64, else f32 with a
Neumaier compensation slot per device (the compensated fold runs inside
the sharded step, so the error bound is independent of both the chunk
count and D).

Observability: the whole drain runs under an ``ingest.shard_pass`` span
(child of the usual ``ingest.screen_pass`` / ``ingest.gram_pass``), with
the single-device path's feed spans per superbatch (``ingest.read``,
``feed_wait``, ``prep`` for the Gram pass's support remap, and ``h2d`` for
the ``device_put`` calls and their block; no ``readback``, the partials
stay on the devices), the ``mesh.devices`` gauge records the topology,
and per-device lane counters
(``ingest.shard.chunks`` / ``ingest.shard.nnz``) accumulate in per-lane
registries merged into the global one at pass end via `Registry.merge` —
the same pooling a real multi-process mesh would do over scraped
snapshots.

Resume: checkpoints store the stacked (D, ...) per-device moments at
superbatch boundaries; `pass_fingerprint` gains the device topology
(``n_devices``), so a cursor written at one D never restores at another.

Degraded mode: a sharded pass that dies with a runtime dispatch error
(XLA OOM, transfer failure — anything `core.bcd.is_dispatch_error`
accepts) is retried WHOLE at half the device count, halving down to
``min_devices`` and finally falling to the single-device engine path.
Each step records ``mesh.degraded`` (registry + ``counters``) and, because
the fingerprint carries ``n_devices``, restarts cleanly at the new
topology rather than restoring a cursor shaped for the old one.  Data
corruption (`store.ShardCorruptionError`) propagates untouched — fewer
devices cannot fix bad bytes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.distributed import psum_partials
from repro.core.elimination import Screen, combine_screens
from repro.data.bow import SupportRemap
from repro.kernels import ops as kernel_ops
from repro.kernels import ref
from repro.kernels.csr_gram import csr_gram_megabatch_pallas
from repro.kernels.csr_stats import csr_column_stats_pallas
from repro.launch.mesh import make_data_mesh
from repro.obs import metrics, trace

from .engine import (
    DEFAULT_MEGABATCH, DEFAULT_PREFETCH, _bump, _count, _feed,
    _stream_prefetch_stats, _reliability,
)
from .resume import DEFAULT_CHECKPOINT_EVERY, pass_fingerprint
from .store import DEFAULT_CHUNK_NNZ, DEFAULT_CHUNK_ROWS, SparseCorpus


# ---------------------------------------------------------------------------
# superbatches: D megabatches in one host-side package


class CSRSuperBatch(NamedTuple):
    """D megabatches stacked lane-per-device — what ONE sharded dispatch
    consumes.  Lane ``d`` holds megabatch ``b*D + d`` of the pass and obeys
    the `CSRMegaBatch` padding contract slot-by-slot; a ragged final
    superbatch pads with empty lanes (all-zero, additively harmless), so
    the jit trace never changes.  Arrays are views into the packer's
    buffer ring — valid until ``ring`` more superbatches are drawn."""

    values: np.ndarray    # (D, C, chunk_nnz) float32
    col_ids: np.ndarray   # (D, C, chunk_nnz) int32, global column ids
    seg_ids: np.ndarray   # (D, C, chunk_nnz) int32, chunk-local row ids
    n_rows: np.ndarray    # (D, C) int32 real rows per slot
    nnz: np.ndarray       # (D, C) int64 real entries per slot
    lanes: int            # real megabatches packed (<= D)
    n_chunks: int         # total real chunks across lanes
    lane_chunks: tuple    # per-lane real chunk counts
    lane_nnz: tuple       # per-lane real nnz
    index: int = 0        # pass position of lane 0's megabatch (``b``)


def _iter_superbatches(store: SparseCorpus, *, devices: int, chunk_nnz: int,
                       chunk_rows: int, megabatch: int, host_id: int,
                       num_hosts: int, ring: int, start_batch: int):
    """Pack D consecutive megabatches per yield into a rotating ring of
    (D, C, chunk_nnz) host buffers.  The inner megabatch views are copied
    into the superbatch immediately, so the store iterator only needs its
    minimal ring; ``start_batch`` is in megabatches (the resume cursor) —
    lane assignment after a mid-pass resume may differ from the original
    run, which is invisible to the final moments (the merge is a sum)."""
    D = int(devices)
    it = store.iter_megabatches(
        chunk_nnz=chunk_nnz, chunk_rows=chunk_rows, megabatch=megabatch,
        host_id=host_id, num_hosts=num_hosts, ring=2,
        start_batch=start_batch,
    )
    ring = max(2, ring)
    bufs = [
        dict(
            values=np.zeros((D, megabatch, chunk_nnz), np.float32),
            col_ids=np.zeros((D, megabatch, chunk_nnz), np.int32),
            seg_ids=np.zeros((D, megabatch, chunk_nnz), np.int32),
            n_rows=np.zeros((D, megabatch), np.int32),
            nnz=np.zeros((D, megabatch), np.int64),
        )
        for _ in range(ring)
    ]
    slot = 0
    index = int(start_batch)
    done = False
    while not done:
        b = bufs[slot]
        lanes = 0
        chunks = 0
        lane_chunks = []
        lane_nnz = []
        for d in range(D):
            mb = next(it, None)
            if mb is None:
                done = True
                break
            b["values"][d] = mb.values
            b["col_ids"][d] = mb.col_ids
            b["seg_ids"][d] = mb.seg_ids
            b["n_rows"][d] = mb.n_rows
            b["nnz"][d] = mb.nnz
            lanes += 1
            chunks += int(mb.n_chunks)
            lane_chunks.append(int(mb.n_chunks))
            lane_nnz.append(int(np.sum(mb.nnz)))
        if lanes == 0:
            return
        for d in range(lanes, D):   # ragged tail: zero the stale lanes
            b["values"][d] = 0.0
            b["col_ids"][d] = 0
            b["seg_ids"][d] = 0
            b["n_rows"][d] = 0
            b["nnz"][d] = 0
        yield CSRSuperBatch(
            values=b["values"], col_ids=b["col_ids"], seg_ids=b["seg_ids"],
            n_rows=b["n_rows"], nnz=b["nnz"], lanes=lanes, n_chunks=chunks,
            lane_chunks=tuple(lane_chunks), lane_nnz=tuple(lane_nnz),
            index=index,
        )
        index += lanes
        slot = (slot + 1) % ring


# ---------------------------------------------------------------------------
# sharded fold steps (one jit trace per (D, geometry), cached for reuse
# across passes and bench reps)


@functools.lru_cache(maxsize=None)
def _data_mesh(n_devices: int):
    return make_data_mesh(n_devices)


def _use_pallas(impl: str) -> bool:
    return impl == "pallas" or (
        impl == "auto" and jax.default_backend() == "tpu"
    )


def _comp_add(acc, delta, err):
    """Neumaier-compensated ``acc += delta`` (same fold as
    `StreamingGram._acc`, expressed functionally for the sharded step)."""
    t = acc + delta
    big = jnp.abs(acc) >= jnp.abs(delta)
    err = err + jnp.where(big, (acc - t) + delta, (delta - t) + acc)
    return t, err


@functools.lru_cache(maxsize=None)
def _stats_step(devices: int, n: int, use_pallas: bool):
    mesh = _data_mesh(devices)
    interpret = jax.default_backend() != "tpu"

    def device_fold(s, ss, es, ess, values, col_ids):
        # blocks: accumulators (1, n), entries (1, C, E) — this device's
        # lane of the superbatch folded into its resident slot.
        if use_pallas:
            ps, pss = csr_column_stats_pallas(
                values[0], col_ids[0], n, interpret=interpret
            )
        else:
            ps, pss = ref.csr_column_stats_batched_ref(
                values[0], col_ids[0], n
            )
        s, es = _comp_add(s, ps[None].astype(s.dtype), es)
        ss, ess = _comp_add(ss, pss[None].astype(ss.dtype), ess)
        return s, ss, es, ess

    acc = P("data", None)
    ent = P("data", None, None)
    # check_vma=False: a pallas_call's outputs carry no varying-axes
    # annotation, and every output here is per-device (P("data", ...)).
    return jax.jit(jax.shard_map(
        device_fold, mesh=mesh,
        in_specs=(acc,) * 4 + (ent,) * 2, out_specs=(acc,) * 4,
        check_vma=False,
    ))


@functools.lru_cache(maxsize=None)
def _gram_step(devices: int, chunk_rows: int, n_hat: int, use_pallas: bool):
    mesh = _data_mesh(devices)
    interpret = jax.default_backend() != "tpu"

    def device_fold(g, err, values, local_cols, seg_ids):
        if use_pallas:
            pg = csr_gram_megabatch_pallas(
                values[0], local_cols[0], seg_ids[0], chunk_rows, n_hat,
                interpret=interpret,
            )
        else:
            pg = ref.csr_gram_batched_ref(
                values[0], local_cols[0], seg_ids[0], chunk_rows, n_hat
            )
        return _comp_add(g, pg[None].astype(g.dtype), err)

    acc = P("data", None, None)
    ent = P("data", None, None)
    return jax.jit(jax.shard_map(
        device_fold, mesh=mesh,
        in_specs=(acc,) * 2 + (ent,) * 3, out_specs=(acc,) * 2,
        check_vma=False,                # as in `_stats_step`
    ))


# ---------------------------------------------------------------------------
# device-resident accumulators


class MeshStats:
    """`StreamingStats` sharded lane-per-device: per-device (sum, sumsq)
    partials stay resident across the whole pass; `pooled` merges them
    with one psum + one host transfer."""

    _acc_fields = ("sum", "sumsq")

    def __init__(self, n_features: int, *, devices: int, impl: str = "auto"):
        self.n = int(n_features)
        self.devices = int(devices)
        self.impl = impl
        self.mesh = _data_mesh(self.devices)
        self._dtype = jax.dtypes.canonicalize_dtype(np.float64)
        self._acc_shard = NamedSharding(self.mesh, P("data", None))
        self._ent_shard = NamedSharding(self.mesh, P("data", None, None))
        z = jnp.zeros((self.devices, self.n), self._dtype)
        self.sum = jax.device_put(z, self._acc_shard)
        self.sumsq = jax.device_put(z, self._acc_shard)
        self._err_sum = jax.device_put(z, self._acc_shard)
        self._err_sumsq = jax.device_put(z, self._acc_shard)
        self.count = 0

    def update_superbatch(self, sb: CSRSuperBatch) -> "MeshStats":
        # The superbatch arrays are ring-buffer views; block on the
        # transfer before releasing them back to the packer (the same
        # rationale as ops._sync_host_inputs).
        with trace.span("ingest.h2d", b=sb.index):
            vals = jax.device_put(sb.values, self._ent_shard)
            cols = jax.device_put(sb.col_ids, self._ent_shard)
            jax.block_until_ready((vals, cols))
        step = _stats_step(self.devices, self.n, _use_pallas(self.impl))
        self.sum, self.sumsq, self._err_sum, self._err_sumsq = step(
            self.sum, self.sumsq, self._err_sum, self._err_sumsq, vals, cols
        )
        self.count += int(np.sum(sb.n_rows))
        return self

    def merge(self, other: "MeshStats") -> "MeshStats":
        assert self.n == other.n and self.devices == other.devices
        self.sum = self.sum + other.sum
        self.sumsq = self.sumsq + other.sumsq
        self._err_sum = self._err_sum + other._err_sum
        self._err_sumsq = self._err_sumsq + other._err_sumsq
        self.count += other.count
        return self

    def _pooled(self):
        s, ss, es, ess = psum_partials(
            (self.sum, self.sumsq, self._err_sum, self._err_sumsq),
            self.mesh, axes=("data",),
        )
        # ONE host transfer per moment; the compensation re-injects here.
        return (np.asarray(s, np.float64) + np.asarray(es, np.float64),
                np.asarray(ss, np.float64) + np.asarray(ess, np.float64))

    def finalize(self, *, center: bool = True) -> Screen:
        s, ss = self._pooled()
        m = max(self.count, 1)
        mean = s / m if center else np.zeros(self.n)
        var = np.maximum(ss / m - mean**2, 0.0)
        return Screen(
            variances=jnp.asarray(var),
            means=jnp.asarray(mean),
            count=np.asarray(self.count, np.int64),
        )

    # -- resume support (stacked per-device moments) -----------------------

    def state_dict(self) -> dict:
        return {
            "sum": np.asarray(self.sum),
            "sumsq": np.asarray(self.sumsq),
            "err_sum": np.asarray(self._err_sum),
            "err_sumsq": np.asarray(self._err_sumsq),
            "count": np.asarray(self.count, np.int64),
        }

    def load_state(self, state: dict) -> "MeshStats":
        put = lambda k: jax.device_put(
            jnp.asarray(np.asarray(state[k]), self._dtype), self._acc_shard
        )
        self.sum, self.sumsq = put("sum"), put("sumsq")
        self._err_sum, self._err_sumsq = put("err_sum"), put("err_sumsq")
        self.count = int(state["count"])
        return self

    def state_signature(self) -> dict:
        return {"acc": "mesh_stats", "n": int(self.n),
                "devices": int(self.devices), "dtype": str(self._dtype)}


class MeshGram:
    """`StreamingGram` sharded lane-per-device: per-device (k, k) partial
    grams (plus Neumaier slots) resident across the pass, pooled with one
    psum at finalize."""

    _acc_fields = ("g",)

    def __init__(self, support: np.ndarray, *, devices: int,
                 impl: str = "auto", chunk_rows: int = DEFAULT_CHUNK_ROWS):
        self.support = np.asarray(support)
        self.devices = int(devices)
        self.impl = impl
        self.chunk_rows = int(chunk_rows)
        self.mesh = _data_mesh(self.devices)
        self._dtype = jax.dtypes.canonicalize_dtype(np.float64)
        k = self.support.size
        self._acc_shard = NamedSharding(self.mesh, P("data", None, None))
        self._ent_shard = NamedSharding(self.mesh, P("data", None, None))
        z = jnp.zeros((self.devices, k, k), self._dtype)
        self.g = jax.device_put(z, self._acc_shard)
        self._err = jax.device_put(z, self._acc_shard)
        self.count = 0
        self._remap = SupportRemap(self.support)

    def update_superbatch(self, sb: CSRSuperBatch) -> "MeshGram":
        if self.support.size == 0:
            self.count += int(np.sum(sb.n_rows))
            return self
        with trace.span("ingest.prep", b=sb.index):
            local = self._remap(sb.col_ids, sb.nnz)
        with trace.span("ingest.h2d", b=sb.index):
            vals = jax.device_put(sb.values, self._ent_shard)
            cols = jax.device_put(local, self._ent_shard)
            segs = jax.device_put(sb.seg_ids, self._ent_shard)
            jax.block_until_ready((vals, cols, segs))
        step = _gram_step(self.devices, self.chunk_rows,
                          int(self.support.size), _use_pallas(self.impl))
        self.g, self._err = step(self.g, self._err, vals, cols, segs)
        self.count += int(np.sum(sb.n_rows))
        return self

    def merge(self, other: "MeshGram") -> "MeshGram":
        assert np.array_equal(self.support, other.support)
        assert self.devices == other.devices
        self.g = self.g + other.g
        self._err = self._err + other._err
        self.count += other.count
        return self

    def finalize(self, *, means: np.ndarray | None = None) -> np.ndarray:
        g_d, err_d = psum_partials((self.g, self._err), self.mesh,
                                   axes=("data",))
        m = max(self.count, 1)
        g = np.asarray(g_d, np.float64) + np.asarray(err_d, np.float64)
        if means is not None:
            mu = np.asarray(means)[self.support]
            g = g - m * np.outer(mu, mu)
        return g / m

    # -- resume support ----------------------------------------------------

    def state_dict(self) -> dict:
        return {
            "g": np.asarray(self.g),
            "err": np.asarray(self._err),
            "count": np.asarray(self.count, np.int64),
        }

    def load_state(self, state: dict) -> "MeshGram":
        put = lambda k: jax.device_put(
            jnp.asarray(np.asarray(state[k]), self._dtype), self._acc_shard
        )
        self.g, self._err = put("g"), put("err")
        self.count = int(state["count"])
        return self

    def state_signature(self) -> dict:
        import zlib
        return {
            "acc": "mesh_gram",
            "n_hat": int(self.support.size),
            "support_crc": int(
                zlib.crc32(np.ascontiguousarray(self.support).tobytes())
                & 0xFFFFFFFF
            ),
            "devices": int(self.devices),
            "dtype": str(self._dtype),
        }


# ---------------------------------------------------------------------------
# the sharded drain


def _degrade_step(e: BaseException, D: int, min_devices: int,
                  counters: dict | None) -> int | None:
    """The next rung of the degraded-mode ladder for a sharded pass that
    died with ``e`` at ``D`` devices: half the topology (floored at
    ``min_devices``), or None when the error is not a retryable dispatch
    failure / the ladder is exhausted (caller re-raises)."""
    from repro.core.bcd import is_dispatch_error
    nD = max(int(min_devices), 1, D // 2)
    if nD >= D or not is_dispatch_error(e):
        return None
    metrics.counter("mesh.degraded").inc()
    _count(counters, "mesh_degraded", 1)
    return nD


def _mesh_drain(store: SparseCorpus, acc, *, devices, chunk_nnz, chunk_rows,
                megabatch, prefetch_depth, host_id, num_hosts, counters,
                launch_key, checkpointer=None, kind: str = "",
                pass_deadline_s: float | None = None):
    """One sharded streaming pass: superbatches of D megabatches,
    prefetched one ahead, ONE dispatch per superbatch — ceil(B/D) launches
    for a pass `engine._drain` does in B.  Mirrors `_drain`'s resume,
    retry, and prefetch accounting; counter keys are identical
    (``screen_launches`` / ``gram_launches`` count *dispatches*, so the
    amortization is visible in the same diagnostics).  ``pass_deadline_s``
    arms the same cooperative watchdog as `engine._drain`, checked at
    superbatch boundaries after the checkpoint cadence runs."""
    D = int(devices)
    wd = None
    if pass_deadline_s is not None:
        from repro.obs import health as _health
        wd = _health.Watchdog(pass_deadline_s, what=f"{kind or launch_key} pass",
                              exc=_health.PassDeadlineError)
    start_batch = 0
    fp = None
    if checkpointer is not None:
        fp = pass_fingerprint(
            kind or launch_key, store, chunk_nnz=chunk_nnz,
            chunk_rows=chunk_rows, megabatch=megabatch, host_id=host_id,
            num_hosts=num_hosts, signature=acc.state_signature(),
            n_devices=D,
        )
        hit = checkpointer.load(fp)
        if hit is not None:
            cursor, state, _complete = hit
            acc.load_state(state)
            start_batch = cursor
            metrics.counter("ingest.resume.loads").inc()
            metrics.counter("ingest.resume.megabatches_skipped").inc(cursor)
            _count(counters, "resumed_megabatches", cursor)
    retries0 = getattr(store, "io_retry_count", 0)
    pstats: dict = {}
    pprev: dict = {}
    it = _feed(_iter_superbatches(
        store, devices=D, chunk_nnz=chunk_nnz, chunk_rows=chunk_rows,
        megabatch=megabatch, host_id=host_id, num_hosts=num_hosts,
        ring=max(2, prefetch_depth + 2), start_batch=start_batch,
    ), start_batch=start_batch, prefetch_depth=prefetch_depth, stats=pstats)
    lane_regs = [metrics.Registry() for _ in range(D)]
    done = start_batch
    with trace.span("ingest.shard_pass", kind=launch_key, devices=D,
                    megabatch=megabatch):
        for sb in it:
            with trace.span("ingest.megabatch", kind=launch_key,
                            chunks=int(sb.n_chunks), lanes=int(sb.lanes),
                            b=sb.index):
                # Fault seam: lets tests kill THIS dispatch the way a real
                # XLA runtime error would, exercising the degrade ladder.
                kernel_ops.solver_fault_before(f"mesh.{kind or launch_key}")
                acc.update_superbatch(sb)
            _bump(counters, **{launch_key: 1, "chunks": sb.n_chunks})
            for d in range(sb.lanes):
                lane_regs[d].counter("ingest.shard.chunks").inc(
                    sb.lane_chunks[d])
                lane_regs[d].counter("ingest.shard.nnz").inc(sb.lane_nnz[d])
                # the registry pools the lanes; the diagnostics keep each
                _count(counters, f"shard_chunks.{d}", sb.lane_chunks[d])
            _stream_prefetch_stats(pstats, pprev)
            prev_done, done = done, done + sb.lanes
            if (checkpointer is not None
                    and done // checkpointer.every
                    > prev_done // checkpointer.every):
                with trace.span("ingest.resume.checkpoint", kind=launch_key,
                                cursor=done):
                    checkpointer.save(fp, done, acc.state_dict())
                metrics.counter("ingest.resume.checkpoints").inc()
                _count(counters, "resume_checkpoints", 1)
            if wd is not None:
                wd.check()
        if checkpointer is not None:
            checkpointer.save(fp, done, acc.state_dict(), complete=True)
            metrics.counter("ingest.resume.checkpoints").inc()
            _count(counters, "resume_checkpoints", 1)
    # Pool the per-lane registries into the global one — the merge a real
    # multi-process mesh performs over scraped per-host snapshots.
    root = metrics.get_registry()
    for r in lane_regs:
        root.merge(r)
    dr = getattr(store, "io_retry_count", 0) - retries0
    if dr:
        _count(counters, "io_retries", dr)
    if pstats:
        _stream_prefetch_stats(pstats, pprev)
        if counters is not None:
            counters["prefetch_consumer_stall_s"] = (
                counters.get("prefetch_consumer_stall_s", 0.0)
                + pstats.get("consumer_stall_s", 0.0))
            counters["prefetch_producer_stall_s"] = (
                counters.get("prefetch_producer_stall_s", 0.0)
                + pstats.get("producer_stall_s", 0.0))
    return acc


# ---------------------------------------------------------------------------
# public passes (signatures mirror engine.sparse_* plus ``devices``)


def mesh_feature_variances(
    store: SparseCorpus,
    *,
    devices: int,
    center: bool = True,
    impl: str = "auto",
    chunk_nnz: int = DEFAULT_CHUNK_NNZ,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    megabatch: int = DEFAULT_MEGABATCH,
    prefetch_depth: int = DEFAULT_PREFETCH,
    num_hosts: int = 1,
    counters: dict | None = None,
    io_retries: int | None = None,
    io_backoff_s: float | None = None,
    resume_dir: str | None = None,
    checkpoint_every: int = DEFAULT_CHECKPOINT_EVERY,
    min_devices: int = 1,
    pass_deadline_s: float | None = None,
) -> Screen:
    """The Thm 2.1 screen input, computed in one D-device sharded pass.

    ``devices <= 1`` falls back to the single-device engine, so callers
    can pass the config knob straight through.  A dispatch failure retries
    the whole pass at D/2 (see the module docstring's degraded-mode
    contract) down to ``min_devices``."""
    if int(devices) <= 1:
        from . import engine
        return engine.sparse_feature_variances(
            store, center=center, impl=impl, chunk_nnz=chunk_nnz,
            chunk_rows=chunk_rows, megabatch=megabatch,
            prefetch_depth=prefetch_depth, num_hosts=num_hosts,
            counters=counters, io_retries=io_retries,
            io_backoff_s=io_backoff_s, resume_dir=resume_dir,
            checkpoint_every=checkpoint_every,
            pass_deadline_s=pass_deadline_s,
        )
    try:
        metrics.gauge("mesh.devices").set(int(devices))
        ckpt = _reliability(store, io_retries, io_backoff_s,
                            resume_dir, checkpoint_every)
        partials = []
        with trace.span("ingest.screen_pass", nnz=int(store.nnz),
                        num_hosts=num_hosts, megabatch=megabatch,
                        devices=int(devices)):
            for h in range(num_hosts):
                acc = MeshStats(store.n_cols, devices=devices, impl=impl)
                _mesh_drain(
                    store, acc, devices=devices, chunk_nnz=chunk_nnz,
                    chunk_rows=chunk_rows, megabatch=megabatch,
                    prefetch_depth=prefetch_depth, host_id=h,
                    num_hosts=num_hosts, counters=counters,
                    launch_key="screen_launches", checkpointer=ckpt,
                    kind="screen", pass_deadline_s=pass_deadline_s,
                )
                partials.append(acc.finalize(center=center))
            _bump(counters, screen_passes=1)
            if len(partials) == 1:
                return partials[0]
            return combine_screens(partials)
    except RuntimeError as e:
        nD = _degrade_step(e, int(devices), min_devices, counters)
        if nD is None:
            raise
        return mesh_feature_variances(
            store, devices=nD, center=center, impl=impl,
            chunk_nnz=chunk_nnz, chunk_rows=chunk_rows, megabatch=megabatch,
            prefetch_depth=prefetch_depth, num_hosts=num_hosts,
            counters=counters, io_retries=io_retries,
            io_backoff_s=io_backoff_s, resume_dir=resume_dir,
            checkpoint_every=checkpoint_every, min_devices=min_devices,
            pass_deadline_s=pass_deadline_s,
        )


def mesh_reduced_covariance(
    store: SparseCorpus,
    support: np.ndarray,
    *,
    devices: int,
    means: np.ndarray | None = None,
    impl: str = "auto",
    chunk_nnz: int = DEFAULT_CHUNK_NNZ,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    megabatch: int = DEFAULT_MEGABATCH,
    prefetch_depth: int = DEFAULT_PREFETCH,
    num_hosts: int = 1,
    counters: dict | None = None,
    io_retries: int | None = None,
    io_backoff_s: float | None = None,
    resume_dir: str | None = None,
    checkpoint_every: int = DEFAULT_CHECKPOINT_EVERY,
    min_devices: int = 1,
    pass_deadline_s: float | None = None,
):
    """Sigma_hat on the surviving columns in one D-device sharded pass."""
    if int(devices) <= 1:
        from . import engine
        return engine.sparse_reduced_covariance(
            store, support, means=means, impl=impl, chunk_nnz=chunk_nnz,
            chunk_rows=chunk_rows, megabatch=megabatch,
            prefetch_depth=prefetch_depth, num_hosts=num_hosts,
            counters=counters, io_retries=io_retries,
            io_backoff_s=io_backoff_s, resume_dir=resume_dir,
            checkpoint_every=checkpoint_every,
            pass_deadline_s=pass_deadline_s,
        )
    try:
        metrics.gauge("mesh.devices").set(int(devices))
        ckpt = _reliability(store, io_retries, io_backoff_s,
                            resume_dir, checkpoint_every)
        support = np.asarray(support)
        accs = []
        with trace.span("ingest.gram_pass", n_hat=int(support.size),
                        num_hosts=num_hosts, megabatch=megabatch,
                        devices=int(devices)):
            for h in range(num_hosts):
                acc = MeshGram(support, devices=devices, impl=impl,
                               chunk_rows=chunk_rows)
                _mesh_drain(
                    store, acc, devices=devices, chunk_nnz=chunk_nnz,
                    chunk_rows=chunk_rows, megabatch=megabatch,
                    prefetch_depth=prefetch_depth, host_id=h,
                    num_hosts=num_hosts, counters=counters,
                    launch_key="gram_launches", checkpointer=ckpt,
                    kind="gram", pass_deadline_s=pass_deadline_s,
                )
                accs.append(acc)
            _bump(counters, gram_passes=1)
            acc = accs[0]
            for other in accs[1:]:
                acc.merge(other)
            out = jnp.asarray(acc.finalize(means=means))
            trace.device_sync(out)
        return out
    except RuntimeError as e:
        nD = _degrade_step(e, int(devices), min_devices, counters)
        if nD is None:
            raise
        return mesh_reduced_covariance(
            store, support, devices=nD, means=means, impl=impl,
            chunk_nnz=chunk_nnz, chunk_rows=chunk_rows, megabatch=megabatch,
            prefetch_depth=prefetch_depth, num_hosts=num_hosts,
            counters=counters, io_retries=io_retries,
            io_backoff_s=io_backoff_s, resume_dir=resume_dir,
            checkpoint_every=checkpoint_every, min_devices=min_devices,
            pass_deadline_s=pass_deadline_s,
        )


def mesh_sparse_stats(
    store: SparseCorpus,
    *,
    devices: int,
    center: bool = True,
    impl: str = "auto",
    chunk_nnz: int = DEFAULT_CHUNK_NNZ,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    megabatch: int = DEFAULT_MEGABATCH,
    prefetch_depth: int = DEFAULT_PREFETCH,
    num_hosts: int = 1,
    counters: dict | None = None,
    io_retries: int | None = None,
    io_backoff_s: float | None = None,
    resume_dir: str | None = None,
    checkpoint_every: int = DEFAULT_CHECKPOINT_EVERY,
    min_devices: int = 1,
    pass_deadline_s: float | None = None,
):
    """The ``(variances, build)`` pair `core.spca._as_stats` consumes,
    computed with D-device sharded passes — same 1 + 1 corpus-pass
    economics as `engine.sparse_stats` (the covariance cache calls
    ``build`` once per fit), with ceil(B/D) dispatches per pass."""
    screen = mesh_feature_variances(
        store, devices=devices, center=center, impl=impl,
        chunk_nnz=chunk_nnz, chunk_rows=chunk_rows, megabatch=megabatch,
        prefetch_depth=prefetch_depth, num_hosts=num_hosts,
        counters=counters, io_retries=io_retries, io_backoff_s=io_backoff_s,
        resume_dir=resume_dir, checkpoint_every=checkpoint_every,
        min_devices=min_devices, pass_deadline_s=pass_deadline_s,
    )
    means = np.asarray(screen.means) if center else None

    def build(support):
        return mesh_reduced_covariance(
            store, np.asarray(support), devices=devices, means=means,
            impl=impl, chunk_nnz=chunk_nnz, chunk_rows=chunk_rows,
            megabatch=megabatch, prefetch_depth=prefetch_depth,
            num_hosts=num_hosts, counters=counters, io_retries=io_retries,
            io_backoff_s=io_backoff_s, resume_dir=resume_dir,
            checkpoint_every=checkpoint_every,
            min_devices=min_devices, pass_deadline_s=pass_deadline_s,
        )

    return np.asarray(screen.variances), build
