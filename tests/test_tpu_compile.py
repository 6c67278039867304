"""Every main-path Pallas kernel compiles for one TPU v5e chip at real widths.

Interpret mode (the CPU tests) cannot see what the TPU compiler refuses:
unaligned blocks, dynamic slices of loaded values, scalar reads from vector
memory, VMEM over the scoped limit.  These tests hand the kernels to the
chip's compiler for a described ``v5e:2x2`` topology (nothing attached) at
the fit and serve paths' own shapes, and check that the lowered program
holds the kernel (``tpu_custom_call``).  The second group compiles the
largest size each VMEM plan admits, so a plan that promises more than the
compiler grants fails here instead of on the chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.bcd_fused import bcd_solve_batched_pallas
from repro.kernels.csr_gram import (
    batched_gram_fits, csr_gram_batched_pallas, csr_gram_pallas,
)
from repro.kernels.csr_stats import csr_column_stats_pallas
from repro.kernels.project import sparse_project_pallas

NYTIMES_WORDS = 102_660
PUBMED_WORDS = 141_043
CHUNK_NNZ, CHUNK_ROWS, MEGABATCH = 16_384, 512, 8   # spca_run defaults


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A described-topology compile is written to the persistent cache but
    # cannot be read back without a chip; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    # The program runs with x64 off on the chip (the test session turns it
    # on globally), and Mosaic cannot lower the 64-bit index math x64 makes.
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _s(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _entries(sharding, *lead):
    shape = (*lead, CHUNK_NNZ)
    return (_s(sharding, shape), _s(sharding, shape, jnp.int32),
            _s(sharding, shape, jnp.int32))


@pytest.mark.parametrize("n", [NYTIMES_WORDS, PUBMED_WORDS])
def test_screen_kernel_compiles(one_chip, n):
    v, c, _ = _entries(one_chip, MEGABATCH)
    _compile(lambda v, c: csr_column_stats_pallas(v, c, n), v, c)


def test_megabatch_gram_kernel_compiles(one_chip):
    _compile(
        lambda v, c, s: csr_gram_batched_pallas(v, c, s, CHUNK_ROWS, 384),
        *_entries(one_chip, MEGABATCH))


def test_single_chunk_gram_kernel_compiles(one_chip):
    _compile(lambda v, c, s: csr_gram_pallas(v, c, s, CHUNK_ROWS, 1536),
             *_entries(one_chip))


def _solve(scheme, B, n, panel_rows=128):
    def fn(Sigmas, X0s, lams, n_valids):
        return bcd_solve_batched_pallas(
            Sigmas, lams, jnp.full((B,), 1e-4, jnp.float32), X0s, 1e-7,
            n_valids, max_sweeps=8, scheme=scheme, panel_rows=panel_rows)
    return fn


def _problems(sharding, B, n):
    return (_s(sharding, (B, n, n)), _s(sharding, (B, n, n)),
            _s(sharding, (B,)), _s(sharding, (B,), jnp.int32))


@pytest.mark.parametrize("scheme,B,n", [
    ("resident", 1, 384),
    ("tiled", 1, 1024),
    ("resident", 4, 256),       # the batched launch of a lambda round
])
def test_fused_solve_kernel_compiles(one_chip, scheme, B, n):
    _compile(_solve(scheme, B, n), *_problems(one_chip, B, n))


@pytest.mark.parametrize("B", [64, 512])
def test_projector_kernel_compiles(one_chip, B):
    P = 5 * 8                                   # k=5 components, cap 8
    _compile(
        lambda X, i, c, v: sparse_project_pallas(X, i, c, v, 5),
        _s(one_chip, (B, NYTIMES_WORDS)), _s(one_chip, (P,), jnp.int32),
        _s(one_chip, (P,), jnp.int32), _s(one_chip, (P,)))


def test_projector_kernel_compiles_on_support_columns(one_chip):
    """The microbatcher's batch as a TopicProjector serves it: 64 rows of
    its support columns, one 128-lane block."""
    P = 5 * 8
    _compile(
        lambda X, i, c, v: sparse_project_pallas(X, i, c, v, 5),
        _s(one_chip, (64, 128)), _s(one_chip, (P,), jnp.int32),
        _s(one_chip, (P,), jnp.int32), _s(one_chip, (P,)))


# ---------------------------------------------------------------- VMEM plans


@pytest.mark.parametrize("B", [1, 8])
def test_largest_planned_solves_compile(one_chip, B):
    """The largest reduced size each solve plan admits compiles within the
    compiler's scoped VMEM limit, for one problem and for a batch."""
    n = max(n for n in range(128, 4097, 128)
            if ops.plan_fused_solve(n, batch=B) is not None)
    plan = ops.plan_fused_solve(n, batch=B)
    _compile(_solve(plan.scheme, B, n, plan.panel_rows or 128),
             *_problems(one_chip, B, n))
    resident = max(
        n for n in range(128, n + 1, 128)
        if ops.plan_fused_solve(n, batch=B).scheme == "resident")
    _compile(_solve("resident", B, resident), *_problems(one_chip, B, resident))


def test_largest_planned_megabatch_gram_compiles(one_chip):
    n_hat = max(n for n in range(128, 2049, 128)
                if batched_gram_fits(n, CHUNK_ROWS, CHUNK_NNZ))
    _compile(
        lambda v, c, s: csr_gram_batched_pallas(v, c, s, CHUNK_ROWS, n_hat),
        *_entries(one_chip, MEGABATCH))
