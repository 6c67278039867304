"""Work counts of the rooflines (bench/work/) against hand counts."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib import gen, harness  # noqa: E402


def work(kernel):
    return harness.load_module(os.path.join(ROOT, "bench", "work", kernel + ".py"),
                               f"w_{kernel}").work


def test_screen_work():
    # 10 entries over 4 words: 10*(4+4) B read + 2 sums of 4 f32 written
    assert work("csr_stats")(nnz=10, n=4) == (30.0, 80.0 + 32.0)


def test_gram_work_counts_support_entries_per_document():
    from bench.drivers import fit_loop

    # doc 0: words 1, 2, 5; doc 1: word 2; doc 2: words 0, 7 (none kept)
    csr = gen.CSR(np.ones(6, np.float32), np.array([1, 2, 5, 2, 0, 7], np.int32),
                  np.array([0, 3, 4, 6]))
    support = np.array([1, 2, 5])
    k2 = fit_loop._sum_k2(csr, support)
    assert k2 == 3 * 3 + 1 * 1 + 0
    assert work("csr_gram")(nnz=6, n_hat=3, sum_k2=k2) == (20.0, 48.0 + 36.0)


@pytest.mark.parametrize("rows,slots,k", [(1, 8, 1), (64, 40, 5)])
def test_project_work(rows, slots, k):
    flops, nbytes = work("project")(rows=rows, slots=slots, k=k)
    assert flops == 2 * rows * slots
    assert nbytes == 4 * rows * slots + 4 * rows * k


def test_peaks_know_v5e_and_refuse_unknown_devices():
    cell = harness.Cell("pubmed.fit_fused")
    assert cell.peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cell.peaks("cpu")
