"""Plain reference for what the timed path produces, and the comparisons
that decide ``correct``.

It imports nothing of the program: it reads the corpus as the generator
made it (host CSR) and the program's outputs as plain arrays.  Every
reduction is numpy in float64.  ``precision="bf16"`` computes the same
things with bfloat16 operands and results (float32 accumulation, as a
matrix unit would): that is the control, the reference put in the
program's place one precision step below the configuration's float32.

Numbers compared (each against its limit in the cell's check table):

fit cells
  screen_rel_err  max over words of |var - var_ref| / var_ref (a word no
                  document holds must read exactly 0);
  gram_rel_err    max over entries of |G - G_ref| / sqrt(G_ref_ii G_ref_jj)
                  on the support the Gram pass was asked for;
  pc_var_rel_err  max over components of |v - x' S_ref x| / x' S_ref x;
  pc_norm_err     max over components of | ||x|| - 1 | plus the norm of x
                  off its support (an empty component reads 1);
  pc_top_ratio    max over components of the largest reference variance
                  among the words still available to it (not in an earlier
                  component's support) over its x' S_ref x: a component
                  explains about as much as the strongest single word at
                  least, and a solve that did no work picks a weak word;
  overlap         words in the supports of two components ('remove'
                  deflation drops a component's words); exact.
Read beside them, not compared: ``below_lam`` and ``eig_gap`` (see there).
serve cells
  score_rel_err   max over answered requests and topics of
                  |s - s_ref| / sum_j |x_j| c_j;
  unanswered      requests due in the window that never got an answer.
"""
from __future__ import annotations

import numpy as np


def _bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


def rounded(a, precision: str):
    a = np.asarray(a, np.float64)
    if precision == "bf16":
        return a.astype(_bf16()).astype(np.float64)
    return a


def screen(values, cols, n_rows: int, n_cols: int, *, precision="f64"):
    """Per-word population variance over all documents."""
    v = rounded(values, precision)
    s = np.bincount(cols, weights=v, minlength=n_cols)
    ss = np.bincount(cols, weights=v * v, minlength=n_cols)
    if precision == "bf16":           # f32 accumulation, bf16 results
        s = rounded(s.astype(np.float32), precision)
        ss = rounded(ss.astype(np.float32), precision)
    mean = s / n_rows
    var = ss / n_rows - mean * mean
    return rounded(np.maximum(var, 0.0), precision), rounded(mean, precision)


def gram(values, cols, row_ptr, support, means, *, precision="f64",
         block_rows: int = 16384):
    """Centred reduced covariance A_S' A_S / m - mu_S mu_S' on ``support``,
    dense row block by row block."""
    support = np.asarray(support)
    n_rows = row_ptr.size - 1
    pos = np.full(int(max(cols.max(initial=0), support.max(initial=0))) + 1,
                  -1, np.int64)
    pos[support] = np.arange(support.size)
    k = support.size
    G = np.zeros((k, k))
    dtype = np.float32 if precision == "bf16" else np.float64
    for lo in range(0, n_rows, block_rows):
        hi = min(lo + block_rows, n_rows)
        a, b = row_ptr[lo], row_ptr[hi]
        p = pos[cols[a:b]]
        keep = p >= 0
        rows = np.repeat(np.arange(hi - lo), np.diff(row_ptr[lo:hi + 1]))[keep]
        B = np.zeros((hi - lo, k), dtype)
        B[rows, p[keep]] = rounded(values[a:b][keep], precision)
        G += (B.T @ B).astype(np.float64)
    G = rounded(G, precision) / n_rows
    mu = rounded(np.asarray(means, np.float64)[support], precision)
    return rounded(G - np.outer(mu, mu), precision)


def screen_rel_err(var, var_ref) -> float:
    var = np.asarray(var, np.float64)
    live = var_ref > 0
    dead_err = float(np.max(np.abs(var[~live]), initial=0.0))
    if dead_err > 0:
        return float("inf")
    return float(np.max(np.abs(var[live] - var_ref[live]) / var_ref[live],
                        initial=0.0))


def gram_rel_err(G, G_ref) -> float:
    d = np.sqrt(np.maximum(np.diag(G_ref), 1e-300))
    return float(np.max(np.abs(np.asarray(G, np.float64) - G_ref)
                        / np.outer(d, d), initial=0.0))


def pc_var_rel_err(components, sigma_of) -> float:
    """``components``: dicts with ``support``, ``x`` (the loadings on the
    support) and ``variance`` (as reported); ``sigma_of(support)`` gives
    the reference covariance on a support."""
    worst = 0.0
    for c in components:
        S = sigma_of(np.asarray(c["support"]))
        v_ref = float(c["x"] @ S @ c["x"])
        worst = max(worst, abs(c["variance"] - v_ref) / max(abs(v_ref), 1e-300))
    return worst


def pc_norm_err(components) -> float:
    """Unit loadings: ``x`` on the support has norm 1, ``off`` (the norm of
    the loadings off it) is 0."""
    return max((abs(float(np.linalg.norm(c["x"])) - 1.0) + float(c["off"])
                for c in components), default=0.0)


def pc_top_ratio(components, var_ref, sigma_of) -> float:
    """Components in deflation order; an empty one reads inf."""
    avail = np.ones(var_ref.size, bool)
    worst = 0.0
    for c in components:
        sup = np.asarray(c["support"], np.int64)
        top = float(np.max(var_ref[avail], initial=0.0))
        v = float(c["x"] @ sigma_of(sup) @ c["x"]) if sup.size else 0.0
        worst = max(worst, top / v if v > 0 else float("inf"))
        avail[sup] = False
    return worst


def below_lam(components, var_ref, *, margin: float) -> int:
    """Not compared (a diagnostic): support words with reference variance
    < lambda (1 - margin), which Thm 2.1 puts at zero in the optimum.  The
    solver's log-det barrier keeps every loading of the padded reduced
    problem above zero, so converged sound fits read some too."""
    return sum(int(np.sum(var_ref[np.asarray(c["support"], np.int64)]
                          < c["lam"] * (1.0 - margin)))
               for c in components)


def overlap(components) -> int:
    """Words that lie in more than one component's support."""
    sups = [np.asarray(c["support"], np.int64) for c in components]
    if not sups:
        return 0
    words = np.concatenate(sups)
    return int(words.size - np.unique(words).size)


def eig_gap(components, sigma_of) -> float:
    """Not compared (a diagnostic): the largest share by which x' S_ref x
    lies below the top eigenvalue of S_ref on the component's support.
    The lambda penalty tilts an exact solution off the principal
    direction, so this reads the method's bias as well as a solve's."""
    worst = 0.0
    for c in components:
        if len(c["support"]) == 0:
            continue
        S = sigma_of(np.asarray(c["support"]))
        top = float(np.linalg.eigvalsh(S)[-1])
        worst = max(worst, (top - float(c["x"] @ S @ c["x"])) / max(top, 1e-300))
    return worst


def project(values, cols, row_ptr, support_idx, loadings, n_words: int, *,
            precision="f64", block_rows: int = 8192):
    """Scores of CSR documents on every packed topic: sum over the topic's
    words of count x loading.  Returns (scores, magnitudes), each
    (docs, k); the magnitude sums |count x loading| and scales the error."""
    x = rounded(loadings, precision)
    k = support_idx.shape[0]
    W = np.zeros((n_words, k))
    for c in range(k):
        np.add.at(W[:, c], support_idx[c], x[c])
    hit = np.any(W != 0.0, axis=1)
    n = row_ptr.size - 1
    scores, mags = np.zeros((n, k)), np.zeros((n, k))
    for lo in range(0, n, block_rows):
        hi = min(lo + block_rows, n)
        a, b = row_ptr[lo], row_ptr[hi]
        rows = np.repeat(np.arange(lo, hi), np.diff(row_ptr[lo:hi + 1]))
        sel = hit[cols[a:b]]
        contrib = (rounded(values[a:b][sel], precision)[:, None]
                   * W[cols[a:b][sel]])
        np.add.at(scores, rows[sel], contrib)
        np.add.at(mags, rows[sel], np.abs(contrib))
    if precision == "bf16":
        scores = rounded(scores.astype(np.float32), precision)
    return scores, mags


def score_rel_err(scores, refs, mags) -> float:
    err = np.abs(np.asarray(scores, np.float64) - refs)
    live = mags > 0
    if np.any(err[~live] > 0):
        return float("inf")
    return float(np.max(err[live] / mags[live], initial=0.0))
