"""Plain reference for ILU(1): the filled pattern, the sequential factor
and the residual of a solution, written from the definitions alone.

Nothing here imports the system under test. The pattern is A's plus every
(i, j) reached through one original entry on each side, i.e. the boolean
product of A's strict lower and strict upper parts (level-of-fill 1 under
the sum rule). The factor is the row-major IKJ elimination restricted to
that pattern: for each row, its lower entries in ascending column order,
``l = f[r,h] / f[h,h]`` and then ``f[r,t] = f[r,t] - l * f[h,t]`` for each
t > h in both rows, each product rounded before its subtraction. Rows
whose lower entries lie in finished rows only are independent, so rows are
taken a dependency level at a time and, inside a level, one lower entry
of every row at a time; that is the same sequence of roundings for every
entry as the row-by-row loop.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def pattern(n, indptr, indices):
    """Filled ILU(1) pattern of A's structure: ``(indptr, indices, diag)``,
    columns sorted in each row, ``diag`` the in-row position of the
    diagonal."""
    ones = np.ones(len(indices), np.float64)
    a = sp.csr_matrix((ones, indices, indptr), shape=(n, n))
    lower = sp.tril(a, -1, format="csr")
    upper = sp.triu(a, 1, format="csr")
    filled = (a + lower @ upper).tocsr()
    filled.sort_indices()
    p_indptr = filled.indptr.astype(np.int64)
    p_indices = filled.indices.astype(np.int64)
    row = np.repeat(np.arange(n), np.diff(p_indptr))
    diag = np.bincount(row[p_indices < row], minlength=n).astype(np.int64)
    if not np.all(p_indices[p_indptr[:-1] + diag] == np.arange(n)):
        raise ValueError("every row of A needs its diagonal entry")
    return p_indptr, p_indices, diag


def _levels(n, p_indptr, p_indices, diag):
    """Dependency level of each row: 1 + the deepest row its lower entries
    name (Kahn's frontier over the lower pattern)."""
    row = np.repeat(np.arange(n), np.diff(p_indptr))
    low = p_indices < row
    src, dst = p_indices[low], row[low]
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    out_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=out_ptr[1:])
    indeg = diag.copy()
    level = np.zeros(n, np.int64)
    frontier = np.flatnonzero(indeg == 0)
    depth = 0
    while frontier.size:
        level[frontier] = depth
        lens = out_ptr[frontier + 1] - out_ptr[frontier]
        idx = np.repeat(out_ptr[frontier] - np.cumsum(lens) + lens, lens) + np.arange(lens.sum())
        succ = dst[idx]
        np.subtract.at(indeg, succ, 1)
        cand = np.unique(succ)
        frontier = cand[indeg[cand] == 0]
        depth += 1
    return level, depth


def _spans(starts, lens):
    """Concatenated ``range(s, s + l)`` for each pair."""
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    return np.repeat(starts - np.cumsum(lens) + lens, lens) + np.arange(total)


def scatter(n, p_indptr, p_indices, indptr, indices, data, dtype=np.float32):
    """A's values placed on the filled pattern (fill entries start at 0)."""
    row = np.repeat(np.arange(n), np.diff(indptr))
    key = row * n + np.asarray(indices, np.int64)
    pos = np.searchsorted(entry_keys(n, p_indptr, p_indices), key)
    vals = np.zeros(len(p_indices), dtype)
    vals[pos] = np.asarray(data).astype(dtype)
    return vals


def entry_keys(n, p_indptr, p_indices):
    """``row * n + col`` of every pattern entry: sorted, so a position is
    found by binary search."""
    return np.repeat(np.arange(n), np.diff(p_indptr)) * n + p_indices


def updates(n, p_indptr, p_indices, diag, rows, p_key=None):
    """The multiply-subtract pairs of the given rows, in the order the
    elimination applies them: ``(pivot_pos, target_pos, source_pos, step)``
    where ``step`` is the pivot's rank among its row's lower entries."""
    if p_key is None:
        p_key = entry_keys(n, p_indptr, p_indices)
    rows = np.asarray(rows, np.int64)
    nl = diag[rows]
    piv_pos = _spans(p_indptr[rows], nl)
    step = piv_pos - np.repeat(p_indptr[rows], nl)
    r = np.repeat(rows, nl)
    h = p_indices[piv_pos]
    tail_start = p_indptr[h] + diag[h] + 1
    tail_len = p_indptr[h + 1] - tail_start
    src = _spans(tail_start, tail_len)
    pp = np.repeat(piv_pos, tail_len)
    rr = np.repeat(r, tail_len)
    key = rr * n + p_indices[src]
    tgt = np.searchsorted(p_key, key)
    tgt_c = np.minimum(tgt, len(p_key) - 1)
    hit = p_key[tgt_c] == key
    return pp[hit], tgt_c[hit], src[hit], np.repeat(step, tail_len)[hit]


def factor(n, p_indptr, p_indices, diag, vals, dtype=np.float32):
    """Sequential ILU on the filled pattern, in ``dtype`` (each quotient,
    product and difference rounded to it). ``vals`` holds A scattered on
    the pattern; returns the factor values in the same layout."""
    f = np.asarray(vals).astype(dtype).copy()
    diag_abs = p_indptr[:-1] + diag
    p_key = entry_keys(n, p_indptr, p_indices)
    level, depth = _levels(n, p_indptr, p_indices, diag)
    order = np.argsort(level, kind="stable")
    bounds = np.searchsorted(level[order], np.arange(depth + 1))
    for d in range(depth):
        rows = order[bounds[d]:bounds[d + 1]]
        if not diag[rows].any():
            continue
        pp, tgt, src, step = updates(n, p_indptr, p_indices, diag, rows, p_key)
        by_step = np.argsort(step, kind="stable")
        pp, tgt, src, step = pp[by_step], tgt[by_step], src[by_step], step[by_step]
        piv_all = _spans(p_indptr[rows], diag[rows])
        piv_step = piv_all - np.repeat(p_indptr[rows], diag[rows])
        s_bounds = np.searchsorted(step, np.arange(int(diag[rows].max()) + 1))
        for t in range(int(diag[rows].max())):
            piv = piv_all[piv_step == t]
            f[piv] = f[piv] / f[diag_abs[p_indices[piv]]]
            lo, hi = s_bounds[t], s_bounds[t + 1]
            prod = f[pp[lo:hi]] * f[src[lo:hi]]
            f[tgt[lo:hi]] = f[tgt[lo:hi]] - prod
    return f


def residual(n, indptr, indices, data, x, b):
    """``||b - A x|| / ||b||`` in float64."""
    a = sp.csr_matrix((np.asarray(data, np.float64), indices, indptr), shape=(n, n))
    b64 = np.asarray(b, np.float64)
    r = b64 - a @ np.asarray(x, np.float64)
    return float(np.linalg.norm(r) / np.linalg.norm(b64))
