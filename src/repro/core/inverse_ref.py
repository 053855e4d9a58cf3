"""Level-based incomplete inverse factors — the sequential bit-compat oracle.

The paper's headline optimization (§V) replaces the two *triangular sweeps*
of the preconditioner apply with precomputed *incomplete inverse* factors:

    M^{-1} = U^{-1} L^{-1}  ~=  Z W,   W ~= L^{-1},  Z ~= U^{-1}

so every apply becomes a short chain of SpMVs (x = Z (W b)) with **no
wavefront recursion at solve time** — the sweep's level-by-level serial
chain is paid once, when the inverse values are computed, instead of on
every Krylov iteration.

Sparsity of the inverse factors is capped by the *same fill-level rule* as
ILU(k) itself: an inverse entry (i, j) produced by the dependency chain
``i -> m -> ... -> j`` through the factor costs the chain's entry levels
plus one per extra hop (``lev = lev_a + lev_b + 1``, exactly the symbolic
fill rule), and survives iff its cheapest chain costs <= k. Diagonals are
level 0 and always kept. With k=0 the inverse pattern equals the factor
pattern (a structurally-ILU(0)-shaped truncated Neumann inverse).

Bit-compat contract (paper abstract): the incomplete inverse method is NOT
bit-compatible with classical ILU(k) — it is a different (weaker, faster)
approximation of M^{-1} — but it IS bit-compatible with the single-threaded
version of *itself*. This module is that single-threaded version: plain
NumPy float32, every reduction an explicit multiply-then-add in ascending
lane order, mirroring ``repro.core.bitmath.masked_lane_sum`` operation for
operation (masked lanes add a literal +0.0; absent inverse entries gather
0.0 *before* the multiply). The JAX engine (``repro.core.inverse``) and
the sharded apply must both reproduce these values
and applies bitwise, on any device count.
"""
from __future__ import annotations

import numpy as np

from .planner import COL_SENTINEL
from .sparse import ILUPattern


def _level_split(pattern: ILUPattern):
    """CSR pattern -> per-row ``(cols, levels)`` of the strict-L / strict-U parts."""
    n = pattern.n
    lower, upper = [], []
    for i in range(n):
        s, e = int(pattern.indptr[i]), int(pattern.indptr[i + 1])
        d = int(pattern.diag_ptr[i])
        cols = pattern.indices[s:e].astype(np.int64)
        levs = pattern.levels[s:e].astype(np.int64)
        lower.append((cols[:d], levs[:d]))
        upper.append((cols[d + 1 :], levs[d + 1 :]))
    return lower, upper


def _closure(rows, order, k: int):
    """Sequential min-plus closure: the level-truncated inverse sparsity.

    ``rows[i] = (cols, levs)`` are row i's strict factor entries (its
    dependencies). Rows are processed in dependency ``order`` (ascending for
    L, descending for U); ``out[m]`` is complete before any i that reads it.
    Pruning at ``> k`` mid-closure is exact: chain costs only grow, so no
    dropped intermediate can support a surviving longer chain.
    """
    out = {}
    for i in order:
        i = int(i)
        best = {i: 0}
        cols, levs = rows[i]
        for m, a in zip(cols.tolist(), levs.tolist()):
            if a <= k and a < best.get(m, k + 1):
                best[m] = a  # the direct entry: the chain i -> m terminates
            for j, b in out[m].items():
                if j == m:
                    continue
                c = a + b + 1  # one extra hop — the ILU(k) fill rule
                if c <= k and c < best.get(j, k + 1):
                    best[j] = c
        out[i] = best
    return [out[i] for i in range(len(rows))]


def inverse_pattern_ref(pattern: ILUPattern, k=None):
    """Level-truncated sparsity of W ~= L^{-1} and Z ~= U^{-1}.

    Returns ``(w_cols, z_cols)`` as sentinel-padded ELL column arrays with
    ascending columns per row; both include the diagonal (W's diagonal
    values are identically 1.0, Z's are 1/U[i,i]). ``k`` defaults to the
    pattern's own fill level.
    """
    k = pattern.k if k is None else int(k)
    n = pattern.n
    lower, upper = _level_split(pattern)
    w = _closure(lower, range(n), k)
    z = _closure(upper, range(n - 1, -1, -1), k)

    def ell(rows):
        wid = max(max((len(r) for r in rows), default=1), 1)
        cols = np.full((n, wid), COL_SENTINEL, np.int32)
        for i, r in enumerate(rows):
            cs = np.sort(np.fromiter(r.keys(), np.int64, len(r)))
            cols[i, : len(cs)] = cs
        return cols

    return ell(w), ell(z)


def inverse_values_ref(
    pattern: ILUPattern, vals: np.ndarray, w_cols: np.ndarray, z_cols: np.ndarray
):
    """Sequential float32 value oracle for the incomplete inverse factors.

    Row i of W solves ``L W = I`` restricted to the truncated pattern:
    ``W[i,j] = d_ij - sum_m L[i,m] W[m,j]`` over row i's strict-L lanes in
    ascending column order (reads outside the pattern gather 0.0); rows
    ascend. Z solves ``U Z = I`` the same way with rows descending and a
    final divide by the diagonal. Arithmetic mirrors ``masked_lane_sum``:
    one f32 rounding per multiply and per add, accumulated in lane order,
    padded lanes contributing a literal +0.0. Returns ``(w_vals, z_vals)``
    aligned with ``w_cols``/``z_cols``; pad lanes hold 0.0.
    """
    from .triangular import _split_lu_ell

    n = pattern.n
    l_cols, l_vals, u_cols, u_vals, diag = _split_lu_ell(pattern, np.asarray(vals, np.float32))

    def sweep(f_cols, f_vals, inv_cols, div, order):
        wid = inv_cols.shape[1]
        out = np.zeros((n, wid), np.float32)
        for i in order:
            i = int(i)
            for t in range(wid):
                j = int(inv_cols[i, t])
                if j >= n:
                    continue  # sentinel pad lane — stays 0.0
                acc = np.float32(0.0)
                for s in range(f_cols.shape[1]):
                    m = int(f_cols[i, s])
                    if m >= n:
                        acc = np.float32(acc + np.float32(0.0))
                        continue
                    p = int(np.searchsorted(inv_cols[m], j))
                    g = out[m, p] if p < wid and inv_cols[m, p] == j else np.float32(0.0)
                    acc = np.float32(acc + np.float32(f_vals[i, s] * g))
                y = np.float32((np.float32(1.0) if j == i else np.float32(0.0)) - acc)
                if div is not None:
                    y = np.float32(y / div[i])
                out[i, t] = y
        return out

    w_vals = sweep(l_cols, l_vals, w_cols, None, range(n))
    z_vals = sweep(u_cols, u_vals, z_cols, diag, range(n - 1, -1, -1))
    return w_vals, z_vals


def inverse_apply_ref(w_cols, w_vals, z_cols, z_vals, b):
    """Sequential oracle apply: ``x = Z (W b)`` — two lane-ordered ELL SpMVs.

    Same lane order and f32 rounding as the engine chain (every device
    count): per row, ``acc += f32(val * x[col])`` ascending lanes, masked
    lanes adding +0.0. Accepts ``b`` of shape (n,) or (nb, n).
    """
    b = np.asarray(b, np.float32)
    if b.ndim == 2:
        return np.stack([inverse_apply_ref(w_cols, w_vals, z_cols, z_vals, bi) for bi in b])

    def spmv(cols, vals_, x):
        n = x.shape[0]
        y = np.zeros(n, np.float32)
        for i in range(n):
            acc = np.float32(0.0)
            for s in range(cols.shape[1]):
                c = int(cols[i, s])
                prod = np.float32(vals_[i, s] * x[c]) if c < n else np.float32(0.0)
                acc = np.float32(acc + prod)
            y[i] = acc
        return y

    return spmv(z_cols, z_vals, spmv(w_cols, w_vals, b))
