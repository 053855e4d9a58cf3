"""Random strictly diagonally dominant sparse matrix, the distribution of
the paper's ``matgen``: each row holds ``per_row - 1`` distinct
off-diagonal columns drawn uniformly, values uniform in [-1, 1], and the
diagonal ``sum(|off-diagonal|) + margin``. Vectorized over rows; it draws
from the same distribution as the program's generator, not the same bits.

The sparsity pattern is drawn from ``pattern_seed`` of the configuration,
the values from the run's seed: a deployment solves many systems on one
pattern, and every seed then gets the same amount of work (the pattern
fixes the plan, its shapes and the compiled programs).
"""
from __future__ import annotations

import numpy as np


def off_diagonal_columns(n: int, m: int, rng: np.random.Generator) -> np.ndarray:
    """(n, m) sorted distinct columns per row, none on the diagonal."""
    cols = rng.integers(0, n - 1, size=(n, m))
    while True:
        s = np.sort(cols, axis=1)
        dup = np.flatnonzero((s[:, 1:] == s[:, :-1]).any(axis=1))
        if dup.size == 0:
            break
        cols[dup] = rng.integers(0, n - 1, size=(dup.size, m))
    s = s + (s >= np.arange(n)[:, None])  # skip the diagonal
    return np.sort(s, axis=1)


def dominant_values(n: int, m: int, margin: float, rng: np.random.Generator):
    """(n, m) off-diagonal values in [-1, 1] and the (n,) diagonal that makes
    each row strictly dominant by ``margin``."""
    off = rng.uniform(-1.0, 1.0, size=(n, m)).astype(np.float32)
    diag = (np.abs(off).sum(axis=1, dtype=np.float32) + np.float32(margin)).astype(np.float32)
    return off, diag


def assemble(n: int, off_cols: np.ndarray, off: np.ndarray, diag: np.ndarray) -> dict:
    """CSR of the rows, columns sorted, the diagonal in its place."""
    m = off_cols.shape[1]
    cols = np.concatenate([off_cols, np.arange(n)[:, None]], axis=1)
    vals = np.concatenate([off, diag[:, None]], axis=1)
    order = np.argsort(cols, axis=1, kind="stable")
    cols = np.take_along_axis(cols, order, axis=1)
    vals = np.take_along_axis(vals, order, axis=1)
    indptr = np.arange(n + 1, dtype=np.int64) * (m + 1)
    return {"n": n, "indptr": indptr, "indices": cols.reshape(-1).astype(np.int32),
            "data": vals.reshape(-1).astype(np.float32)}


def generate(params: dict, rng: np.random.Generator) -> dict:
    n, m = int(params["n"]), int(params["per_row"]) - 1
    cols = off_diagonal_columns(n, m, np.random.default_rng(int(params["pattern_seed"])))
    off, diag = dominant_values(n, m, float(params["margin"]), rng)
    return assemble(n, cols, off, diag)
