"""Bytes and operations each kernel's roofline is measured against.

They count the work the algorithm needs, from the sparsity pattern alone:
every value and index of the operands read once, every result written
once, 4 bytes each (float32 values, int32 indices and row pointers). The
plan's padded ELL or round-major arrays, and gathers repeated through a
cache, are never counted, so a change of layout moves a roofline share and
not its yardstick. The three kernels (triangular sweep, SpMV, numeric
factorization) do about one operation per byte or less, far below the
chip's balance point, so each share is bytes over (device time x HBM
bandwidth); ``roofline_share`` takes the larger of the two bounds all the
same and says which one it was.
"""
from __future__ import annotations

import numpy as np

from bench.references import ilu1

VALUE = INDEX = 4


def spmv(n: int, nnz_a: int) -> dict:
    """y = A x: A's values, column indices and row pointers, x read, y written."""
    return {"bytes": (VALUE + INDEX) * nnz_a + INDEX * (n + 1) + 2 * VALUE * n,
            "ops": 2 * nnz_a}


def sweep(n: int, nnz_f: int) -> dict:
    """x = U^-1 L^-1 b over the filled pattern: every factor value and index
    read once, the row pointers of L and of U, b read and x written. One
    multiply and one subtract per off-diagonal entry, one divide per row."""
    return {"bytes": (VALUE + INDEX) * nnz_f + 2 * INDEX * (n + 1) + 2 * VALUE * n,
            "ops": 2 * (nnz_f - n) + n}


def update_count(n, p_indptr, p_indices, diag, chunk_rows: int = 8192) -> int:
    """Multiply-subtract pairs ILU on this pattern applies: for every lower
    entry (r, h), the entries of row h right of its diagonal that row r
    also holds."""
    key = ilu1.entry_keys(n, p_indptr, p_indices)
    total = 0
    for lo in range(0, n, chunk_rows):
        rows = np.arange(lo, min(lo + chunk_rows, n))
        total += len(ilu1.updates(n, p_indptr, p_indices, diag, rows, key)[0])
    return total


def factor(n: int, nnz_a: int, nnz_f: int, nnz_l: int, updates: int) -> dict:
    """Numeric factorization: A's values read, the filled pattern's indices
    and row pointers read, the factor's values written. One divide per lower
    entry, a multiply and a subtract per update."""
    return {"bytes": VALUE * nnz_a + (VALUE + INDEX) * nnz_f + INDEX * (n + 1),
            "ops": nnz_l + 2 * updates}


def roofline_share(work: dict, seconds: float, peak) -> tuple:
    """(percent of the roofline, which bound) for one call of ``seconds``."""
    t_bytes = work["bytes"] / peak.hbm_bytes_per_s
    t_ops = work["ops"] / peak.bf16_flops_per_s
    bound = "bytes" if t_bytes >= t_ops else "ops"
    return 100.0 * max(t_bytes, t_ops) / seconds, bound
