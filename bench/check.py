"""The comparison that decides ``correct``: what the timed path produced,
against the configuration's plain reference (``bench/references/``).

Three numbers, each with a limit in ``bench/limits/<workload>.json``:

* ``factor_bits_differ``: factor entries whose float32 bits differ from
  the sequential reference factor of the same values (the program's
  bit-compatibility guarantee; every entry of a factor whose pattern
  differs counts). Exact: limit 0.
* ``residual_over_tol``: the largest ``||b - A x|| / ||b||``, in float64,
  of a checked solution, over the tolerance it was asked for.
* ``missing``: requests or steps of the window that never got an ok
  answer. Limit 0.

The control (``control.py``) puts the same reference, computed in
bfloat16, in the program's place.
"""
from __future__ import annotations

import numpy as np

from bench.harness import Run, load_module


def reference(run: Run):
    return load_module(run.root, "references", run.config["reference"])


def reference_pattern(run: Run):
    """Cached ``(indptr, indices, diag)`` of the filled pattern."""
    if "pattern" not in run.state:
        m = run.matrix
        run.state["pattern"] = reference(run).pattern(m["n"], m["indptr"], m["indices"])
    return run.state["pattern"]


def reference_factor(run: Run, data, dtype=np.float32) -> np.ndarray:
    m = run.matrix
    ref = reference(run)
    p_indptr, p_indices, diag = reference_pattern(run)
    vals = ref.scatter(m["n"], p_indptr, p_indices, m["indptr"], m["indices"], data, dtype)
    return ref.factor(m["n"], p_indptr, p_indices, diag, vals, dtype)


def factor_bits_differ(run: Run, got_pattern, got_vals, want_vals) -> int:
    """Entries of the factor whose bits differ; all of them where the
    program's filled pattern ``(indptr, indices)`` is not the reference's."""
    p_indptr, p_indices, _ = reference_pattern(run)
    g_indptr, g_indices = (np.asarray(x) for x in got_pattern)
    got = np.asarray(got_vals, np.float32)
    if (not np.array_equal(g_indptr, p_indptr) or not np.array_equal(g_indices, p_indices)
            or got.shape != want_vals.shape):
        return int(len(want_vals))
    want = np.asarray(want_vals, np.float32)
    return int(np.count_nonzero(got.view(np.int32) != want.view(np.int32)))


def residual_over_tol(run: Run, data, x, b, tol: float) -> float:
    m = run.matrix
    r = reference(run).residual(m["n"], m["indptr"], m["indices"], data, x, b)
    return float(r / tol) if np.isfinite(r) else float("inf")


def rhs(run: Run, data, x_true: np.ndarray) -> np.ndarray:
    """``b = A x_true`` in float64, rounded to float32: a right-hand side
    whose solution is known, so a float32 solve can reach the tolerance."""
    import scipy.sparse as sp

    m = run.matrix
    a = sp.csr_matrix((np.asarray(data, np.float64), m["indices"], m["indptr"]),
                      shape=(m["n"], m["n"]))
    return (a @ x_true.astype(np.float64)).astype(np.float32)


def program_matrix(run: Run):
    """The generated matrix in the program's own container."""
    from repro.core.sparse import CSRMatrix

    m = run.matrix
    return CSRMatrix(n=m["n"], indptr=np.asarray(m["indptr"], np.int64),
                     indices=np.asarray(m["indices"], np.int32),
                     data=np.asarray(m["data"], np.float32))


def bf16_round(x) -> np.ndarray:
    """``x`` rounded to bfloat16 and back to float32."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def control_factor_bits(run: Run, data) -> int:
    """The reference factor computed in bfloat16 put in the program's place."""
    import ml_dtypes

    want = reference_factor(run, data)
    got = reference_factor(run, data, ml_dtypes.bfloat16).astype(np.float32)
    p_indptr, p_indices, _ = reference_pattern(run)
    return factor_bits_differ(run, (p_indptr, p_indices), got, want)
