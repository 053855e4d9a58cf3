"""The 5-point Laplacian on an nx x nx grid (Saad, Iterative Methods for
Sparse Linear Systems, section 2.2): 4 on the diagonal, -1 to each grid
neighbour, rows in natural (row-major) grid order. The seed is not used:
the matrix is fixed by its size."""
from __future__ import annotations

import numpy as np


def generate(params: dict, rng: np.random.Generator) -> dict:
    nx = int(params["nx"])
    n = nx * nx
    i, j = np.divmod(np.arange(n), nx)
    rows, cols, vals = [np.arange(n)], [np.arange(n)], [np.full(n, 4.0)]
    for di, dj in ((-1, 0), (0, -1), (0, 1), (1, 0)):
        ok = (i + di >= 0) & (i + di < nx) & (j + dj >= 0) & (j + dj < nx)
        r = np.flatnonzero(ok)
        rows.append(r)
        cols.append(r + di * nx + dj)
        vals.append(np.full(len(r), -1.0))
    r, c, v = (np.concatenate(x) for x in (rows, cols, vals))
    order = np.lexsort((c, r))
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(r, minlength=n), out=indptr[1:])
    return {"n": n, "indptr": indptr, "indices": c[order].astype(np.int32),
            "data": v[order].astype(np.float32)}
