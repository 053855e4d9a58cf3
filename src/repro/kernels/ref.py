"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel's sweep test asserts against these references across shapes and
dtypes. The substitution-order references run the kernels' exact
recurrences, so kernel and reference agree *bitwise*, not just to
tolerance.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp



def panel_update_ref(c, a, b):
    """Trailing-panel LU update: C - A @ B (f32 accumulation)."""
    acc = jnp.dot(a, b, preferred_element_type=jnp.float32)
    return (c.astype(jnp.float32) - acc).astype(c.dtype)


def trsm_right_upper_ref(a, u):
    """Solve X U = A with U upper-triangular (the BILU L-panel step:
    L_JI = A_JI @ U_II^{-1})."""
    xt = jax.scipy.linalg.solve_triangular(
        u.T.astype(jnp.float32), a.T.astype(jnp.float32), lower=True
    )
    return xt.T.astype(a.dtype)


def trsm_left_unit_lower_ref(l, a):
    """Solve L X = A with L unit-lower (the BILU U-panel step:
    U_IJ = L_II^{-1} @ A_IJ)."""
    x = jax.scipy.linalg.solve_triangular(
        l.astype(jnp.float32), a.astype(jnp.float32), lower=True, unit_diagonal=True
    )
    return x.astype(a.dtype)


def trsm_right_upper_subst_ref(a, u):
    """Substitution-order oracle for ``trsm_right_upper`` — the exact
    column-by-column recurrence the kernel runs, in plain jnp. Use for
    bitwise comparisons; `trsm_right_upper_ref` (LAPACK-style) only to
    tolerance."""
    bs = u.shape[0]
    iota = jax.lax.iota(jnp.int32, bs)
    x = jnp.zeros_like(a)

    def col(c, x):
        ucol = jnp.where(iota < c, u[:, c], 0.0)
        acc = jnp.dot(x, ucol, preferred_element_type=jnp.float32)
        return x.at[:, c].set(((a[:, c] - acc) / u[c, c]).astype(a.dtype))

    return jax.lax.fori_loop(0, bs, col, x)


def trsm_left_unit_lower_subst_ref(l, a):
    """Substitution-order oracle for ``trsm_left_unit_lower`` (row-by-row
    forward recurrence); bitwise counterpart of the kernel."""
    bs = l.shape[0]
    iota = jax.lax.iota(jnp.int32, bs)
    x = jnp.zeros_like(a)

    def row(r, x):
        lrow = jnp.where(iota < r, l[r, :], 0.0)
        acc = jnp.dot(lrow, x, preferred_element_type=jnp.float32)
        return x.at[r, :].set((a[r, :] - acc).astype(a.dtype))

    return jax.lax.fori_loop(0, bs, row, x)
