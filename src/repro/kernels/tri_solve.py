"""Pallas TPU kernels: dense-tile triangular solves for Block-ILU(k).

Two panel solves appear in the BILU pivot step:

* ``trsm_right_upper``:  L_JI = A_JI @ U_II^{-1}    (X U = A, U upper)
* ``trsm_left_unit_lower``: U_IJ = L_II^{-1} @ A_IJ (L X = A, L unit-lower)

Each runs substitution *inside* the kernel over the tile's 128 columns/rows
(a serial fori — the MXU still vectorizes the (bm,)xbs panel dot each step),
with the panel dimension tiled by the grid. The diagonal tile is broadcast
to every grid step (index_map pins it to block (0,0)); working set per step
= panel block + diagonal tile + output block ≈ 3*bm*bs floats.

Substitution recurrences are sequential in exact arithmetic order, so the
result is deterministic — required for the bit-compatible solve path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl



def _right_upper_kernel(a_ref, u_ref, o_ref):
    bs = u_ref.shape[0]
    o_ref[...] = jnp.zeros_like(o_ref)
    iota = jax.lax.iota(jnp.int32, bs)

    def col(c, _):
        ucol = jnp.where(iota < c, u_ref[:, c], 0.0)  # (bs,)
        acc = jnp.dot(o_ref[...], ucol, preferred_element_type=jnp.float32)
        x_c = (a_ref[:, c] - acc) / u_ref[c, c]
        o_ref[:, pl.ds(c, 1)] = x_c[:, None].astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, bs, col, 0)


def _left_unit_lower_kernel(l_ref, a_ref, o_ref):
    bs = l_ref.shape[0]
    o_ref[...] = jnp.zeros_like(o_ref)
    iota = jax.lax.iota(jnp.int32, bs)

    def row(r, _):
        lrow = jnp.where(iota < r, l_ref[r, :], 0.0)  # (bs,)
        acc = jnp.dot(lrow, o_ref[...], preferred_element_type=jnp.float32)
        x_r = a_ref[r, :] - acc
        o_ref[pl.ds(r, 1), :] = x_r[None, :].astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, bs, row, 0)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def trsm_right_upper(a, u, *, bm=256, interpret):
    """Solve X U = A. a: (M, bs) panel, u: (bs, bs) upper-triangular tile."""
    m, bs = a.shape
    assert u.shape == (bs, bs)
    bm = min(bm, m)
    assert m % bm == 0
    return pl.pallas_call(
        _right_upper_kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, bs), lambda i: (i, 0)),
            pl.BlockSpec((bs, bs), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, bs), a.dtype),
        interpret=interpret,
    )(a, u)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def trsm_left_unit_lower(l, a, *, bn=256, interpret):
    """Solve L X = A. l: (bs, bs) unit-lower tile, a: (bs, N) panel."""
    bs, n = a.shape
    assert l.shape == (bs, bs)
    bn = min(bn, n)
    assert n % bn == 0
    return pl.pallas_call(
        _left_unit_lower_kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bs, bs), lambda i: (0, 0)),
            pl.BlockSpec((bs, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((bs, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((bs, n), a.dtype),
        interpret=interpret,
    )(l, a)
