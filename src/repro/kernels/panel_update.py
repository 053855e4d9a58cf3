"""Pallas TPU kernel: the dense Block-ILU(k) panel update.

:func:`panel_update` — dense trailing-panel LU update ``C <- C - A @ B``,
the FLOP hot-spot of the Block-ILU(k) numeric phase (the MXU adaptation of
the paper's row-merge update, DESIGN.md §3): once fill lives on 128-aligned
tiles, every pivot step is a batch of these panel GEMMs.

Tiling: classic three-loop matmul grid ``(M/bm, N/bn, K/bk)``; the output
block is revisited along k and accumulated in VMEM; the first k-step
initializes from C so the subtraction costs no extra pass over HBM. VMEM
working set per step: bm*bk + bk*bn + bm*bn floats (128³ tiles -> 192 KiB,
far under the ~16 MiB VMEM budget; the default bm=bn=256, bk=128 uses
384 KiB and keeps the MXU pipeline full).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, b_ref, c_ref, o_ref):
    k = pl.program_id(2)
    acc = jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = (c_ref[...].astype(jnp.float32) - acc).astype(o_ref.dtype)

    @pl.when(k > 0)
    def _accum():
        o_ref[...] = (o_ref[...].astype(jnp.float32) - acc).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def panel_update(c, a, b, *, bm=256, bn=256, bk=128, interpret):
    """C - A @ B for (M,K)x(K,N); M,N,K must be multiples of the block sizes
    (ops.py pads). f32 accumulation regardless of input dtype."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and c.shape == (m, n)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    return pl.pallas_call(
        _kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), c.dtype),
        interpret=interpret,
    )(a, b, c)
