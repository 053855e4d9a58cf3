"""Jit'd public wrappers around the Pallas kernels: padding to tile
multiples, and the kernel mode, which the platform chooses (interpret mode
on the CPU, the compiled Mosaic lowering on a TPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import panel_update as _pu
from . import tri_solve as _ts


def interpret_mode() -> bool:
    """True on the CPU (the Pallas interpreter), False on a TPU (compiled);
    any other backend has no Pallas lowering here and raises."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel mode for backend {backend!r}")


def _pad2(x, m0, m1, fill=0.0):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)), constant_values=fill)
    return x


def panel_update(c, a, b, bm=256, bn=256, bk=128):
    """C - A @ B with automatic padding to block multiples."""
    m, n = c.shape
    k = a.shape[1]
    bm_, bn_, bk_ = min(bm, max(m, 8)), min(bn, max(n, 8)), min(bk, max(k, 8))
    cp = _pad2(c, bm_, bn_)
    ap = _pad2(a, bm_, bk_)
    bp = _pad2(b, bk_, bn_)
    out = _pu.panel_update(cp, ap, bp, bm=bm_, bn=bn_, bk=bk_, interpret=interpret_mode())
    return out[:m, :n]


def trsm_right_upper(a, u, bm=256):
    """X = A @ U^{-1} (U upper-triangular)."""
    m, bs = a.shape
    bm_ = min(bm, max(m, 8))
    ap = _pad2(a, bm_, bs)
    out = _ts.trsm_right_upper(ap, u, bm=bm_, interpret=interpret_mode())
    return out[:m]


def trsm_left_unit_lower(l, a, bn=256):
    """X = L^{-1} @ A (L unit-lower-triangular)."""
    bs, n = a.shape
    bn_ = min(bn, max(n, 8))
    ap = _pad2(a, bs, bn_)
    out = _ts.trsm_left_unit_lower(l, ap, bn=bn_, interpret=interpret_mode())
    return out[:, :n]
