"""Bit-deterministic sparse row arithmetic shared by every solve-path engine.

The paper's bit-compatibility guarantee holds only if every implementation
of the same reduction performs the same floating-point operations in the
same order. XLA breaks that silently in two ways:

* ``jnp.sum(..., axis=1)`` may lower to different reduction trees at
  different shapes / fusion contexts, and
* a ``mul`` feeding an ``add`` may be contracted into an FMA in one
  compilation and not another. XLA:CPU contracts wherever it can and
  expands ``optimization_barrier`` before it fuses; what stops it there
  is the lane mask ``select`` between a product and its add, which XLA
  folds away when the mask is a constant — see :class:`hoisted_jit`.

:func:`masked_lane_sum` pins the contract: products are rounded to f32
(through an ``optimization_barrier`` and the lane mask), then accumulated
left-to-right in lane order. Every sparse row reduction on the solve path —
the SpMVs, the wavefront sweeps and the inverse chain — goes through this
helper so they agree bitwise by construction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import obs


class hoisted_jit:
    """``jax.jit(fn)`` for a solve-path engine, compiled so that every
    product is rounded to f32 before the add it feeds, in every context.

    Two things undo the ``optimization_barrier`` contract on XLA:CPU, which
    expands the barriers before it fuses:

    * closed-over arrays (a matvec's ELL arrays, a preconditioner's factor
      arrays) embedded as constants let XLA fold the lane masks of
      :func:`masked_lane_sum` away, and the exposed multiply-add is then
      contracted into an FMA. Here every array ``fn`` closes over is passed
      to the executable as a runtime argument instead;
    * the CPU fusion emitters contract a multiply feeding an add within a
      fusion, and fusion boundaries move with the batch size of a vmapped
      engine (coalesced != solo). The engine is compiled with the classic
      CPU emitters (``xla_cpu_use_fusion_emitters=False``, an option the
      TPU compiler ignores).

    Called inside another trace it inlines ``fn``. ``lower(*args)`` gives
    the AOT form: its ``compile()`` returns a callable with ``fn``'s own
    signature, whose ``compiled`` is XLA's executable.

    ``name`` names the engine: its XLA module is ``jit__eval_<name>``
    (unnamed engines stay ``jit__eval``), so a profile tells the engines
    apart. ``eval_jaxpr`` keeps the ``jax.named_scope`` stack ``fn`` was
    traced under, so the scopes reach the compiled ops' ``op_name``. A
    named engine registers itself with ``obs.note_program`` (a weak
    handle); :meth:`program_texts` gives the HLO text of what its calls
    compiled, and only then is that text made.
    """

    #: XLA options of every engine compile (see the class docstring)
    COMPILER_OPTIONS = {"xla_cpu_use_fusion_emitters": False}

    def __init__(self, fn, name=None):
        self._fn = fn
        self._name = name
        self._traced = {}
        self._run = jax.jit(_named_eval(name), static_argnums=(0, 1),
                            compiler_options=self.COMPILER_OPTIONS)

    def _closed(self, args):
        leaves, tree = jax.tree.flatten(args)
        key = (tree, tuple((getattr(x, "shape", ()), getattr(x, "dtype", type(x)))
                           for x in leaves))
        hit = self._traced.get(key)
        if hit is None:
            closed, out_shape = jax.make_jaxpr(self._fn, return_shape=True)(*args)
            # the last slot: the argument specs of the first call, once noted
            hit = self._traced[key] = [closed, jax.tree.structure(out_shape), None]
        return hit

    def __call__(self, *args):
        if any(isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(args)):
            # inside an enclosing trace: inline, so the enclosing program
            # hoists what ``fn`` closes over along with its own arrays
            return self._fn(*args)
        hit = self._closed(args)
        closed, out_tree, specs = hit
        out = self._run(closed.jaxpr, out_tree, closed.consts, args)
        if self._name and specs is None:
            hit[2] = jax.tree.map(_spec, args)
            obs.note_program(self)
        return out

    def program_texts(self):
        """The HLO text of each program the calls compiled, lowered again
        from the first call's argument specs (jax's caches then give the
        executable that ran)."""
        return [self._run.lower(closed.jaxpr, out_tree, closed.consts, specs).compile().as_text()
                for closed, out_tree, specs in list(self._traced.values()) if specs is not None]

    def lower(self, *args):
        closed, out_tree, _ = self._closed(args)
        lowered = self._run.lower(closed.jaxpr, out_tree, closed.consts, args)
        return _HoistedLowered(lowered, closed.consts, bool(self._name))


def _spec(x):
    """What ``jax.jit`` keys a compile on of one argument: its type, and
    its placement where it was committed to a device."""
    aval = jax.typeof(x)
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype, weak_type=aval.weak_type,
                                sharding=sharding)


def _eval(jaxpr, out_tree, consts, args):
    outs = jax.core.eval_jaxpr(jaxpr, consts, *jax.tree.leaves(args))
    return jax.tree.unflatten(out_tree, outs)


def _named_eval(name):
    """``_eval`` under the function name ``_eval_<name>``: ``jax.jit`` names
    the XLA module ``jit_`` + the function's name."""
    if not name:
        return _eval

    def named(jaxpr, out_tree, consts, args):
        return _eval(jaxpr, out_tree, consts, args)

    named.__name__ = named.__qualname__ = f"_eval_{name}"
    return named


class _HoistedLowered:
    def __init__(self, lowered, consts, note=False):
        self._lowered, self._consts, self._note = lowered, consts, note

    def compile(self):
        out = _HoistedCompiled(self._lowered.compile(), self._consts)
        if self._note:
            obs.note_program(out)
        return out


class _HoistedCompiled:
    """A compiled engine called with ``fn``'s own signature; ``compiled``
    is XLA's executable (``as_text()``, ``memory_analysis()``)."""

    def __init__(self, compiled, consts):
        self.compiled, self._consts = compiled, consts

    def __call__(self, *args):
        return self.compiled(self._consts, args)

    def program_texts(self):
        return [self.compiled.as_text()]


def pairwise_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Fixed-topology pairwise tree sum over the trailing axis.

    ``jnp.sum`` lowers to an XLA reduce whose accumulation order is
    implementation-defined per shape/layout — a vmapped (batched) solve and
    a single solve can round differently. This tree is built from plain
    elementwise adds with a topology fixed by the input length (zero-padded
    to the next power of two), so the bits are identical in every context:
    jit, vmap lanes, shard_map bodies. Cost is log2(n) elementwise adds.
    """
    n = x.shape[-1]
    p = 1 if n <= 1 else 1 << (n - 1).bit_length()
    if p != n:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, p - n)]
        x = jnp.pad(x, widths)
    while x.shape[-1] > 1:
        x = x[..., ::2] + x[..., 1::2]
    return x[..., 0]


def bitdot(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Bit-reproducible dot product: products rounded to f32 through an
    ``optimization_barrier`` (no FMA contraction), pairwise-tree summed."""
    return pairwise_sum(jax.lax.optimization_barrier(x * y))


def bitnorm(x: jnp.ndarray) -> jnp.ndarray:
    """Bit-reproducible 2-norm over the trailing axis."""
    return jnp.sqrt(bitdot(x, x))


def barred(x: jnp.ndarray) -> jnp.ndarray:
    """Round an intermediate product to f32 before it feeds an add —
    blocks FMA contraction, which XLA applies (or not) per fusion context
    and would otherwise let a vmapped solve round differently from a
    single one."""
    return jax.lax.optimization_barrier(x)


_MANT = 0x7FFFFF  # the f32 fraction bits
_HIDDEN = 0x800000  # the implicit leading bit of a normal f32
_MIN_NORMAL, _MAX_FINITE = 0x00800000, 0x7F7FFFFF  # as bit patterns


def _exponent_field(bits):
    return (bits >> 23) & 0xFF


def _significand(bits):
    """The integer significand in [2**23, 2**24) of a normal f32."""
    return (bits & _MANT) | _HIDDEN


def _remainder(A, B, C):
    """``A·2**25 - C·B`` exactly, in int32, for ``A``, ``B`` in
    [2**23, 2**24) and ``C`` below 2**26 with the result below 2**29 in
    magnitude. ``C·B`` is taken in 12-bit limbs, so every product and
    partial sum is an exact int32."""
    c1, c0 = C >> 12, C & 0xFFF
    b1, b0 = B >> 12, B & 0xFFF
    h = (A << 1) - c1 * b1
    k = (h << 12) - (c1 * b0 + c0 * b1)
    return (k << 12) - c0 * b0


def nearest_quotient(a: jnp.ndarray, b: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """The round-to-nearest f32 quotient ``a / b``, from an approximation
    ``q`` at most two ulps off.

    Of ``q`` and its two neighbours on either side, the rounded quotient is
    the ``c`` with the least exact remainder ``|a - c·b|``. The remainders
    are computed in integer arithmetic on the significands, with ``a`` and
    ``b`` taken to [1, 2) and ``c`` to the matching scale by their
    exponents, so they are exact at every magnitude and no multiply-add
    contraction can change them. The quotient of two f32 is never a
    midpoint, so there is no tie. A zero, subnormal or non-finite operand
    or ``q`` passes through as ``q``, and no candidate outside the normal
    range is chosen."""
    i32 = jnp.int32
    ab, bb, qb = (jax.lax.bitcast_convert_type(x, i32) for x in (a, b, q))
    ea, eb = _exponent_field(ab), _exponent_field(bb)
    A, B = _significand(ab), _significand(bb)
    sign, mag = qb & i32(-0x80000000), qb & i32(0x7FFFFFFF)
    best, best_r = mag, jnp.full(mag.shape, jnp.iinfo(i32).max, i32)
    for step in (-2, -1, 0, 1, 2):
        cb = mag + step  # the float ``step`` ulps from |q|
        # c's scale next to a, b in [1, 2): |c|·2**(eb-ea)·2**25 = significand << shift,
        # the shift 0, 1 or 2 for every c within a few ulps of a / b
        shift = _exponent_field(cb) - ea + eb - 125  # the biases: -127 + 2
        r = jnp.abs(_remainder(A, B, _significand(cb) << jnp.clip(shift, 0, 2)))
        usable = (shift >= 0) & (shift <= 2) & (cb >= _MIN_NORMAL) & (cb <= _MAX_FINITE)
        r = jnp.where(usable, r, jnp.iinfo(i32).max)
        best = jnp.where(r < best_r, cb, best)
        best_r = jnp.minimum(r, best_r)
    repaired = jax.lax.bitcast_convert_type(best | sign, jnp.float32)
    normal = ((ea >= 1) & (ea <= 254) & (eb >= 1) & (eb <= 254)
              & (mag >= _MIN_NORMAL) & (mag <= _MAX_FINITE))
    return jnp.where(normal, repaired, q)


def _significand_quotient(a, b):
    """``a / b`` from the hardware divide of the two significands, taken to
    [1, 2), with the exponents and sign put back in integer arithmetic.
    The TPU's divide is within two ulps on such operands; on operands near
    the top of the range it is not (hundreds of ulps off at 1e37 / 1e36).
    Where the quotient leaves the normal range, the plain ``a / b``."""
    i32 = jnp.int32
    ab, bb = (jax.lax.bitcast_convert_type(x, i32) for x in (a, b))
    one = i32(0x3F800000)  # the bits of 1.0
    am, bm = (jax.lax.bitcast_convert_type((x & _MANT) | one, jnp.float32) for x in (ab, bb))
    qb = jax.lax.bitcast_convert_type(am / bm, i32)  # in (0.5, 2)
    ea, eb = _exponent_field(ab), _exponent_field(bb)
    eq = _exponent_field(qb) + ea - eb
    sign = (ab ^ bb) & i32(-0x80000000)
    q = jax.lax.bitcast_convert_type((qb & _MANT) | (jnp.clip(eq, 1, 254) << 23) | sign,
                                     jnp.float32)
    normal = (ea >= 1) & (ea <= 254) & (eb >= 1) & (eb <= 254) & (eq >= 1) & (eq <= 254)
    return jnp.where(normal, q, a / b)


def exact_div(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a / b`` rounded to nearest, as IEEE 754 and the NumPy oracles round
    it. Every division the oracles perform (pivots, the U diagonal, the
    inverse factors' diagonal) goes through here.

    The TPU v5e's f32 divide is not rounded to nearest: it is off by up to
    2 ulps on about a third of random quotients (``chip_smoke.py``'s
    ``divide`` phase counts them), so there it is :func:`rounded_quotient`.
    XLA's CPU divide is IEEE already and is used as it is. The branch is
    chosen when the program is lowered for its platform; the compiled
    program holds only one of them."""
    return jax.lax.platform_dependent(a, b, cpu=jnp.divide, default=rounded_quotient)


def rounded_quotient(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``exact_div`` as it runs on the TPU, on any backend: the quotient of
    the significands, repaired to round to nearest."""
    return nearest_quotient(a, b, _significand_quotient(a, b))


def lane_gather(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``x[idx]`` for an ELL ``(rows, W)`` index array, gathered through its
    lane-major ``(W, rows)`` transpose — the same values. The TPU compiler
    takes about 90 s for a (160000, 5) gather from a vector and about 2 s
    for the transposed one (compiled for v5e)."""
    return x[idx.T].T


_UNROLL = 16  # lanes unrolled per graph node; wider rows scan over chunks


def _lane_chunk(acc, cols, vals, gathered, limit):
    for lane in range(cols.shape[-1]):
        prod = jax.lax.optimization_barrier(vals[..., lane] * gathered[..., lane])
        acc = acc + jnp.where(cols[..., lane] < limit, prod, 0.0)
    return acc


def masked_lane_sum(
    cols: jnp.ndarray, vals: jnp.ndarray, gathered: jnp.ndarray, limit
) -> jnp.ndarray:
    """Sum ``vals * gathered`` over the trailing lane axis where ``cols < limit``.

    ``cols``/``vals``/``gathered`` share shape ``(..., W)``; returns ``(...,)``.
    Lane order is the accumulation order (matches a sequential sweep over a
    sorted sparse row); each product is barriered so it is rounded to f32
    before the add. Rows wider than ``_UNROLL`` lanes are processed as a
    ``lax.scan`` over fixed-size chunks — identical accumulation order
    (chunk-sequential, lane-sequential within a chunk), so the result is
    bitwise independent of the chunking, with graph size O(_UNROLL) instead
    of O(W).
    """
    w = cols.shape[-1]
    if w <= _UNROLL:
        return _lane_chunk(jnp.zeros(cols.shape[:-1], vals.dtype), cols, vals, gathered, limit)
    pad = (-w) % _UNROLL
    if pad:
        widths = [(0, 0)] * (cols.ndim - 1) + [(0, pad)]
        cols = jnp.pad(cols, widths, constant_values=int(limit))  # masked out
        vals = jnp.pad(vals, widths)
        gathered = jnp.pad(gathered, widths)
    nchunk = cols.shape[-1] // _UNROLL

    def to_chunks(x):
        x = x.reshape(x.shape[:-1] + (nchunk, _UNROLL))
        return jnp.moveaxis(x, -2, 0)  # (nchunk, ..., _UNROLL)

    def body(acc, inp):
        c, v, g = inp
        return _lane_chunk(acc, c, v, g, limit), None

    acc0 = jnp.zeros(cols.shape[:-1], vals.dtype)
    acc, _ = jax.lax.scan(body, acc0, (to_chunks(cols), to_chunks(vals), to_chunks(gathered)))
    return acc
