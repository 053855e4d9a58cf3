"""Level-based incomplete inverse preconditioning — plan + engines (paper §V).

The execution-layer counterpart of ``repro.core.inverse_ref``: turn the
factorization into level-truncated approximate inverse factors ``W ~= L^{-1}``
and ``Z ~= U^{-1}`` once, so every preconditioner apply is the SpMV chain
``x = Z (W b)`` — two masked lane-ordered ELL products, no wavefront
recursion, and (sharded) no sweep epochs: the only collectives are the two
SpMV halo exchanges.

Plan -> compile -> execute, like every other stage:

* :func:`build_inverse_plan` (host, vectorized) reuses the already-computed
  level machinery of ``build_triangular_plan`` — the same strict-L/U ELL
  split and the same ``wavefront_schedule_ell`` wavefronts (computing W row
  i depends on exactly the rows the L sweep depends on) — and derives the
  truncated inverse sparsity from the oracle's min-plus closure
  (``inverse_pattern_ref``, the same fill-level rule as ILU(k)). It emits
  level-major gather tables so the value engine is one ``lax.scan``.
* :func:`inverse_values_jnp` computes the inverse values on device, one
  wavefront per scan step, every reduction through ``masked_lane_sum`` —
  bitwise equal to ``inverse_values_ref`` by construction.
* :class:`InversePrecondApply` / :class:`ShardedInversePrecondApply` are the
  drop-in ``PrecondApply`` counterparts behind the ``precond_method`` knob.

Bit-compat anchor: *not* the classical ILU(k) sweep (this is a different
approximation of M^{-1}) but the sequential NumPy oracle in
``inverse_ref.py`` — factors, applies, and solves must match it bitwise on
any device count (the paper-abstract contract for the inverse method).

``"auto"`` method selection extends the epoch/read-set sweep cost model
(``ShardedTriangularPlan.comm_summary`` / ``ordering.sweep_comm_model``)
with the SpMV-chain cost (:func:`inverse_comm_model`): the chain always
ships two full vector-slice gathers, the sweep ships exact read sets but
one collective per epoch — whichever modeled cost is lower wins.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .bitmath import exact_div, hoisted_jit, lane_gather, masked_lane_sum
from .inverse_ref import inverse_pattern_ref
from .planner import COL_SENTINEL, wavefront_schedule_ell
from .sparse import ILUPattern


@dataclasses.dataclass
class InversePlan:
    """Inverse sparsity + level-major value-engine tables for both factors.

    ``w_cols``/``z_cols`` are the truncated inverse patterns (sentinel-padded
    ELL, diagonal included). The ``l_*``/``u_*`` tables drive
    :func:`inverse_values_jnp`: per (level, rank) row they carry the strict
    factor lanes (``*_f_cols``/``*_f_vals``), a flat gather address per
    (output lane, factor lane) product into the slot-major inverse storage
    (``*_addr``; misses point at the trailing zero slot), the unit
    right-hand side (``*_rhs``), and the row -> slot map (``*_slot``).
    """

    n: int
    k: int
    w_cols: np.ndarray  # (n, WI) int32
    z_cols: np.ndarray  # (n, ZI) int32
    l_f_cols: np.ndarray  # (nl, maxr_l, WL) int32 — global col ids (mask: < n)
    l_f_vals: np.ndarray  # (nl, maxr_l, WL) f32
    l_addr: np.ndarray  # (nl, maxr_l, WI, WL) int32 into W slot-flat storage
    l_rhs: np.ndarray  # (nl, maxr_l, WI) f32
    l_slot: np.ndarray  # (n,) int64 — row -> W slot
    u_f_cols: np.ndarray  # (nu, maxr_u, WU) int32
    u_f_vals: np.ndarray  # (nu, maxr_u, WU) f32
    u_addr: np.ndarray  # (nu, maxr_u, ZI, WU) int32 into Z slot-flat storage
    u_rhs: np.ndarray  # (nu, maxr_u, ZI) f32
    u_diag: np.ndarray  # (nu, maxr_u) f32, 1-padded
    u_slot: np.ndarray  # (n,) int64 — row -> Z slot

    @property
    def depth(self) -> int:
        """Wavefront depth paid once at value-computation time (the apply
        itself is depth 2 — one SpMV per factor)."""
        return self.l_f_cols.shape[0] + self.u_f_cols.shape[0]

    def nnz_inverse(self) -> int:
        return int((self.w_cols < self.n).sum() + (self.z_cols < self.n).sum())


def _slot_of_row(levels: np.ndarray, n: int) -> np.ndarray:
    """Map row id -> its slot index ``level * maxr + rank`` in the
    level-major tables."""
    slot = np.zeros(n, dtype=np.int64)
    flat = levels.reshape(-1).astype(np.int64)
    valid = flat < n
    slot[flat[valid]] = np.nonzero(valid)[0]
    return slot


def _factor_tables(levels: np.ndarray, f_cols: np.ndarray, f_vals: np.ndarray,
                   inv_cols: np.ndarray, n: int):
    """Level-major tables for one factor's inverse value sweep (vectorized).

    For row i at (level, rank), output lane t (inverse column j), factor
    lane s (dependency row m): the engine accumulates
    ``f_vals[i,s] * Winv[m,j]`` — ``addr[..., t, s]`` resolves (m, j) to its
    flat slot-major storage address, or to the trailing zero slot when the
    truncated pattern dropped (m, j) (the oracle's gathered 0.0).
    """
    nlev, maxr = levels.shape
    WI = inv_cols.shape[1]
    pad = levels >= n
    rows = np.minimum(levels, max(n - 1, 0))
    fc = np.where(pad[:, :, None], COL_SENTINEL, f_cols[rows]).astype(np.int32)
    fv = np.where(pad[:, :, None], 0.0, f_vals[rows]).astype(np.float32)
    slot_of = _slot_of_row(levels, n)
    flat = nlev * maxr * WI

    # global (m, j) -> storage-address lookup over the stored inverse entries;
    # keys ascend (row-major over ascending-column rows) so searchsorted works
    valid = inv_cols < n
    rowm = np.broadcast_to(np.arange(n)[:, None], inv_cols.shape)
    lane = np.broadcast_to(np.arange(WI)[None, :], inv_cols.shape)
    keys = rowm[valid].astype(np.int64) * (n + 1) + inv_cols[valid]
    store = slot_of[rowm[valid]] * WI + lane[valid]

    m_all = fc[:, :, None, :].astype(np.int64)  # (nlev, maxr, 1, WF)
    j_all = np.where(pad[:, :, None], n, inv_cols[rows]).astype(np.int64)[..., None]
    ok = (m_all < n) & (j_all < n)
    q = np.where(ok, m_all * (n + 1) + j_all, 0)
    posn = np.searchsorted(keys, q)
    hit = ok & (posn < keys.size)
    hp = np.where(hit, posn, 0)
    hit &= keys[hp] == q
    addr = np.where(hit, store[hp], flat).astype(np.int32)

    rhs = ((inv_cols[rows] == rows[:, :, None]) & ~pad[:, :, None]).astype(np.float32)
    return fc, fv, addr, rhs, slot_of


def build_inverse_plan(pattern: ILUPattern, vals: np.ndarray, k=None) -> InversePlan:
    """Host planning: truncated inverse sparsity + level-major value tables.

    Reuses the triangular stack's primitives — ``_split_lu_ell`` for the
    strict factor ELL split and ``wavefront_schedule_ell`` for the level
    structure (the W/Z value dependencies are exactly the L/U sweep
    dependencies). ``k`` defaults to the pattern's fill level.
    """
    from .triangular import _split_lu_ell

    k = pattern.k if k is None else int(k)
    n = pattern.n
    vals = np.asarray(vals, np.float32)
    l_cols, l_vals, u_cols, u_vals, diag = _split_lu_ell(pattern, vals)
    w_cols, z_cols = inverse_pattern_ref(pattern, k)
    l_levels = wavefront_schedule_ell(l_cols, n)
    u_levels = wavefront_schedule_ell(u_cols, n)

    lf, lv, la, lr, ls = _factor_tables(l_levels, l_cols, l_vals, w_cols, n)
    uf, uv, ua, ur, us = _factor_tables(u_levels, u_cols, u_vals, z_cols, n)
    pad_u = u_levels >= n
    rows_u = np.minimum(u_levels, max(n - 1, 0))
    u_diag = np.where(pad_u, 1.0, diag[rows_u]).astype(np.float32)

    return InversePlan(
        n=n, k=k, w_cols=w_cols, z_cols=z_cols,
        l_f_cols=lf, l_f_vals=lv, l_addr=la, l_rhs=lr, l_slot=ls,
        u_f_cols=uf, u_f_vals=uv, u_addr=ua, u_rhs=ur, u_diag=u_diag, u_slot=us,
    )


def inverse_values_jnp(f_cols, f_vals, addr, rhs, diag, limit):
    """One factor's level-major inverse value sweep (bit anchor:
    ``inverse_values_ref``).

    Per wavefront: gather the already-computed inverse entries for every
    (row, output lane, factor lane) product, reduce over factor lanes in
    ascending column order through ``masked_lane_sum`` (mask: factor column
    < ``limit`` = n — identical lanes, identical order, identical +0.0
    masking as the sequential oracle), subtract from the unit RHS, divide by
    ``diag`` (U only), and write the wavefront's contiguous slot block.
    Returns the slot-major (n_slots, WI) value array.
    """
    nlev, maxr, WI, WF = addr.shape
    flat = nlev * maxr * WI

    def step(carry, inp):
        w, start = carry
        if diag is None:
            c, v, a, r = inp
        else:
            c, v, a, r, d = inp
        g = w[a]  # (maxr, WI, WF); misses land on the trailing zero slot
        cb = jnp.broadcast_to(c[:, None, :], a.shape)
        vb = jnp.broadcast_to(v[:, None, :], a.shape)
        y = r - masked_lane_sum(cb, vb, g, limit)
        if diag is not None:
            y = exact_div(y, d[:, None])
        w = jax.lax.dynamic_update_slice(w, y.reshape(-1), (start,))
        return (w, start + maxr * WI), None

    inp = (f_cols, f_vals, addr, rhs) + (() if diag is None else (diag,))
    w0 = jnp.zeros(flat + 1, jnp.float32)
    (w, _), _ = jax.lax.scan(step, (w0, jnp.int32(0)), inp)
    return w[:flat].reshape(nlev * maxr, WI)


_values_exec = jax.jit(inverse_values_jnp, static_argnames=("limit",))


def compute_inverse_values(plan: InversePlan):
    """Both factors' inverse values on device: row-major ELL aligned with
    ``plan.w_cols``/``plan.z_cols``, pad lanes normalized to +0.0 (the
    engine's pad-lane arithmetic — e.g. 0/−diag — never escapes; the oracle
    leaves pads at 0.0 and so do we)."""
    n = plan.n
    w = _values_exec(jnp.asarray(plan.l_f_cols), jnp.asarray(plan.l_f_vals),
                     jnp.asarray(plan.l_addr), jnp.asarray(plan.l_rhs),
                     None, limit=n)
    w = jnp.where(jnp.asarray(plan.w_cols) < n, w[jnp.asarray(plan.l_slot)], 0.0)
    z = _values_exec(jnp.asarray(plan.u_f_cols), jnp.asarray(plan.u_f_vals),
                     jnp.asarray(plan.u_addr), jnp.asarray(plan.u_rhs),
                     jnp.asarray(plan.u_diag), limit=n)
    z = jnp.where(jnp.asarray(plan.z_cols) < n, z[jnp.asarray(plan.u_slot)], 0.0)
    return w, z


def inverse_chain_jnp(w_cols, w_vals, z_cols, z_vals, b):
    """x = Z (W b): the fused two-SpMV preconditioner apply. Both SpMVs
    reduce via ``masked_lane_sum``, so it is bit-identical to
    ``inverse_apply_ref``."""
    n = b.shape[0]
    b = b.astype(jnp.float32)
    y = masked_lane_sum(w_cols, w_vals, lane_gather(b, jnp.minimum(w_cols, n - 1)),
                        COL_SENTINEL)
    return masked_lane_sum(z_cols, z_vals, lane_gather(y, jnp.minimum(z_cols, n - 1)),
                           COL_SENTINEL)


class InversePrecondApply:
    """Cached, device-resident M^{-1} ~= Z W apply — ``PrecondApply``'s
    drop-in counterpart for ``precond_method="inverse"``.

    Builds the inverse plan once, computes the inverse values on device
    (one scan per factor — the wavefront chain is paid here, not per
    apply), and exposes the same surface as ``PrecondApply``:

    * ``apply(b)`` / ``__call__`` — jitted fused SpMV chain, safe inside
      outer jitted code;
    * ``batched(B)`` — the chain ``vmap``-ped over a RHS stack;
    * ``warm(batch_sizes)`` — AOT compilation for the serving hot path.
    """

    def __init__(self, pattern: ILUPattern, vals: np.ndarray,
                 k=None, plan: Optional[InversePlan] = None):
        self.plan = plan if plan is not None else build_inverse_plan(pattern, vals, k=k)
        self.n = self.plan.n
        self.w_cols = jnp.asarray(self.plan.w_cols)
        self.z_cols = jnp.asarray(self.plan.z_cols)
        self.w_vals, self.z_vals = compute_inverse_values(self.plan)

        def _raw(b):
            return inverse_chain_jnp(self.w_cols, self.w_vals, self.z_cols, self.z_vals,
                                     b.astype(jnp.float32))

        # compiled with the ELL arrays as runtime operands, like every
        # solve engine (bitmath.hoisted_jit)
        self._apply = hoisted_jit(_raw, name="inverse_apply")
        self._batched = hoisted_jit(jax.vmap(_raw), name="inverse_apply_batched")
        self._aot = {}

    def __call__(self, b):
        ex = self._aot.get(1)
        if ex is not None and not isinstance(b, jax.core.Tracer):
            return ex(jnp.asarray(b, jnp.float32))
        return self._apply(b)

    apply = __call__

    def batched(self, bs):
        """Apply to a (batch, n) stack. If ``warm`` prepared a bucket >=
        batch, the stack zero-pads to it (vmap lanes are independent)."""
        if isinstance(bs, jax.core.Tracer):
            return self._batched(bs)
        bs = jnp.asarray(bs, jnp.float32)
        nb = bs.shape[0]
        fit = [w for w in self._aot if w != 1 and w >= nb]
        if not fit:
            return self._batched(bs)
        tgt = min(fit)
        if tgt > nb:
            bs = jnp.concatenate([bs, jnp.zeros((tgt - nb, self.n), jnp.float32)])
        return self._aot[tgt](bs)[:nb]

    def warm(self, batch_sizes=(1,)):
        """AOT-compile the chain for the given RHS batch sizes (1 = the
        single-RHS apply). Returns {batch_size: compile_seconds}."""
        import time

        out = {}
        for nb in batch_sizes:
            t0 = time.perf_counter()
            if nb not in self._aot:
                if nb == 1:
                    sds = jax.ShapeDtypeStruct((self.n,), jnp.float32)
                    self._aot[1] = self._apply.lower(sds).compile()
                else:
                    sds = jax.ShapeDtypeStruct((nb, self.n), jnp.float32)
                    self._aot[nb] = self._batched.lower(sds).compile()
            out[nb] = time.perf_counter() - t0
        return out


class ShardedInversePrecondApply:
    """Row-block sharded M^{-1} ~= Z W apply: the distributed SpMV chain.

    The inverse values are computed once by the single-device engine (the
    bitwise anchor holds for any device count because the values *are* the
    single-device values) and the W/Z ELL blocks are then placed row-block
    sharded over the mesh's band axis. Each apply is two sharded SpMVs: a
    device reduces its own rows through ``masked_lane_sum`` (the same lanes
    in the same order as single-device, hence bitwise equal) and ONE
    ``all_gather`` per SpMV reassembles the replicated vector — the only
    collectives on the apply path. No sweep epochs, no read-set fusion, and
    the collective count is independent of wavefront depth: 2 per apply,
    amortized over the whole RHS batch (``batched``).
    """

    AXIS = "band"

    def __init__(self, pattern: ILUPattern, vals: np.ndarray, mesh, k=None,
                 base: Optional[InversePrecondApply] = None,
                 plan: Optional[InversePlan] = None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from jax import shard_map

        if base is None:
            base = InversePrecondApply(pattern, vals, k=k, plan=plan)
        self.base = base
        self.plan = base.plan
        self.mesh = mesh
        self.n = n = base.n
        D = int(mesh.devices.size)
        self.n_devices = D
        rows_loc = -(-n // D)
        n_pad = rows_loc * D
        self._n_pad = n_pad
        ax = self.AXIS

        def pad_rows(cols, vals_):
            cols, vals_ = np.asarray(cols), np.asarray(vals_)
            if n_pad > n:
                cols = np.concatenate([cols, np.full(
                    (n_pad - n, cols.shape[1]), COL_SENTINEL, np.int32)])
                vals_ = np.concatenate([vals_, np.zeros((n_pad - n, vals_.shape[1]), np.float32)])
            return cols, vals_

        wc, wv = pad_rows(self.plan.w_cols, base.w_vals)
        zc, zv = pad_rows(self.plan.z_cols, base.z_vals)
        sh = NamedSharding(mesh, P(ax, None))
        self._args = tuple(jax.device_put(jnp.asarray(x), sh) for x in (wc, wv, zc, zv))

        def chain(wc, wv, zc, zv, b):
            def one(b1):
                y_loc = masked_lane_sum(wc, wv, lane_gather(b1, jnp.minimum(wc, n - 1)),
                                        COL_SENTINEL)
                # untiled (D, rows_loc) gather + reshape: row blocks are
                # contiguous in device order, so this is the (n_pad,) vector
                # — and unlike tiled=True its vmap batching is bit-stable
                y = jax.lax.all_gather(y_loc, ax).reshape(-1)
                x_loc = masked_lane_sum(zc, zv, lane_gather(y, jnp.minimum(zc, n_pad - 1)),
                                        COL_SENTINEL)
                x = jax.lax.all_gather(x_loc, ax).reshape(-1)
                return x[:n]
            return jax.vmap(one)(b.astype(jnp.float32))

        self._sm = jax.jit(shard_map(
            chain, mesh=mesh,
            in_specs=(P(ax, None), P(ax, None), P(ax, None), P(ax, None),
                      P(None, None)),
            out_specs=P(None, None), check_vma=False))
        self._aot = {}

    def _chain(self, b2):
        nb = b2.shape[0]
        ex = self._aot.get(nb)
        if ex is not None and not isinstance(b2, jax.core.Tracer):
            return ex(*self._args, b2)
        return self._sm(*self._args, b2)

    def __call__(self, b):
        if getattr(b, "ndim", 1) == 2:
            return self.batched(b)
        if isinstance(b, jax.core.Tracer):
            return self._chain(b[None, :])[0]
        b2 = jnp.asarray(np.asarray(b, np.float32).reshape(1, -1))
        return self._chain(b2)[0]

    apply = __call__

    def batched(self, bs):
        """Apply to a (nb, n) stack — both collectives carry the whole
        batch. A warmed bucket >= nb absorbs ragged batches by padding."""
        bs = bs if isinstance(bs, jax.core.Tracer) else jnp.asarray(bs, jnp.float32)
        nb = bs.shape[0]
        if not isinstance(bs, jax.core.Tracer):
            fit = [w for w in self._aot if w >= nb]
            if fit and nb not in self._aot:
                tgt = min(fit)
                bs = jnp.concatenate([bs, jnp.zeros((tgt - nb, self.n), jnp.float32)])
        return self._chain(bs)[:nb]

    def lower(self, nb: int = 1):
        """AOT-lower the chain for a (nb, n) batch (HLO inspection + warm)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        def sds(arr):
            return jax.ShapeDtypeStruct(arr.shape, arr.dtype, sharding=arr.sharding)

        b_s = jax.ShapeDtypeStruct(
            (nb, self.n), jnp.float32,
            sharding=NamedSharding(self.mesh, P(None, None)))
        return self._sm.lower(*[sds(a) for a in self._args], b_s)

    def warm(self, batch_sizes=(1,)):
        """AOT-compile the chain for the given RHS batch sizes."""
        import time

        out = {}
        for nb in batch_sizes:
            t0 = time.perf_counter()
            if nb not in self._aot:
                self._aot[nb] = self.lower(nb).compile()
            out[nb] = time.perf_counter() - t0
        return out


# --------------------------------------------------------------------------
# the "auto" cost model: sweep epochs vs the SpMV chain
# --------------------------------------------------------------------------
# modeled fixed cost of one collective, in payload-byte equivalents — the
# latency term that makes many small epoch exchanges lose to two big
# vector-slice gathers (and a single cheap assembly beat them back)
AUTO_COLLECTIVE_COST_BYTES = 4096


def inverse_comm_model(n: int, n_devices: int, nb: int = 1) -> dict:
    """The SpMV-chain communication record, same schema as the sweep's
    ``comm_summary``: two all_gathers per apply, each shipping this device's
    ceil(n/D) vector slice to the D-1 others (ring model), amortized over
    the whole RHS batch."""
    D = int(n_devices)
    if D <= 1:
        return {"n_devices": 1, "collectives_per_apply": 0,
                "payload_slots_per_apply": 0, "bytes_per_apply": 0}
    rows_loc = -(-int(n) // D)
    return {
        "n_devices": D,
        "collectives_per_apply": 2,
        "payload_slots_per_apply": 2 * rows_loc,
        "bytes_per_apply": (D - 1) * 2 * rows_loc * 4 * nb,
    }


def modeled_apply_cost(summary: dict) -> int:
    """Scalar cost of one preconditioner apply from a communication record
    (sweep ``comm_summary`` or :func:`inverse_comm_model`): per-collective
    latency plus wire bytes."""
    return (summary["collectives_per_apply"] * AUTO_COLLECTIVE_COST_BYTES
            + summary["bytes_per_apply"])


def resolve_precond_method(method: str, pattern: Optional[ILUPattern] = None,
                           n_devices: int = 1, band_rows: int = 32,
                           sweep_summary: Optional[dict] = None) -> str:
    """Resolve ``precond_method`` ("sweep" | "inverse" | "auto").

    ``"auto"`` picks per matrix: single-device always sweeps (the exact
    apply, no collectives either way, fewer Krylov iterations); distributed,
    the modeled sweep cost (epoch collectives + exact read-set bytes, from
    ``comm_summary``) races the modeled SpMV-chain cost
    (:func:`inverse_comm_model`) and the cheaper apply wins. Pass
    ``sweep_summary`` to reuse an existing plan's record; otherwise one is
    modeled from ``pattern`` via ``ordering.sweep_comm_model``.
    """
    if method not in ("sweep", "inverse", "auto"):
        raise ValueError(f"precond_method must be 'sweep', 'inverse' or 'auto', got {method!r}")
    if method != "auto":
        return method
    if n_devices <= 1:
        return "sweep"
    if sweep_summary is None:
        from .ordering import sweep_comm_model

        sweep_summary = sweep_comm_model(pattern, band_rows, n_devices)
    n = pattern.n if pattern is not None else None
    inv = inverse_comm_model(n, n_devices)
    return ("inverse" if modeled_apply_cost(inv) < modeled_apply_cost(sweep_summary) else "sweep")
