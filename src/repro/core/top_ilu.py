"""TOP-ILU — task-oriented parallel ILU(k) over a device mesh (paper §IV).

Maps the paper's distributed-memory algorithm onto JAX SPMD, re-emitted
(PR 3) over the *sharded value layout* from the planner:

* bands → round-robin ownership over the mesh axis (static load balancing,
  §IV-D; device ``d`` owns bands ``{b : b ≡ d (mod D)}``),
* value storage → **sharded**: each device holds only its bands' values
  (``s_loc = n_pad/D`` rows) plus a halo of the finalized foreign pivot
  rows it actually consumes, precomputed on the host from the band
  superstep schedule (``planner._halo_exchange_schedule``). Nothing is
  replicated on the value path, so the largest solvable system scales with
  the *mesh*, not with one device's memory — the paper's §IV point,
* the frontier loop → ``lax.fori_loop`` over band-dependency *wavefronts*
  inside one jitted step: bands whose dependencies are satisfied factor
  concurrently, pulling pivot rows from local storage or the halo,
* the Fig-4 ring pipeline → ONE halo exchange per superstep — an XLA ring
  ``all_gather`` of each device's (E, W) egress payload (``broadcast=
  'psum'`` is accepted as the historical alias for this fast path) or an
  explicit ``ppermute`` directed ring (``broadcast='ring'``) — shipping
  only the pivot rows another device needs, instead of every finished band,
* dynamic load balancing (master/worker) → intentionally absent from the
  SPMD fast path; the paper itself measures static LB as strictly better
  (Table I). It survives as the fault-tolerance reassignment path in
  ``repro.runtime``.

Structure (column indices, destination-lane maps, the schedule itself) is
static planning output and never communicated: 4 bytes/entry on the wire
instead of the paper's 8 — see §V-E and DESIGN.md §5. The factorization
output stays device-resident as a :class:`ShardedILUFactorization`, whose
``precond()``/``solve`` consume the sharded values in place — distributed
solves never re-replicate L/U onto one device.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from .planner import NumericPlan, make_plan
from .numeric_jax import (
    make_superstep_factorizer,
    plan_device_arrays,
    plan_shard_specs,
    plan_state_array,
)
from .sparse import CSRMatrix, ILUPattern

AXIS = "band"

_ARG_ORDER = ("state", "sched", "piv_addr", "piv_dlane", "piv_dst", "n_piv", "egress", "ingress")


def _values_to_csr_order(plan: NumericPlan, pattern: ILUPattern, vals_rm: np.ndarray) -> np.ndarray:
    """Padded row-major values -> CSR-aligned flat values (one gather)."""
    vals_rm = np.asarray(vals_rm)
    rowlen = np.diff(pattern.indptr).astype(np.int64)
    row_of = np.repeat(np.arange(pattern.n, dtype=np.int64), rowlen)
    lane = np.arange(pattern.nnz, dtype=np.int64) - pattern.indptr[row_of]
    return vals_rm[row_of, lane].astype(np.float32)


def band_mesh(mesh: Optional[Mesh] = None) -> Mesh:
    """Default 1-D ``(band,)`` mesh over every available device."""
    if mesh is not None:
        return mesh
    from repro.launch.mesh import make_band_mesh

    return make_band_mesh()


@dataclasses.dataclass
class ShardedILUFactorization:
    """Device-resident sharded factorization output (DESIGN.md §5).

    ``loc_vals`` is a jax array of shape (D, s_loc, W) — the factored ELL
    values in device-major band order, sharded over the mesh's band axis so
    each device holds only its own (s_loc, W) block. The preconditioner
    apply (:meth:`precond`) and the distributed solve consume it in place;
    :meth:`values_csr` gathers to the host only when explicitly asked
    (tests / interop), it is not on any solve path.
    """

    a: CSRMatrix
    k: int
    pattern: ILUPattern
    plan: NumericPlan
    mesh: Mesh
    loc_vals: jax.Array  # (D, s_loc, W) f32, sharded over AXIS
    symbolic_seconds: float = 0.0
    numeric_seconds: float = 0.0
    # the row ordering the system was permuted with before factoring
    # (None = natural). ``a``/``pattern``/``loc_vals`` describe the
    # *permuted* system; ``solve`` un/permutes at the boundary, while
    # ``precond()`` stays in permuted row space (``solve_sharded`` owns
    # the boundary on that path).
    ordering: Optional[object] = None
    # how M^{-1} applies: "sweep" (epoch-scheduled triangular sweeps),
    # "inverse" (the incomplete-inverse SpMV chain — two collectives per
    # apply, no epochs), or "auto" (cost-modeled per matrix)
    precond_method: str = "sweep"
    # pivot-guard audit (core.guard.FactorHealth) — None when the guard was
    # bypassed; ``health.shift`` > 0 means this factorization describes the
    # diagonally shifted system, and ``health.degraded`` routes
    # ``precond()`` to the identity fallback
    health: Optional[object] = None
    # structure-keyed shared cache (the engine-store entry): the sharded
    # triangular plan + compiled sweep live here, so refactorizations of
    # the same structure rebind values to one compiled solve engine
    _shared: dict = dataclasses.field(default_factory=dict, repr=False, compare=False)
    _preconds: dict = dataclasses.field(default_factory=dict, repr=False, compare=False)

    @property
    def nnz(self) -> int:
        return self.pattern.nnz

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    def per_device_value_bytes(self) -> int:
        return self.plan.per_device_value_bytes()

    def values_csr(self) -> np.ndarray:
        """Gather the sharded factors to the host as CSR-aligned values."""
        dm = np.asarray(self.loc_vals).reshape(self.plan.n_pad, self.plan.width)
        return _values_to_csr_order(self.plan, self.pattern, self.plan.rows_from_device_major(dm))

    def _tri_plan(self):
        """The structure-keyed sharded triangular plan (built on demand)."""
        from .triangular import build_sharded_triangular_plan

        tp = self._shared.get("tri_plan")
        if tp is None:
            tp = self._shared["tri_plan"] = build_sharded_triangular_plan(
                self.pattern, self.plan.band_rows, self.n_devices)
        return tp

    def resolve_method(self, method: Optional[str] = None) -> str:
        """Resolve ``precond_method`` for this mesh: ``"auto"`` races the
        sweep plan's actual ``comm_summary`` (epoch collectives + exact
        read-set bytes) against the SpMV-chain model and returns the
        cheaper apply."""
        from .inverse import resolve_precond_method

        method = method if method is not None else self.precond_method
        summary = self._tri_plan().comm_summary() if method == "auto" else None
        return resolve_precond_method(method, self.pattern, self.n_devices,
                                      self.plan.band_rows, sweep_summary=summary)

    def precond(self, broadcast: str = "gather", method: Optional[str] = None):
        """Cached band-partitioned M^{-1} apply over the sharded values.

        ``method`` (default: this factorization's ``precond_method``) picks
        the engine. ``"sweep"`` →
        ``repro.core.triangular.ShardedPrecondApply``: L/U storage stays
        sharded and the sweep vector is device-local; communication follows
        the epoch/read-set schedule (DESIGN.md §5.5), with ``broadcast``
        choosing the XLA ``all_gather`` fast path (``"gather"``/``"psum"``)
        or the explicit ``ppermute`` ring (``"ring"``). The triangular plan
        and its compiled sweep are structure-keyed (shared across
        refactorizations); this factorization's values bind to them via one
        jitted on-device extract. ``"inverse"`` →
        ``repro.core.inverse.ShardedInversePrecondApply``: the truncated
        inverse SpMV chain, two collectives per apply regardless of
        wavefront depth (``broadcast`` is moot — both exchanges are plain
        all_gathers). ``"auto"`` races the two cost models."""
        if self.health is not None and self.health.degraded:
            # shift-ladder exhaustion under on_breakdown="fallback":
            # sweeping the broken factor would NaN every lane, so M^{-1}=I
            from .guard import IdentityPrecondApply

            return self._preconds.setdefault("identity", IdentityPrecondApply())
        method = self.resolve_method(method)
        if method == "inverse":
            if "inverse" not in self._preconds:
                from .inverse import ShardedInversePrecondApply

                self._preconds["inverse"] = ShardedInversePrecondApply(
                    self.pattern, self.values_csr(), self.mesh)
            return self._preconds["inverse"]
        if broadcast == "psum":
            broadcast = "gather"
        if broadcast not in self._preconds:
            from .triangular import ShardedPrecondApply, ShardedTriangularEngine

            tp = self._tri_plan()
            eng = self._shared.get(("tri_engine", broadcast))
            if eng is None:
                eng = self._shared[("tri_engine", broadcast)] = (
                    ShardedTriangularEngine(tp, self.mesh, broadcast=broadcast))
            self._preconds[broadcast] = ShardedPrecondApply(
                eng.plan, self.loc_vals, self.mesh, engine=eng)
        return self._preconds[broadcast]

    def solve(self, b: np.ndarray) -> np.ndarray:
        """Apply the preconditioner: L y = b then U x = y, distributed.
        With an ordering, ``b`` permutes in and ``x`` un-permutes out."""
        b = np.asarray(b, np.float32)
        if self.ordering is not None:
            b = self.ordering.permute_vector(b)
        out = np.asarray(self.precond()(b))
        if self.ordering is not None:
            out = self.ordering.unpermute_vector(out)
        return out

    def to_host(self):
        """Materialize as a host :class:`repro.core.api.ILUFactorization`."""
        from .api import ILUFactorization

        return ILUFactorization(
            a=self.a, k=self.k, pattern=self.pattern, vals=self.values_csr(),
            symbolic_seconds=self.symbolic_seconds,
            numeric_seconds=self.numeric_seconds, ordering=self.ordering,
            precond_method=self.precond_method)


def _sharded_inputs(plan: NumericPlan, mesh: Mesh, keys=None):
    """Place the factorizer inputs on the mesh, each sharded on its device
    axis (``launch.sharding.band_shardings``) so no array is replicated.
    ``keys`` restricts which arrays are built and placed."""
    from repro.launch.sharding import band_shardings

    arrays = plan_device_arrays(plan, keys=keys)
    shardings = band_shardings(mesh, plan_shard_specs(AXIS))
    return {k: jax.device_put(v, shardings[k]) for k, v in arrays.items()}


def _build_topilu_engine(a, pattern, band_rows, mesh, broadcast):
    """Structure-keyed engine-store entry: plan, compiled engine, placed
    *schedule* arrays (no values — the state is rebuilt per call), the
    state sharding, and a dict the solve-side engines cache into."""
    d = mesh.devices.size
    plan = make_plan(a, pattern, band_rows=band_rows, n_devices=d)
    fac = make_superstep_factorizer(plan, axis_name=AXIS if d > 1 else None, broadcast=broadcast)
    static = tuple(k for k in _ARG_ORDER if k != "state")
    if d == 1:
        import jax.numpy as jnp

        fn = jax.jit(fac)
        state_sharding = None
        # commit the constant schedule tables to device once — numpy args
        # would re-transfer per cached-engine refactorization. The value
        # state is NOT placed here: it is rebuilt from a.data per call.
        arrays = plan_device_arrays(plan, keys=static)
        args = tuple(jnp.asarray(arrays[k]) for k in static)
    else:
        specs = plan_shard_specs(AXIS)
        fn = jax.jit(shard_map(
            fac,
            mesh=mesh,
            in_specs=tuple(specs[k] for k in _ARG_ORDER),
            out_specs=P(AXIS, None, None),
            check_vma=False,
        ))
        from repro.launch.sharding import band_shardings

        placed = _sharded_inputs(plan, mesh, keys=static)
        state_sharding = band_shardings(mesh, plan_shard_specs(AXIS))["state"]
        args = tuple(placed[k] for k in static)
    return dict(plan=plan, fn=fn, args=args, state_sharding=state_sharding, shared={})


def topilu_factor_sharded(
    a: CSRMatrix,
    pattern: ILUPattern,
    band_rows: int = 32,
    mesh: Optional[Mesh] = None,
    broadcast: str = "psum",
) -> ShardedILUFactorization:
    """Parallel numeric factorization; output stays sharded on the mesh.

    The plan, the compiled shard_map engine, and the placed schedule arrays
    are memoized on the matrix object (same lifetime rule as
    ``factor_plan_for``: the cache dies with the matrix), keyed by pattern
    content, band size, mesh devices, and broadcast — repeated
    factorizations of the same configuration re-execute the cached engine
    instead of replanning and recompiling. The *value* state is rebuilt
    from ``a.data`` on every call, so refactorizing with updated values
    never reuses stale numbers.
    """
    mesh = band_mesh(mesh)
    from .factor_plan import _pattern_fingerprint

    key = ("topilu", _pattern_fingerprint(pattern), band_rows,
           tuple(dev.id for dev in mesh.devices.flat), broadcast)
    try:
        store = a.__dict__.setdefault("_topilu_engines", {})
    except AttributeError:  # exotic container without __dict__: no caching
        store = {}
    entry = store.get(key)
    if entry is None:
        entry = store[key] = _build_topilu_engine(a, pattern, band_rows, mesh, broadcast)
    plan = entry["plan"]
    state = plan_state_array(plan, a)
    if entry["state_sharding"] is not None:
        state = jax.device_put(state, entry["state_sharding"])
    return ShardedILUFactorization(
        a=a, k=pattern.k, pattern=pattern, plan=plan, mesh=mesh,
        loc_vals=entry["fn"](state, *entry["args"]),
        _shared=entry["shared"])


def topilu_numeric(
    a: CSRMatrix,
    pattern: ILUPattern,
    band_rows: int = 32,
    mesh: Optional[Mesh] = None,
    broadcast: str = "psum",
) -> np.ndarray:
    """Parallel numeric factorization. Returns CSR-aligned host values.

    With ``mesh=None`` uses every available device on a 1-D mesh; pass an
    explicit 1-D mesh to control the device set. This is the host-gathering
    convenience wrapper; :func:`topilu_factor_sharded` keeps the output
    device-resident.
    """
    return topilu_factor_sharded(
        a, pattern, band_rows=band_rows, mesh=mesh, broadcast=broadcast
    ).values_csr()


def lower_topilu(
    a: CSRMatrix,
    pattern: ILUPattern,
    band_rows: int,
    mesh: Mesh,
    broadcast: str = "psum",
):
    """AOT-lower the parallel factorization (for dry-runs / HLO inspection)."""
    d = mesh.devices.size
    plan = make_plan(a, pattern, band_rows=band_rows, n_devices=d)
    arrays = plan_device_arrays(plan)
    specs = plan_shard_specs(AXIS)
    fac = make_superstep_factorizer(plan, axis_name=AXIS, broadcast=broadcast)
    smapped = shard_map(
        fac,
        mesh=mesh,
        in_specs=tuple(specs[k] for k in _ARG_ORDER),
        out_specs=P(AXIS, None, None),
        check_vma=False,
    )
    from repro.launch.sharding import band_shardings

    shardings = band_shardings(mesh, specs)
    args = [
        jax.ShapeDtypeStruct(arrays[k].shape, arrays[k].dtype, sharding=shardings[k])
        for k in _ARG_ORDER
    ]
    return jax.jit(smapped).lower(*args), plan
