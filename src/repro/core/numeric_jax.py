"""Device-side numeric factorization (Phase II) — wavefront + superstep engines.

All functions here are pure JAX and shape-static; they implement exactly the
oracle's arithmetic (divide; barriered multiply-then-subtract; ascending
pivots per row) so the result is **bit-compatible** with
:func:`repro.core.numeric_ref.numeric_ilu_ref`.

Two executors over the same plan-layer contracts (DESIGN.md §3):

* :func:`factor_wavefront_sweeps_jnp` / :func:`make_wavefront_factorizer` —
  the single-device fast path. One ``lax.scan`` over the *pivot-op*
  wavefronts of a :class:`repro.core.factor_plan.FactorPlan`: each round
  applies one pivot to every row whose turn has come (all independent by
  construction), through the precomputed flat destination-lane map — no
  ``searchsorted``, no per-band sequential sweep, and padded work bounded
  by ``n_rounds * max_ops * W`` (exact op count, robust to skewed
  patterns) instead of the old ``n_bands * n_pad * max_piv`` dense partial
  reductions.
* :func:`make_superstep_factorizer` — the banded TOP-ILU executor (paper
  §IV), re-emitted over the *band superstep schedule*: bands whose
  dependencies are satisfied factor concurrently (vmapped per device over
  its members of the superstep), each band *pulling* its inter-band pivot
  rows from the replicated finalized values. One collective per superstep
  (an ``all_gather`` of the bands each device finished — ``broadcast=
  "psum"`` is kept as an alias — or an explicit ``ppermute`` directed ring,
  the paper's Fig-4 pipeline) replaces one broadcast per band. Pivot order
  within a row
  is ascending (earlier-band columns precede in-band columns), so the pull
  formulation is bitwise identical to the oracle by construction.

The same superstep body runs single-device (``axis_name=None``) or under
``shard_map`` with each device computing the bands it owns round-robin
(static load balancing, §IV-D).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .bitmath import exact_div, hoisted_jit
from .planner import NumericPlan

# --------------------------------------------------------------------------
# row-wavefront executor (single device)
# --------------------------------------------------------------------------
def factor_wavefront_sweeps_jnp(op_row, op_lane, op_piv, op_dlane, op_dst,
                                dst_flat, a_vals_ext):
    """Round-major pivot-op wavefront factorization.

    ``a_vals_ext``: (n+1, W) A-values on the pattern + zero scratch row;
    schedule arrays as in :class:`repro.core.factor_plan.FactorPlan`.
    Each round applies at most one pivot per row (rows distinct within a
    round by construction), so the per-round read-modify-write on the
    value array is conflict-free. Returns the factored (n, W) values.
    """
    NR, MO = op_row.shape
    n = a_vals_ext.shape[0] - 1
    idx = jnp.arange(MO)

    def round_step(vals, inp):
        rows, lanes, pivs, dlanes, ids = inp
        valid = rows < n  # padding ops target the scratch row
        x = vals[rows]  # (MO, W)
        pv = vals[pivs]  # (MO, W) — pivot rows, final since earlier rounds
        pdiag = jnp.where(valid, pv[idx, dlanes], jnp.float32(1))
        xp = x[idx, lanes]
        l = exact_div(xp, pdiag)
        # multiply-then-subtract, product rounded to f32 before the add
        # (no FMA contraction) — the oracle's exact arithmetic
        contrib = lax.optimization_barrier(l[:, None] * pv)
        dd = dst_flat[ids]  # (MO, W); pad op -> all lanes dropped
        x = jax.vmap(lambda xr, dr, cr: xr.at[dr].add(-cr, mode="drop"))(x, dd, contrib)
        x = x.at[idx, lanes].set(jnp.where(valid, l, xp))
        return vals.at[rows].set(x), None

    with jax.named_scope("factor.rounds"):
        vals, _ = lax.scan(round_step, a_vals_ext, (op_row, op_lane, op_piv, op_dlane, op_dst))
    return vals[:n]


def make_wavefront_factorizer(plan):
    """Compiled ``(n+1, W) -> (n, W)`` factorizer over a FactorPlan.

    The schedule arrays live on device (cached on the plan) and ride as
    runtime arguments; the returned callable is jitted once and reused for
    every refactorization of the same structure.
    """
    dev = plan.device_arrays()

    def _raw(vals):
        return factor_wavefront_sweeps_jnp(
            dev["op_row"], dev["op_lane"], dev["op_piv"],
            dev["op_dlane"], dev["op_dst"], dev["dst_flat"],
            jnp.asarray(vals, jnp.float32),
        )

    return hoisted_jit(_raw, name="factorize")


# --------------------------------------------------------------------------
# band superstep executor (TOP-ILU, single- or multi-device, sharded values)
# --------------------------------------------------------------------------
def make_superstep_factorizer(
    plan: NumericPlan,
    axis_name: Optional[str] = None,
    broadcast: str = "psum",
):
    """Build the jit-able band-superstep numeric factorization body.

    Value storage is **sharded**: each device carries only its
    ``[local | halo | scratch]`` state (``s_loc + H + 1`` rows, not
    ``n_pad``) and the schedule/gather tables for the rows it owns. Every
    argument of the returned function is a *device-local block* with a
    leading device axis of 1 (the shape ``shard_map`` hands over when the
    host array is sharded along that axis — see
    :func:`plan_device_arrays` / ``plan_shard_specs``):

    state      (1, s_loc+H+1, W) f32 — band-local A values | halo | scratch
    sched      (n_sup, 1, MPD) i32 — this device's bands per superstep
    piv_addr   (1, s_loc, MP) i32 — device-local pivot-read addresses
    piv_dlane  (1, s_loc, MP) i32 — pivot row's diagonal lane
    piv_dst    (1, s_loc, MP, W) i32 — destination lanes ([0, W]; W = drop)
    n_piv      (1, s_loc) i32 — pivots per row (diag position)
    egress     (n_sup, 1, E) i32 — local addrs of rows to ship per superstep
    ingress    (n_sup, 1, D, E) i32 — halo addrs of received rows (pad=scratch)

    Returns this device's factored local values ``(1, s_loc, W)``.

    Per superstep: finish the owned bands of the wave (in-band pivots pulled
    from the band buffer being built, everything else from local/halo state
    through the precomputed ``piv_addr``), then exchange *only the finalized
    pivot rows some other device consumes* — one ``all_gather`` of the
    (E, W) egress payload (``broadcast="psum"`` kept as the historical alias
    for this XLA-collective fast path) or an explicit ``ppermute`` directed
    ring (the paper's Fig-4 pipeline) that forwards the payload D-1 hops and
    scatters each hop through the sender's ingress row. Both paths only
    *copy* finalized f32 rows (no arithmetic on the wire), so the exchange
    cannot perturb a single bit.
    """
    R = plan.band_rows
    B = plan.n_bands
    D = plan.n_devices
    # a multi-device plan without an axis would silently factor only device
    # 0's bands (me=0, no exchange) — fail fast instead
    assert axis_name is not None or D == 1, \
        f"plan built for {D} devices needs axis_name"
    W = plan.width
    MP = plan.max_piv
    S_loc = plan.s_loc
    H = plan.halo_size
    E = plan.egress_max
    scratch = S_loc + H
    n_sup = plan.n_supersteps
    exchange = axis_name is not None and D > 1 and H > 0
    if broadcast == "psum":  # historical alias: the XLA-collective fast path
        broadcast = "gather"
    assert broadcast in ("gather", "ring")

    def factorize(state, sched, piv_addr, piv_dlane, piv_dst, n_piv, egress, ingress):
        state = state[0]  # (S_loc+H+1, W) — this device's value state
        piv_addr, piv_dlane = piv_addr[0], piv_dlane[0]
        piv_dst, n_piv = piv_dst[0], n_piv[0]
        me = lax.axis_index(axis_name) if axis_name is not None else jnp.int32(0)

        def superstep(s, state):
            my_bands = lax.dynamic_slice(
                sched, (s, 0, 0), (1, 1, sched.shape[2]))[0, 0]  # (MPD,)

            def do_band(b):
                live = b < B
                g = jnp.where(live, b // jnp.int32(D), 0)  # owner-local band
                base = (g * R).astype(jnp.int32)
                rows = base + jnp.arange(R, dtype=jnp.int32)
                buf = state[rows]  # (R, W) — the band's A values

                def row_step(r, buf):
                    x = buf[r]
                    jl = base + r  # device-local row index

                    def piv_step(p, x):
                        addr = piv_addr[jl, p]
                        valid = p < n_piv[jl]
                        li = addr - base
                        in_band = (li >= 0) & (li < R)
                        # pull: in-band pivots from the buffer being built,
                        # finalized rows from local storage or the halo
                        pvals = jnp.where(in_band, buf[jnp.clip(li, 0, R - 1)], state[addr])
                        piv = jnp.where(valid, pvals[piv_dlane[jl, p]], jnp.float32(1))
                        xp = x[jnp.minimum(p, W - 1)]
                        l = exact_div(xp, piv)
                        contrib = lax.optimization_barrier(l * pvals)
                        x = x.at[piv_dst[jl, p]].add(-contrib, mode="drop")
                        return x.at[jnp.minimum(p, W - 1)].set(jnp.where(valid, l, xp))

                    x = lax.fori_loop(0, MP, piv_step, x)
                    return buf.at[r].set(x)

                buf = lax.fori_loop(0, R, row_step, buf)
                # padded bands write into the scratch row (garbage allowed
                # there: scratch reads feed only dropped scatter lanes)
                return buf, jnp.where(live, rows, jnp.int32(scratch))

            # bands of a superstep are independent; a fori (not vmap — the
            # optimization_barrier has no batching rule) fills this device's
            # members, while other devices process theirs concurrently
            def band_loop(gi, carry):
                bufs, wrows = carry
                buf, rw = do_band(my_bands[gi])
                return bufs.at[gi].set(buf), wrows.at[gi].set(rw)

            mpd = my_bands.shape[0]
            bufs, wrows = lax.fori_loop(
                0, mpd, band_loop,
                (jnp.zeros((mpd, R, W), jnp.float32),
                 jnp.full((mpd, R), scratch, jnp.int32)),
            )
            state = state.at[wrows.reshape(-1)].set(bufs.reshape(-1, W))

            if exchange:
                eg = lax.dynamic_slice(egress, (s, 0, 0), (1, 1, E))[0, 0]  # (E,)
                payload = state[eg]  # (E, W) — finalized rows others consume
                ing = lax.dynamic_slice(
                    ingress, (s, 0, 0, 0), (1, 1, D, E))[0, 0]  # (D, E)
                if broadcast == "gather":
                    all_p = lax.all_gather(payload, axis_name)  # (D, E, W)
                    state = state.at[ing.reshape(-1)].set(all_p.reshape(-1, W))
                else:  # explicit directed ring — the paper's Fig-4 pipeline
                    perm = [(d, (d + 1) % D) for d in range(D)]
                    cur = payload
                    for hop in range(1, D):
                        cur = lax.ppermute(cur, axis_name, perm)
                        src = jnp.mod(me - hop, D)  # whose payload we now hold
                        dst = jnp.take(ing, src, axis=0)  # (E,)
                        state = state.at[dst].set(cur)
            return state

        state = lax.fori_loop(0, n_sup, superstep, state)
        return state[None, :S_loc]

    return factorize


def _device_major(plan: NumericPlan, x):
    """(n_pad, ...) row table -> (D, s_loc, ...) device blocks."""
    return plan.rows_device_major(x).reshape((plan.n_devices, plan.s_loc) + x.shape[1:])


def plan_state_array(plan: NumericPlan, a=None):
    """The (D, state_rows, W) initial value state: band-local A values
    (device-major), zero halo, zero scratch. ``a=None`` uses the values
    captured at plan build; passing a matrix with the same structure
    re-scatters its current data (the refactorization path)."""
    vals = plan.a_vals if a is None else plan.scatter_values(a)
    state = np.zeros((plan.n_devices, plan.state_rows, plan.width), np.float32)
    state[:, : plan.s_loc] = _device_major(plan, vals)
    return state


def plan_device_arrays(plan: NumericPlan, keys=None):
    """Host-side inputs of the sharded superstep factorizer.

    Every per-row table is permuted device-major and reshaped to a leading
    device axis, so sharding that axis over the mesh (``plan_shard_specs``)
    gives each device exactly the rows it owns: the value state and the
    per-row gather tables (``piv_*``) are ``O(n_pad/D)`` per device, never
    replicated. (The small per-superstep schedules scale differently —
    ``sched``/``egress`` are O(n_sup·MPD)/O(n_sup·E) per device and
    ``ingress`` O(n_sup·D·E), index entries only.) ``keys`` restricts which
    arrays are built — the value ``state`` is the expensive one and most
    callers rebuild it per factorization from ``plan_state_array``.
    """
    def dm(x):
        return _device_major(plan, x)

    builders = dict(
        state=lambda: plan_state_array(plan),
        sched=lambda: plan.superstep_bands,
        piv_addr=lambda: dm(plan.piv_addr),
        piv_dlane=lambda: dm(plan.piv_dlane),
        piv_dst=lambda: dm(plan.piv_dst),
        n_piv=lambda: dm(plan.diag_pos.astype(np.int32)),
        egress=lambda: plan.egress_idx,
        ingress=lambda: plan.ingress_idx,
    )
    keys = builders.keys() if keys is None else keys
    return {k: builders[k]() for k in keys}


def plan_shard_specs(axis_name: str):
    """``shard_map``/``NamedSharding`` PartitionSpecs for the factorizer
    arguments (device axis of each array in :func:`plan_device_arrays`)."""
    from jax.sharding import PartitionSpec as P

    return dict(
        state=P(axis_name, None, None),
        sched=P(None, axis_name, None),
        piv_addr=P(axis_name, None, None),
        piv_dlane=P(axis_name, None, None),
        piv_dst=P(axis_name, None, None, None),
        n_piv=P(axis_name, None),
        egress=P(None, axis_name, None),
        ingress=P(None, axis_name, None, None),
    )
