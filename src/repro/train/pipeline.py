"""Pipeline parallelism (GPipe schedule) over a ``pipe`` mesh axis.

The paper's core scheduling idea — stream completed units of work through a
device ring while every stage keeps computing (Fig 4) — applied to layers
instead of matrix bands. SPMD formulation:

* the layer stack (L leading axis) reshapes to (P, L/P, ...) and shards its
  stage axis over ``pipe``;
* microbatches enter stage 0; activations hop stage->stage with
  `lax.ppermute` (the band broadcast's sibling); a `lax.scan` over
  N + P - 1 ticks realizes the schedule, bubble fraction (P-1)/(N+P-1);
* every device executes its stage every tick (SPMD-uniform; bubble ticks
  compute on garbage and are masked out), exactly like TOP-ILU's redundant
  `finish_band` on non-owners;
* backward differentiates through the scan/ppermute (transpose of a
  permutation is the reverse permutation), giving 1F1B-equivalent traffic.

Composable with the data/model axes: pass a mesh like
``jax.make_mesh((pipe, data, model), ("pipe", "data", "model"))`` and shard
batches/params on the other axes as usual.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..models.transformer import layer_forward


def _stage_fn(cfg, stage_layers, x, positions):
    """Apply this device's L/P layers (scan over the local slice)."""

    def body(carry, lp):
        return layer_forward(cfg, lp, carry, positions), None

    out, _ = lax.scan(body, x, stage_layers)
    return out


def make_pipelined_forward(cfg, mesh, n_microbatches: int, axis: str = "pipe"):
    """Returns ``fn(stacked_layers, x, positions) -> y`` running the layer
    stack as a P-stage GPipe pipeline over ``axis``.

    ``stacked_layers`` leaves have leading dim L (divisible by P);
    ``x`` is (B, S, d) with B divisible by n_microbatches.
    """
    Pn = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    def pipelined(layers, x, positions):
        B, S, d = x.shape
        N = n_microbatches
        assert B % N == 0
        mb = B // N
        xs = x.reshape(N, mb, S, d)

        def inner(stage_layers, xs_in):
            # stage_layers leaves: (1, L/P, ...) local slice -> drop stage dim
            stage_layers_l = jax.tree.map(lambda t: t[0], stage_layers)
            idx = lax.axis_index(axis)
            T = N + Pn - 1

            def tick(buf, t):
                m = jnp.clip(t, 0, N - 1)
                inject = lax.dynamic_index_in_dim(xs_in, m, keepdims=False)
                inp = jnp.where(idx == 0, inject, buf)
                out = _stage_fn(cfg, stage_layers_l, inp, positions)
                perm = [(i, i + 1) for i in range(Pn - 1)]
                nxt = lax.ppermute(out, axis, perm)
                y_t = jnp.where(idx == Pn - 1, out, jnp.zeros_like(out))
                return nxt, y_t

            buf0 = jnp.zeros((mb, S, d), x.dtype)
            _, ys = lax.scan(tick, buf0, jnp.arange(T))
            # microbatch m exits the last stage at tick m + P - 1; psum
            # replicates the result (other stages contribute zeros)
            return lax.psum(ys[Pn - 1 :], axis)

        # reshape stacked layers (L, ...) -> (P, L/P, ...) sharded on stage
        def to_stages(t):
            L = t.shape[0]
            assert L % Pn == 0, (L, Pn)
            return t.reshape(Pn, L // Pn, *t.shape[1:])

        staged = jax.tree.map(to_stages, layers)
        in_specs = (
            jax.tree.map(lambda _: P(axis), staged),
            P(),  # microbatches replicated in; stage 0 consumes them
        )
        smapped = shard_map(
            inner, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False,
        )
        ys = smapped(staged, xs)
        return ys.reshape(B, S, d)

    return pipelined


def pipeline_bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_stages - 1 + n_microbatches)
