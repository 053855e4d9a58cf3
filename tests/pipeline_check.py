"""Subprocess body: 4-stage GPipe pipeline == sequential layer stack."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_mesh
    from repro.configs import get_config
    from repro.models.transformer import init_stacked_layers, stack_forward
    from repro.train.pipeline import make_pipelined_forward, pipeline_bubble_fraction

    cfg = get_config("smollm-135m").reduced()
    cfg = dataclasses.replace(cfg, n_layers=8, q_chunk=32, kv_chunk=32, remat="none")
    devs = jax.devices()
    assert len(devs) == 4
    mesh = make_mesh(np.asarray(devs), ("pipe",))

    key = jax.random.PRNGKey(0)
    layers = init_stacked_layers(key, cfg)
    B, S, d = 8, 32, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d), cfg.act_dtype) * 0.1
    positions = jnp.arange(S)

    want = stack_forward(cfg, layers, x, positions)
    pipe = make_pipelined_forward(cfg, mesh, n_microbatches=4)
    got = jax.jit(lambda l, xx: pipe(l, xx, positions))(layers, x)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))
    print("forward max err:", err)
    assert err < 1e-4, err

    # backward: grads through the pipeline must match the sequential stack
    def loss_pipe(l, xx):
        return jnp.sum(pipe(l, xx, positions) ** 2)

    def loss_seq(l, xx):
        return jnp.sum(stack_forward(cfg, l, xx, positions) ** 2)

    g_pipe = jax.jit(jax.grad(loss_pipe))(layers, x)
    g_seq = jax.jit(jax.grad(loss_seq))(layers, x)
    errs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
        g_pipe, g_seq,
    )
    worst = max(jax.tree.leaves(errs))
    print("grad max err:", worst)
    assert worst < 1e-2, worst
    print(f"bubble fraction @(P=4, N=4): {pipeline_bubble_fraction(4, 4):.2f}")
    print("PIPELINE OK")


if __name__ == "__main__":
    main()
