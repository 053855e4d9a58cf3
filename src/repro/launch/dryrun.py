import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
)
# ^ MUST precede any jax import: jax locks the device count at first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun

For each cell this:
  1. builds the production mesh (16x16, or 2x16x16 with --multi-pod),
  2. abstract-inits params/optimizer/caches (jax.eval_shape — no allocation),
  3. jits train_step / prefill_step / serve_step with the sharding rules,
  4. ``.lower().compile()`` — success is the deliverable,
  5. prints memory_analysis + cost_analysis and writes the roofline JSON.
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax


def _lower_and_compile(cfg, shape_name, mesh, opts, microbatches):
    from repro.configs.base import SHAPES
    from repro.launch.sharding import ShardingRules
    from repro.models import model as M
    from repro.models.common import logical_mesh
    from repro.optim import adamw
    from repro.train.step import make_prefill_step, make_serve_step, make_train_step

    seq, gbatch, kind = SHAPES[shape_name]
    rules = ShardingRules(cfg, mesh)
    params_shapes = jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0))
    p_shard = rules.params_shardings(params_shapes)
    batch_specs = cfg.input_specs(shape_name)
    b_shard = rules.batch_shardings(batch_specs)

    with logical_mesh(mesh):
        if kind == "train":
            opt_shapes = jax.eval_shape(adamw.init, params_shapes)
            o_shard = rules.opt_shardings(opt_shapes, zero1=opts.get("zero1", False))
            step = make_train_step(cfg, adamw.AdamWConfig(), microbatches=microbatches)
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_shapes, opt_shapes, batch_specs)
        elif kind == "prefill":
            step = make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(params_shapes, batch_specs)
        else:  # decode
            cache_len = cfg.cache_len(shape_name)
            cache_shapes = jax.eval_shape(lambda: M.init_cache(cfg, gbatch, cache_len))
            c_shard = rules.cache_shardings(cache_shapes, gbatch)
            step = make_serve_step(cfg)
            in_sh = [p_shard, c_shard, b_shard["tokens"]]
            args = [params_shapes, cache_shapes, batch_specs["tokens"]]
            if cfg.family == "audio":
                in_sh.append(b_shard["frames"])
                args.append(batch_specs["frames"])
            jitted = jax.jit(
                step,
                in_shardings=tuple(in_sh),
                out_shardings=(None, None, c_shard),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return compiled, kind


def _build_cell(arch, shape_name, multi_pod, opts):
    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    if opts.get("remat"):
        cfg = dataclasses.replace(cfg, remat=opts["remat"])
    if opts.get("q_chunk"):
        cfg = dataclasses.replace(cfg, q_chunk=opts["q_chunk"], kv_chunk=opts["q_chunk"])
    if opts.get("window") and cfg.sliding_window:
        cfg = dataclasses.replace(cfg, sliding_window=opts["window"])
    if shape_name not in cfg.supported_shapes:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "full-attention arch: 512k dense KV decode excluded "
                          "(DESIGN.md §4)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)

    # ---- pass A: production form (scan over layers) -> compile + memory ---
    t0 = time.time()
    compiled_a, kind = _lower_and_compile(cfg, shape_name, mesh, opts, opts.get("microbatches", 1))
    t_a = time.time() - t0
    mem = compiled_a.memory_analysis()
    print(mem)  # proves it fits
    from repro.roofline.analysis import cost_analysis_dict

    ca_a = cost_analysis_dict(compiled_a)
    print({k: ca_a[k] for k in ("flops", "bytes accessed") if k in ca_a})

    # ---- pass B: cost form — unrolled 1-layer and 2-layer modules, prefix
    # attention, no grad-accumulation loop; per-layer costs extrapolated to
    # the full stack. XLA's cost_analysis counts while-loop bodies ONCE
    # (verified on this jax version), so the production scan form cannot be
    # used for the roofline and full unrolls are too slow to compile for
    # every cell; layer-homogeneous extrapolation is exact here.
    from repro.roofline.analysis import (
        V5E, analyze_costs, extract_costs, extrapolate_costs, model_flops,
        recurrent_scan_correction,
    )

    def cost_cfg(nl):
        kw = dict(scan_layers=False, attn_unroll=True, n_layers=nl)
        if cfg.block_types:
            kw["block_types"] = (cfg.block_types * nl)[:nl]
        if cfg.encoder_layers:
            kw["encoder_layers"] = nl
        return dataclasses.replace(cfg, **kw)

    t1 = time.time()
    if opts.get("skip_cost_pass"):
        costs = extract_costs(compiled_a)
    else:
        cb1, _ = _lower_and_compile(cost_cfg(1), shape_name, mesh, opts, 1)
        cb2, _ = _lower_and_compile(cost_cfg(2), shape_name, mesh, opts, 1)
        costs = extrapolate_costs(extract_costs(cb1), extract_costs(cb2), cfg.n_layers)
    t_b = time.time() - t1

    corr = recurrent_scan_correction(cfg, shape_name, int(mesh.devices.size))
    rep = analyze_costs(
        costs, arch=arch, shape=shape_name, mesh_name=mesh_name,
        chips=int(mesh.devices.size),
        model_flops_global=model_flops(cfg, shape_name),
        corrections=corr,
        device_kind=V5E,
        memory_stats={
            "argument_bytes": float(mem.argument_size_in_bytes),
            "output_bytes": float(mem.output_size_in_bytes),
            "temp_bytes": float(mem.temp_size_in_bytes),
            "alias_bytes": float(mem.alias_size_in_bytes),
        },
    )
    out = rep.to_json()
    out.update(
        status="ok", kind=kind, compile_a_s=round(t_a, 1), compile_b_s=round(t_b, 1),
        multi_pod=multi_pod, opts=opts, scan_correction=corr,
        memory_stats_production={
            "argument_bytes": float(mem.argument_size_in_bytes),
            "output_bytes": float(mem.output_size_in_bytes),
            "temp_bytes": float(mem.temp_size_in_bytes),
        },
        fits_hbm_16g=bool(
            (mem.temp_size_in_bytes + mem.argument_size_in_bytes) < 16e9
        ),
    )
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="all (arch x shape) cells")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--remat", default=None, choices=[None, "none", "dots", "full"])
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--q-chunk", type=int, default=None)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--skip-cost-pass", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    from repro.configs import ARCHS
    from repro.configs.base import SHAPES

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    os.makedirs(args.out, exist_ok=True)
    opts = {"remat": args.remat, "zero1": args.zero1,
            "microbatches": args.microbatches, "q_chunk": args.q_chunk,
            "window": args.window, "skip_cost_pass": args.skip_cost_pass}
    failures = 0
    for arch, shape in cells:
        tag = f"{arch}__{shape}__{'pod2' if args.multi_pod else 'pod1'}"
        if args.tag:
            tag += f"__{args.tag}"
        path = os.path.join(args.out, tag + ".json")
        print(f"=== {tag} ===", flush=True)
        try:
            res = _build_cell(arch, shape, args.multi_pod, opts)
        except Exception as e:
            traceback.print_exc()
            res = {"arch": arch, "shape": shape, "status": "error",
                   "error": f"{type(e).__name__}: {e}"}
            failures += 1
        with open(path, "w") as f:
            json.dump(res, f, indent=2)
        print(json.dumps({k: res.get(k) for k in
                          ("status", "bottleneck", "compute_s", "memory_s",
                           "collective_s", "useful_ratio", "fits_hbm_16g",
                           "compile_a_s", "compile_b_s", "reason", "error")}),
              flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
