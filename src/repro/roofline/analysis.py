"""Roofline term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), per the assignment:

    compute    = HLO_FLOPs_per_device            / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device            / HBM_bw_per_chip
    collective = collective_bytes_per_device     / link_bw_per_chip

`compiled.cost_analysis()` reports **per-device** FLOPs/bytes for SPMD
modules (verified empirically on this jax version), so no chip division is
needed. Collective bytes are parsed from the post-SPMD optimized HLO: for
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute we take output-shape bytes and the replica-group size g
and apply the standard ring-algorithm wire models:

    all-gather        (g-1)/g * out_bytes
    all-reduce        2*(g-1)/g * out_bytes
    reduce-scatter    (g-1) * out_bytes        (out is the scattered shard)
    all-to-all        (g-1)/g * out_bytes
    collective-permute out_bytes

Hardware model: the published peaks of the device, from :data:`PEAKS`,
keyed by ``device_kind``. CPU-side callers (dry runs, modelled tables)
name the chip they model, ``V5E``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``. TPU v5e:
#: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s, 1,600 Gbit/s ICI per chip (modelled here as 50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
V5E = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table entry of ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\()?\s*((?:[a-z0-9]+\[[0-9,]*\][^ ]*,?\s*)+)\s*(?:\))?\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_SRC_TGT_RE = re.compile(r"source_target_pairs=\{")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_per_device(hlo_text: str) -> Dict[str, float]:
    """Sum wire bytes per device by collective kind."""
    out: Dict[str, float] = {
        "all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
        "all-to-all": 0.0, "collective-permute": 0.0,
    }
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shapes, kind = m.group(1), m.group(2)
        if "-done(" in line:
            continue  # async pair: count only the -start
        nbytes = _shape_bytes(shapes)
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            first = gm.group(1).split("},{")[0].strip("{}")
            g = len([t for t in first.split(",") if t.strip() != ""])
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            if gi:
                g = int(gi.group(2))
        g = max(g, 1)
        if kind == "all-reduce":
            out[kind] += 2 * (g - 1) / g * nbytes
        elif kind == "all-gather":
            out[kind] += (g - 1) / g * nbytes
        elif kind == "reduce-scatter":
            out[kind] += (g - 1) * nbytes
        elif kind == "all-to-all":
            out[kind] += (g - 1) / g * nbytes
        else:  # collective-permute
            out[kind] += nbytes
    return out


def collective_op_counts(hlo_text: str) -> Dict[str, int]:
    """Count collective *ops* per device by kind (async pairs count once).

    The epoch-fused sweep asserts its collective count against the host
    epoch model with this — XLA cannot merge the exchanges (each epoch
    depends on the previous one), so the compiled count equals the
    schedule's.
    """
    out: Dict[str, int] = {
        "all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
        "all-to-all": 0, "collective-permute": 0,
    }
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done(" in line:
            continue  # async pair: count only the -start
        out[m.group(2)] += 1
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float
    collective_breakdown: Dict[str, float]
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_ratio: float
    memory_stats: Dict[str, float]

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` normalized across jax versions (older
    versions return ``[dict]``, jax>=0.4.3x a bare dict or list)."""
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca


def extract_costs(compiled) -> Dict[str, float]:
    """Per-device flops / bytes / per-kind collective bytes of one module."""
    ca = cost_analysis_dict(compiled)
    coll = collective_bytes_per_device(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        **{f"coll/{k}": v for k, v in coll.items()},
    }


def extrapolate_costs(
    c1: Dict[str, float], c2: Dict[str, float], n_layers: int
) -> Dict[str, float]:
    """Layer-homogeneous extrapolation: cost(L) = c1 + (L-1)*(c2-c1).

    c1/c2 are 1-layer/2-layer unrolled modules. Exact for stacks whose
    layers are identical (all ten assigned archs as configured)."""
    out = {}
    for k in c1:
        per_layer = c2[k] - c1[k]
        out[k] = c1[k] + (n_layers - 1) * max(per_layer, 0.0)
    return out


def analyze_costs(costs: Dict[str, float], *, arch: str, shape: str, mesh_name: str,
                  chips: int, model_flops_global: float, memory_stats: Dict[str, float],
                  device_kind: str, corrections: Optional[Dict[str, float]] = None,
                  ) -> RooflineReport:
    flops_dev = costs["flops"]
    bytes_dev = costs["bytes"]
    if corrections:
        flops_dev += corrections.get("flops", 0.0)
        bytes_dev += corrections.get("bytes", 0.0)
    coll = {k.split("/", 1)[1]: v for k, v in costs.items() if k.startswith("coll/")}
    coll_total = sum(coll.values())
    peak = peaks(device_kind)
    compute_s = flops_dev / peak["flops"]
    memory_s = bytes_dev / peak["hbm_bw"]
    collective_s = coll_total / peak["link_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    useful = model_flops_global / (flops_dev * chips) if flops_dev else 0.0
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_device=flops_dev, bytes_per_device=bytes_dev,
        collective_bytes=coll_total, collective_breakdown=coll,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=bottleneck, model_flops=model_flops_global,
        useful_ratio=useful, memory_stats=memory_stats,
    )


def recurrent_scan_correction(cfg, shape_name: str, chips: int) -> Dict[str, float]:
    """Analytic per-device FLOPs/bytes for time-step `lax.scan` recurrences
    (mamba / mLSTM / sLSTM), which XLA cost_analysis counts exactly once.

    Only the train/prefill shapes need this (decode is a single step, fully
    counted). Costs are per full sequence, batch-sharded over the dp axes.
    """
    from repro.configs.base import SHAPES

    seq, gbatch, kind = SHAPES[shape_name]
    if kind == "decode":
        return {"flops": 0.0, "bytes": 0.0}
    # tokens per device (batch shards over dp; model axis replicates tokens)
    dp = max(chips // 16, 1)  # model axis is 16 on the production meshes
    tokens = seq * gbatch / dp
    mult = 3.0 if kind == "train" else 1.0  # fwd + ~2x bwd
    flops = 0.0
    bytes_ = 0.0
    if cfg.hybrid_parallel_ssm and cfg.ssm_state:
        di = (cfg.ssm_inner or cfg.d_model) / 16  # di sharded over model
        N = cfg.ssm_state
        per_tok = 9.0 * di * N
        flops += cfg.n_layers * per_tok * tokens
        bytes_ += cfg.n_layers * 8.0 * di * N * tokens  # state read+write f32
    if cfg.family == "ssm" and cfg.block_types:
        H = cfg.n_heads
        hd_m = 2 * cfg.d_model / H
        hd_s = cfg.d_model / H
        n_m = sum(1 for t in cfg.block_types if t == "m")
        n_s = len(cfg.block_types) - n_m
        flops += n_m * 5.0 * H * hd_m * hd_m * tokens
        bytes_ += n_m * 8.0 * H * hd_m * hd_m * tokens
        flops += n_s * (8.0 * H * hd_s * 4 * hd_s + 20.0 * cfg.d_model) * tokens
        bytes_ += n_s * 16.0 * cfg.d_model * tokens
    return {"flops": flops * mult, "bytes": bytes_ * mult}


def model_flops(cfg, shape_name: str) -> float:
    """MODEL_FLOPS: 6*N*D for training (N=active params), 2*N*D for decode
    forward-only, per the assignment's definition."""
    from repro.configs.base import SHAPES

    seq, gbatch, kind = SHAPES[shape_name]
    counts = cfg.param_count()
    n_active = counts["active"]
    if kind == "train":
        return 6.0 * n_active * seq * gbatch
    if kind == "prefill":
        return 2.0 * n_active * seq * gbatch
    return 2.0 * n_active * 1 * gbatch  # decode: one token per sequence
