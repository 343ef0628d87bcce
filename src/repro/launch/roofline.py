"""Roofline-term extraction from a compiled dry-run artifact.

compute term    = HLO_FLOPs / (chips * peak)    [cost_analysis is
memory term     = HLO_bytes / (chips * HBM_bw)   per-device, so /chip
collective term = collective_bytes / (chips * link_bw)   cancels out]

collective_bytes is NOT in cost_analysis — we parse the post-SPMD HLO
and sum result-buffer sizes of every collective op (shapes in the
partitioned module are already per-device).
"""
from __future__ import annotations

import re
from dataclasses import asdict, dataclass
from typing import Optional

# TPU v5e
PEAK_FLOPS = 197e12      # bf16 FLOP/s per chip
HBM_BW = 819e9           # B/s per chip
ICI_BW = 50e9            # B/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|"
                       r"u32|s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+(all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)(-start|-done)?\(")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by each collective type (result sizes)."""
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if m.group(3) == "-done":
            continue  # counted at -start
        result, op = m.group(1), m.group(2)
        out[op] += _shape_bytes(result)
        counts[op] += 1
    out["_counts"] = counts
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops_per_chip: float
    hlo_bytes_per_chip: float
    coll_bytes_per_chip: float
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float           # 6ND (train) / 2ND (serve) over the batch
    useful_ratio: float          # model_flops / (hlo_flops_per_chip * chips)
    bottleneck: str
    arg_bytes_per_chip: float = 0.0
    temp_bytes_per_chip: float = 0.0
    coll_counts: Optional[dict] = None

    def to_dict(self):
        return asdict(self)


def analyze(compiled, *, arch: str, shape_name: str, mesh_name: str,
            chips: int, model_flops: float) -> Roofline:
    """Roofline terms from the compiled module.

    Primary source is the structured HLO walk (launch.hlo_analysis),
    which scales while-loop bodies by trip count — ``cost_analysis()``
    counts scan bodies once and under-reports by ~num_layers.  We take
    the max of both flops numbers defensively.
    """
    from repro.launch.hlo_analysis import analyze_hlo
    hlo = compiled.as_text()
    walked = analyze_hlo(hlo)
    ca = compiled.cost_analysis() or {}
    flops = max(float(ca.get("flops", 0.0)), walked.flops)   # per-device
    nbytes = max(float(ca.get("bytes accessed", 0.0)), walked.bytes)
    counts = {k: int(v) for k, v in walked.coll_counts.items()}
    coll = float(walked.coll_bytes)

    compute_s = flops / PEAK_FLOPS
    memory_s = nbytes / HBM_BW
    collective_s = coll / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)

    ma = compiled.memory_analysis()
    arg_b = float(getattr(ma, "argument_size_in_bytes", 0) or 0)
    tmp_b = float(getattr(ma, "temp_size_in_bytes", 0) or 0)

    total_hlo = flops * chips
    return Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops_per_chip=flops, hlo_bytes_per_chip=nbytes,
        coll_bytes_per_chip=coll, compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, model_flops=model_flops,
        useful_ratio=(model_flops / total_hlo) if total_hlo else 0.0,
        bottleneck=bottleneck, arg_bytes_per_chip=arg_b,
        temp_bytes_per_chip=tmp_b, coll_counts=counts)


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS per step: 6·N_active·tokens (train), 2·N_active·tokens
    (prefill), 2·N_active·batch (decode: one token per sequence)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch
