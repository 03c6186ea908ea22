"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh) cell, in seconds (DESIGN.md §8):

    compute    = HLO_FLOPs   / (chips x peak bf16 FLOP/s)
    memory     = HLO_bytes   / (chips x peak HBM B/s)
    collective = coll_bytes  / (chips x ICI B/s per link)

with the peaks of the target chip from :data:`HW` (v5e for the dry run).

FLOPs/bytes come from ``compiled.cost_analysis()``. Collective bytes are
NOT in cost_analysis: we parse the optimized HLO text and sum the output
shape bytes of every all-gather / all-reduce / reduce-scatter / all-to-all
/ collective-permute op (output size = bytes that actually cross links for
AG; for all-reduce we count 2x the operand — reduce-scatter + all-gather
decomposition of a ring).

Also derives MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE) and the
useful-compute ratio MODEL_FLOPS / HLO_FLOPs.
"""

from __future__ import annotations

import dataclasses
import json
import re

from repro.configs.base import ArchConfig, ShapeConfig

__all__ = ["HW", "V5E", "peaks", "collective_bytes_from_hlo", "roofline_terms",
           "model_flops", "RooflineReport"]

#: ``jax.Device.device_kind`` of a TPU v5e chip.
V5E = "TPU v5 lite"

#: Published per-chip peaks, keyed by ``device_kind``. Source: Google
#: Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s
#: bf16, 819 GB/s of HBM bandwidth, 1,600 Gbit/s of chip-to-chip
#: interconnect (counted here per link, over four links).
HW: dict[str, dict[str, float]] = {
    V5E: {
        "flops_bf16": 197e12,     # FLOP/s
        "hbm_bw": 819e9,          # B/s
        "ici_bw": 50e9,           # B/s per link
    },
}


def peaks(device_kind: str) -> dict[str, float]:
    """Published peaks of ``device_kind``; a kind not in :data:`HW` is an
    error, never a default."""
    try:
        return HW[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to roofline.HW with their source (known: {sorted(HW)})"
        ) from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
    re.MULTILINE,
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of 'bf16[128,4096]{...}' or tuple '(f32[2], bf16[4,4])'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> dict[str, int]:
    """Sum output bytes per collective kind (deduping -start/-done pairs:
    only -start (or the plain op) is counted)."""
    out: dict[str, int] = {}
    seen_done_skip = 0
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        # skip the -done half of async pairs (shape repeats)
        line = hlo_text[m.start():hlo_text.find("\n", m.start())]
        if f"{kind}-done(" in line:
            seen_done_skip += 1
            continue
        nbytes = _shape_bytes(shape_str)
        if kind == "all-reduce":
            nbytes *= 2  # ring AR = RS + AG worth of wire bytes
        out[kind] = out.get(kind, 0) + nbytes
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """6*N*D (dense) or 6*N_active*D (MoE); D = tokens processed.

    decode shapes process global_batch tokens per step; train includes the
    3x backward factor already via the 6 (2 fwd + 4 bwd); for pure-forward
    shapes (prefill/decode) use 2*N*D."""
    n = cfg.active_param_count() if cfg.is_moe else cfg.param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch  # one token per sequence
    return 2.0 * n * tokens


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    collectives: dict
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    useful_ratio: float
    bytes_per_device: float | None = None
    peak_memory_per_device: float | None = None

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


def roofline_terms(cfg: ArchConfig, shape: ShapeConfig, mesh_name: str,
                   chips: int, cost: dict, hlo_text: str,
                   memory_stats: dict | None = None) -> RooflineReport:
    flops = float(cost.get("flops", 0.0))
    # cost_analysis 'bytes accessed' aggregates operand+output HBM traffic
    hbytes = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes_from_hlo(hlo_text)
    hw = peaks(V5E)
    compute_s = flops / (chips * hw["flops_bf16"])
    memory_s = hbytes / (chips * hw["hbm_bw"])
    collective_s = coll["total"] / (chips * hw["ici_bw"])
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    return RooflineReport(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=hbytes,
        collective_bytes=float(coll["total"]), collectives=coll,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, model_flops=mf,
        useful_ratio=(mf / flops if flops else 0.0),
        peak_memory_per_device=(memory_stats or {}).get("bytes_per_device"),
    )
