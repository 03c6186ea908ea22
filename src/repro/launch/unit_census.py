"""Scan-aware collective/FLOP census by unit extrapolation.

``compiled.cost_analysis()`` and naive HLO parsing count while-loop bodies
once (benchmarks/README.md §Roofline methodology). This tool compiles the SAME
cell at ``n_layers = 0 units`` and ``n_layers = 1 unit`` and extrapolates:

    total(L) = cost(0) + L * (cost(1) - cost(0))

which is exact for scanned stacks (every unit is identical HLO) and keeps
everything derived from compiled artifacts. Used by the §Perf hillclimbs
to measure collective-byte deltas of sharding changes.

Run as:  python -m repro.launch.unit_census --arch X --shape Y [--mesh ...]
(own process: forces 512 host devices).
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402

from repro.configs.base import SHAPES, get_arch  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch import steps as steps_mod  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402


def _cell_costs(cfg, shape, mesh):
    if shape.kind == "train":
        step, structs, in_sh, _ = steps_mod.build_train_step(cfg, shape, mesh)
        args = structs
    elif shape.kind == "prefill":
        step, structs, in_sh, _ = steps_mod.build_prefill_step(cfg, shape, mesh)
        args = structs
    else:
        step, structs, in_sh, _ = steps_mod.build_decode_step(cfg, shape, mesh)
        p_struct, cache_struct, ispecs = structs
        p_sh, c_sh, i_sh = in_sh
        args = [p_struct, cache_struct, ispecs["token"], ispecs["pos"]]
        in_sh = tuple([p_sh, c_sh, i_sh["token"], i_sh["pos"]])
        if "enc_out" in ispecs:
            args.append(ispecs["enc_out"])
            in_sh = in_sh + (i_sh["enc_out"],)
        with mesh:
            compiled = jax.jit(step, in_shardings=in_sh).lower(*args).compile()
        return _extract(compiled)
    with mesh:
        compiled = jax.jit(step, in_shardings=in_sh).lower(*args).compile()
    return _extract(compiled)


def _extract(compiled):
    cost = dict(compiled.cost_analysis() or {})
    hlo = compiled.as_text()
    coll = rl.collective_bytes_from_hlo(hlo)
    mem = compiled.memory_analysis()
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll,
        "temp_gb": (mem.temp_size_in_bytes / 2**30) if mem else None,
    }


def unit_census(arch: str, shape_name: str, multi_pod: bool = False,
                cfg_override=None):
    """Returns (c0, c1, extrapolated_total) cost dicts."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = cfg_override or get_arch(arch)
    shape = SHAPES[shape_name]
    unit = len(cfg.block_pattern)
    nd = cfg.n_dense_layers
    cfg0 = dataclasses.replace(cfg, n_layers=nd, n_dense_layers=nd)
    cfg1 = dataclasses.replace(cfg, n_layers=nd + unit, n_dense_layers=nd)
    c0 = _cell_costs(cfg0, shape, mesh)
    c1 = _cell_costs(cfg1, shape, mesh)
    n_units = (cfg.n_layers - nd) // unit
    total = {}
    for k in ("flops", "bytes"):
        total[k] = c0[k] + n_units * (c1[k] - c0[k])
    total["coll_total"] = (c0["coll"]["total"]
                           + n_units * (c1["coll"]["total"] - c0["coll"]["total"]))
    total["coll_kinds"] = {
        kind: c0["coll"].get(kind, 0)
        + n_units * (c1["coll"].get(kind, 0) - c0["coll"].get(kind, 0))
        for kind in set(c0["coll"]) | set(c1["coll"]) if kind != "total"
    }
    return c0, c1, total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--multipod", action="store_true")
    args = ap.parse_args()
    c0, c1, total = unit_census(args.arch, args.shape, args.multipod)
    chips = 512 if args.multipod else 256
    hw = rl.peaks(rl.V5E)
    print(json.dumps({
        "c0_coll": c0["coll"], "c1_coll": c1["coll"],
        "extrapolated": total,
        "coll_s_per_dev": total["coll_total"] / chips / hw["ici_bw"],
        "flops_s": total["flops"] * chips / chips / hw["flops_bf16"],
    }, indent=1))


if __name__ == "__main__":
    main()
