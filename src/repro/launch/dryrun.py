"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as its own process (``python -m repro.launch.dryrun``): the
first two lines force 512 host devices BEFORE any jax import — smoke tests
and benches must never see this.

For each live cell (see configs.base.cells): builds the appropriate step
(train_step for train shapes, serve prefill/decode for inference shapes),
``jit(...).lower(*ShapeDtypeStructs)`` with explicit in/out shardings,
``.compile()``, then records memory_analysis + cost_analysis + the HLO
collective-byte census into a JSONL file consumed by benchmarks/README.md and
benchmarks/bench_roofline.py.

Also dry-runs the paper's own workload (distributed LAMC co-clustering,
``--arch lamc-coclustering``) on the same meshes.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs.base import SHAPES, cells, get_arch  # noqa: E402
from repro.core import LAMCConfig  # noqa: E402
from repro.core.distributed import lamc_input_specs, lamc_step_fn  # noqa: E402
from repro.core.partition import PartitionPlan  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch import steps as steps_mod  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

# The paper's own workload cells: (name, rows, cols, m, n, t_p, k)
LAMC_SHAPES = {
    "lamc_1m": dict(rows=1_048_576, cols=262_144, m=16, n=16, t_p=2, k=16),
    "lamc_4m": dict(rows=4_194_304, cols=262_144, m=16, n=16, t_p=1, k=16),
}


def _mesh_for(name: str):
    if name == "multipod":
        return make_production_mesh(multi_pod=True)
    return make_production_mesh(multi_pod=False)


def dryrun_lm_cell(arch_name: str, shape_name: str, mesh_name: str) -> dict:
    mesh = _mesh_for(mesh_name)
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    t0 = time.time()
    if shape.kind == "train":
        step, structs, in_sh, out_sh = steps_mod.build_train_step(cfg, shape, mesh)
        state_struct, ispecs = structs
        # donate the train state: the production loop aliases it in place —
        # without donation buffer assignment double-counts params+opt as temp
        fn = jax.jit(step, in_shardings=in_sh, out_shardings=None,
                     donate_argnums=(0,))
        with mesh:
            lowered = fn.lower(state_struct, ispecs)
    elif shape.kind == "prefill":
        step, structs, in_sh, out_sh = steps_mod.build_prefill_step(cfg, shape, mesh)
        p_struct, ispecs = structs
        fn = jax.jit(step, in_shardings=in_sh, out_shardings=None)
        with mesh:
            lowered = fn.lower(p_struct, ispecs)
    else:
        step, structs, in_sh, out_sh = steps_mod.build_decode_step(cfg, shape, mesh)
        p_struct, cache_struct, ispecs = structs
        p_sh, c_sh, i_sh = in_sh
        args = [p_struct, cache_struct, ispecs["token"], ispecs["pos"]]
        shards = [p_sh, c_sh, i_sh["token"], i_sh["pos"]]
        if "enc_out" in ispecs:
            args.append(ispecs["enc_out"])
            shards.append(i_sh["enc_out"])
        # donate the KV cache (serving updates it in place)
        fn = jax.jit(step, in_shardings=tuple(shards), out_shardings=None,
                     donate_argnums=(1,))
        with mesh:
            lowered = fn.lower(*args)
    lower_s = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    cost = dict(compiled.cost_analysis() or {})
    mem = compiled.memory_analysis()
    mem_stats = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            mem_stats[attr] = getattr(mem, attr, None)
    hlo = compiled.as_text()
    chips = mesh.devices.size
    rep = rl.roofline_terms(steps_mod.padded_cfg(cfg), shape, mesh_name,
                            chips, cost, hlo)
    rec = dataclasses.asdict(rep)
    rec.update(memory=mem_stats, lower_s=round(lower_s, 1),
               compile_s=round(compile_s, 1), status="ok")
    return rec


def dryrun_lamc_cell(shape_name: str, mesh_name: str) -> dict:
    mesh = _mesh_for(mesh_name)
    spec = LAMC_SHAPES[shape_name]
    m, n, t_p = spec["m"], spec["n"], spec["t_p"]
    block_axes = ("data", "model")
    resample_axis = None
    if "pod" in mesh.axis_names:
        if t_p % mesh.shape["pod"] == 0:
            # pod axis parallelizes the T_p resamples (§Perf L3)
            resample_axis = "pod"
        else:
            # T_p=1: split the block grid across pods instead
            m *= mesh.shape["pod"]
            block_axes = ("pod", "data", "model")
    plan = PartitionPlan(
        n_rows=spec["rows"], n_cols=spec["cols"], m=m, n=n,
        phi=spec["rows"] // m, psi=spec["cols"] // n, t_p=t_p, seed=0)
    cfg = LAMCConfig(n_row_clusters=spec["k"], n_col_clusters=spec["k"],
                     svd_iters=4, kmeans_iters=16)
    step, in_sh, out_sh = lamc_step_fn(cfg, plan, mesh, block_axes,
                                       resample_axis=resample_axis)
    a_spec = lamc_input_specs(plan)
    t0 = time.time()
    fn = jax.jit(step, in_shardings=in_sh, out_shardings=None)
    with mesh:
        lowered = fn.lower(a_spec)
    lower_s = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    cost = dict(compiled.cost_analysis() or {})
    hlo = compiled.as_text()
    chips = mesh.devices.size
    coll = rl.collective_bytes_from_hlo(hlo)
    flops = float(cost.get("flops", 0.0))
    hbytes = float(cost.get("bytes accessed", 0.0))
    hw = rl.peaks(rl.V5E)
    rec = dict(
        arch="lamc-coclustering", shape=shape_name, mesh=mesh_name,
        chips=chips, hlo_flops=flops, hlo_bytes=hbytes,
        collective_bytes=coll["total"], collectives=coll,
        compute_s=flops / (chips * hw["flops_bf16"]),
        memory_s=hbytes / (chips * hw["hbm_bw"]),
        collective_s=coll["total"] / (chips * hw["ici_bw"]),
        lower_s=round(lower_s, 1), compile_s=round(compile_s, 1),
        status="ok",
    )
    terms = {k: rec[k] for k in ("compute_s", "memory_s", "collective_s")}
    rec["dominant"] = max(terms, key=terms.get).replace("_s", "")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="single arch (default: all)")
    ap.add_argument("--shape", default=None, help="single shape (default: all)")
    ap.add_argument("--mesh", default="singlepod",
                    choices=["singlepod", "multipod", "both"])
    ap.add_argument("--out", default="dryrun_results.jsonl")
    ap.add_argument("--include-lamc", action="store_true", default=True)
    ap.add_argument("--skip-lamc", dest="include_lamc", action="store_false")
    args = ap.parse_args()

    meshes = ["singlepod", "multipod"] if args.mesh == "both" else [args.mesh]
    todo = []
    if args.arch == "lamc-coclustering":
        for m in meshes:
            for s in (LAMC_SHAPES if args.shape is None else [args.shape]):
                todo.append(("lamc", s, m))
    else:
        for cfg, shape, live, why in cells(include_skipped=True):
            if args.arch and cfg.name != args.arch:
                continue
            if args.shape and shape.name != args.shape:
                continue
            for m in meshes:
                todo.append(("lm", (cfg.name, shape.name, live, why), m))
        if args.include_lamc and args.arch is None and args.shape is None:
            for m in meshes:
                for s in LAMC_SHAPES:
                    todo.append(("lamc", s, m))

    done = set()
    if os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") == "ok" or r.get("status") == "skipped":
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    with open(args.out, "a") as f:
        for kind, payload, mesh_name in todo:
            if kind == "lm":
                arch, shape, live, why = payload
                key = (arch, shape, mesh_name)
                if key in done:
                    print(f"[skip-cached] {key}", flush=True)
                    continue
                if not live:
                    rec = dict(arch=arch, shape=shape, mesh=mesh_name,
                               status="skipped", reason=why)
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                    print(f"[skipped] {key}: {why}", flush=True)
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                try:
                    rec = dryrun_lm_cell(arch, shape, mesh_name)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = dict(arch=arch, shape=shape, mesh=mesh_name,
                               status="error", error=f"{type(e).__name__}: {e}",
                               tb=traceback.format_exc()[-2000:])
            else:
                key = ("lamc-coclustering", payload, mesh_name)
                if key in done:
                    print(f"[skip-cached] {key}", flush=True)
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                try:
                    rec = dryrun_lamc_cell(payload, mesh_name)
                except Exception as e:  # noqa: BLE001
                    rec = dict(arch="lamc-coclustering", shape=payload,
                               mesh=mesh_name, status="error",
                               error=f"{type(e).__name__}: {e}",
                               tb=traceback.format_exc()[-2000:])
            f.write(json.dumps(rec) + "\n")
            f.flush()
            status = rec["status"]
            extra = ""
            if status == "ok":
                extra = (f" dominant={rec.get('dominant')}"
                         f" compute={rec.get('compute_s', 0):.4f}s"
                         f" mem={rec.get('memory_s', 0):.4f}s"
                         f" coll={rec.get('collective_s', 0):.4f}s"
                         f" compile={rec.get('compile_s')}s")
            print(f"[{status}] {key}{extra}", flush=True)


if __name__ == "__main__":
    main()
