#!/usr/bin/env python3
"""Record a small profiler trace of LAMC fits on the chip, as plain JSON.

    python bench/record_trace.py --chips 1 --out trace_1chip.json.gz
    python bench/record_trace.py --chips 4 --out trace_4chip.json.gz

Fits a small planted matrix a few times inside a ``bench.window``
annotation (``lamc_cocluster`` on one chip, ``distributed_lamc`` on a
2 x 2 mesh), and keeps the planes that ``trace_reduce`` reads: the
devices' ``XLA Ops`` lines and the host's events, cut to the window. The
file is the recorded trace that ``tests/test_trace_reduce.py`` checks the
reduction on.
"""

from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--out", required=True)
    ap.add_argument("--fits", type=int, default=3)
    args = ap.parse_args(argv)

    import jax
    from jax.sharding import AxisType, NamedSharding
    from jax.sharding import PartitionSpec as P

    import data
    import trace_reduce
    from repro.core import LAMCConfig, lamc_cocluster
    from repro.core.distributed import distributed_lamc
    from repro.core.partition import make_plan

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < args.chips:
        print("record_trace: needs the chips it is asked for", file=sys.stderr)
        return 1
    rows, cols, k = 8192 * args.chips, 2048, 8
    cfg = LAMCConfig(n_row_clusters=k, n_col_clusters=k,
                     min_cocluster_rows=rows // k, min_cocluster_cols=cols // k)
    if args.chips == 1:
        a = data.plant_dense(1, rows, cols, k, k, signal=4.0, noise=0.6).a
        fit = lambda: lamc_cocluster(a, cfg)
    else:
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             devices=jax.devices()[:4],
                             axis_types=(AxisType.Auto,) * 2)
        sh = NamedSharding(mesh, P(("data",), "model"))
        a = data.plant_dense(1, rows, cols, k, k, signal=4.0, noise=0.6,
                             sharding=sh).a
        plan = make_plan(rows, cols, min_cocluster_rows=rows // k,
                         min_cocluster_cols=cols // k, workers=4, k=k)
        fit = lambda: distributed_lamc(mesh, a, cfg, plan)
    jax.block_until_ready(fit())
    tmp = tempfile.mkdtemp(prefix="bench-record-")
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(args.fits):
                with jax.profiler.TraceAnnotation("bench.fit"):
                    jax.device_get(fit().row_labels)
        jax.profiler.stop_trace()
        planes = []
        for p in sorted(Path(tmp).rglob("*.xplane.pb")):
            planes += trace_reduce.load(str(p))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    t0, t1 = trace_reduce.window(planes)
    kept = []
    for pl in planes:
        device = bool(trace_reduce.DEVICE_PLANE.match(pl["name"]))
        if not (device or pl["name"].startswith("/host:")):
            continue
        lines = []
        for ln in pl["lines"]:
            evs = [e for e in ln["events"] if e[1] + e[2] >= t0 and e[1] <= t1]
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        kept.append({"name": pl["name"], "lines": lines})
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    trace_reduce.save_json(kept, args.out)
    names = sorted({(pl["name"], ln["name"], len(ln["events"]))
                    for pl in kept for ln in pl["lines"]})
    print("planes/lines:", names)
    red = trace_reduce.reduce(kept)
    if red is None:
        print("record_trace: no device operations found", file=sys.stderr)
        return 1
    print({k: v for k, v in red.items() if k != "breakdown"})
    print(red["breakdown"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
