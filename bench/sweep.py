#!/usr/bin/env python3
"""Offered-load sweep of a serve cell, to find the rate it sustains.

    python bench/sweep.py --workload <serve cell> --seconds 20 --rates 300 450 600

One process: the cell's set-up once, then for each rate a fresh service
and a window of the cell's own mix at that rate. Prints, per rate, the
latency quantiles, rejects and completed rows against offered rows. The
knee is the highest rate with no ``queue_full`` and no backlog growing
through the window (the last third's p50 no higher than the first's).
The serve cell's traffic file then fixes four fifths of it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    import run
    import spec

    bench, w, config, traffic = spec.cell(args.workload)
    try:
        run.require_chips(jax, w["chips"])
    except run.Refused as e:
        print(f"sweep: refused: {e}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(BENCH.parent / "src"))
    from repro.runtime import compile_cache

    import cells

    compile_cache.enable()
    cell = cells.make(config, dict(traffic), args.seed, w["chips"])
    cell.setup()
    first = True
    for rate in args.rates:
        if not first:
            cell.start_service()
        first = False
        cell.traffic["rate_per_s"] = rate
        cell.window(args.seconds)
        cell.release()
        lat = cell.latencies_ms()
        third = len(lat) // 3
        e2e = cell.end_to_end()
        res = cell.stats["results"]
        reasons = {}
        for r in res:
            if r is not None and not r.ok:
                reasons[r.reason] = reasons.get(r.reason, 0) + 1
        offered = sum(q["rows"] for q in cell.requests) / args.seconds
        print(json.dumps({
            "rate": rate, "requests": len(lat), **e2e,
            "offered_rows_per_s": offered, "rejects": reasons,
            "never": sum(r is None for r in res),
            "p50_first_third_ms": float(np.percentile(lat[:third], 50)),
            "p50_last_third_ms": float(np.percentile(lat[-third:], 50)),
            "batch_fill_pct": (cell.registry_diff["serve_svc_batch_fill_pct"]
                               ["sum"] / max(cell.registry_diff[
                                   "serve_svc_batch_fill_pct"]["count"], 1)),
            "sender_late_p99_ms": float(np.percentile(cell.sender_late_ms(),
                                                      99))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
