"""Service load-test harness: the assignment service under real traffic.

Where ``bench_stream.py`` times the bare jitted assign step, this section
drives the full :class:`repro.streaming.AssignService` stack — admission,
fixed-shape batch coalescing, worker replicas, hot swap — under the
traffic mixes a deployment actually sees (DESIGN.md §15). Rows (all land
in ``BENCH_stream.json``; µs column is the p50 submit→fulfil latency,
which *includes* queueing, unlike the direct-loop numbers):

  serve_load_baseline   single-process direct jit loop at the same batch
                        size — the per-replica floor the service is
                        measured against; derived carries QPS.
  serve_load_uniform    uniform random quarter-batch requests, k=1, one
                        replica; derived carries QPS, per-replica QPS,
                        p99 and mean batch fill %.
  serve_load_hotkey     hot-key skew: 90% of requests share one payload
                        (a viral item being re-scored) — exercises the
                        coalescer's fairness, not a cache (scoring is
                        O(batch) regardless).
  serve_load_topk_k4    k=4 overlap-mode traffic (DESIGN.md §11) — the
                        marginal service cost of top-k over argmax.
  serve_load_cols       column-axis traffic (feature width = n_rows).
  serve_load_swap       sustained multi-thread traffic across a hot
                        model swap; derived carries QPS, errors (the
                        harness *fails* unless 0) and versions seen
                        (must be 2: responses from both sides of the
                        swap, each attributable to exactly one).

``--dry-run`` runs every mix at smoke scale with the same invariant
checks and writes nothing — the CI serve-smoke lane runs it; a dropped
or errored request under swap fails the process, not just a number.

CPU numbers are architecture proxies (interpret-mode kernels); the
per-PR trajectory is the signal, as with the other sections.
"""

from __future__ import annotations

import argparse
import threading
import time


def _fit_model(seed: int, *, quick: bool):
    import numpy as np

    from repro import streaming
    from repro.data import planted_cocluster_matrix

    m, n, k = (512, 256, 5) if quick else (2048, 512, 8)
    rng = np.random.default_rng(seed)
    data = planted_cocluster_matrix(rng, m, n, k=k, d=k, signal=4.0,
                                    noise=0.6)
    cfg = streaming.StreamConfig(n_row_clusters=k, n_col_clusters=k,
                                 seed=seed)
    model, _ = streaming.fit(
        streaming.iter_row_chunks(data.matrix, max(128, m // 4)), cfg)
    return model


def _drive(model, payloads, *, axis: str, k: int, replicas: int,
           batch: int) -> dict:
    """Submit ``payloads`` through a fresh service; return traffic stats.

    A fresh service (and metrics registry) per mix keeps every mix's
    percentiles isolated. The first submit is a warm-up for the (axis, k)
    scorer so the timed stream measures serving, not tracing.
    """
    from repro import obs, streaming

    reg = obs.Registry()
    cfg = streaming.ServeConfig(
        batch=batch, replicas=replicas,
        max_queue_rows=sum(p.shape[0] for p in payloads) + batch)
    with streaming.AssignService(model, version="v1", config=cfg,
                                 metrics=reg) as svc:
        warm = svc.submit(payloads[0], axis=axis, k=k).result(timeout=120.0)
        t0 = time.perf_counter()
        tickets = [svc.submit(x, axis=axis, k=k) for x in payloads]
        results = [t.result(timeout=120.0) for t in tickets]
        wall = time.perf_counter() - t0
        stats = svc.stats()
    rows = sum(len(r.labels) for r in results if r.ok)
    errors = sum(not r.ok for r in [warm, *results])
    return {
        "rows": rows, "errors": errors,
        "qps": rows / max(wall, 1e-9),
        "p50_us": stats["p50_request_us"],
        "p99_us": stats["p99_request_us"],
        "fill_pct": stats["mean_batch_fill_pct"],
    }


def _swap_under_load(model, model2, *, batch: int, n_requests: int) -> dict:
    """Pump traffic from 3 threads, hot-swap at the halfway mark.

    Returns stats incl. the set of versions observed in responses. Every
    response must be ok (the zero-drop guarantee) and attributable to
    exactly one version; the caller asserts both.
    """
    import numpy as np

    from repro import obs, streaming

    reg = obs.Registry()
    size = max(1, batch // 4)
    dim = model.n_cols
    cfg = streaming.ServeConfig(batch=batch, replicas=2,
                                max_queue_rows=8 * batch)
    results: list = []
    lock = threading.Lock()
    stop = threading.Event()

    with streaming.AssignService(model, version="v1", config=cfg,
                                 metrics=reg) as svc:
        warm = svc.submit(np.zeros((size, dim), np.float32)).result(
            timeout=120.0)

        def pump(seed: int) -> None:
            rng = np.random.default_rng(seed)
            while not stop.is_set():
                x = rng.normal(size=(size, dim)).astype(np.float32)
                res = svc.submit(x).result(timeout=120.0)
                with lock:
                    results.append(res)

        threads = [threading.Thread(target=pump, args=(i,), daemon=True)
                   for i in range(3)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        while len(results) < n_requests // 2:
            time.sleep(0.002)
        svc.swap(model2, "v2")
        # the pumps kept running through the (slow) pre-warm above, so
        # gate on responses *after* the publish, not a raw total — else
        # the stream can end before a single v2 response exists
        with lock:
            at_swap = len(results)
        while len(results) < max(n_requests, at_swap + 6):
            time.sleep(0.002)
        stop.set()
        for t in threads:
            t.join(timeout=120.0)
        wall = time.perf_counter() - t0
        stats = svc.stats()

    rows = sum(len(r.labels) for r in results if r.ok)
    errors = sum(not r.ok for r in [warm, *results])
    return {
        "rows": rows, "errors": errors,
        "qps": rows / max(wall, 1e-9),
        "p50_us": stats["p50_request_us"],
        "p99_us": stats["p99_request_us"],
        "versions": sorted({r.version for r in results if r.ok}),
    }


def run(report, *, quick: bool = False, dry_run: bool = False) -> None:
    import jax
    import numpy as np

    from repro import streaming

    quick = quick or dry_run
    batch = 32 if quick else 64
    n_req = 24 if dry_run else (64 if quick else 192)
    size = max(1, batch // 4)

    model = _fit_model(0, quick=quick)
    model2 = _fit_model(1, quick=quick)
    dim_rows, dim_cols = model.n_cols, model.n_rows
    rng = np.random.default_rng(2)

    # baseline: the direct jit loop at the same batch size — what one
    # process gets with zero service machinery; per-replica service QPS
    # is judged against this floor
    step = jax.jit(lambda x: streaming.assign_rows(model, x))
    xb = rng.normal(size=(batch, dim_rows)).astype(np.float32)
    jax.block_until_ready(step(xb))
    reps = max(4, n_req // 4)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(step(xb))
    us = (time.perf_counter() - t0) / reps * 1e6
    base_qps = batch / (us / 1e6)
    report(f"serve_load_baseline,{us:.0f},qps={base_qps:.0f}")

    def uniform(n: int, dim: int) -> list:
        return [rng.normal(size=(size, dim)).astype(np.float32)
                for _ in range(n)]

    hot = rng.normal(size=(size, dim_rows)).astype(np.float32)
    hotkey = [hot if i % 10 else
              rng.normal(size=(size, dim_rows)).astype(np.float32)
              for i in range(n_req)]

    mixes = (
        ("serve_load_uniform", uniform(n_req, dim_rows),
         dict(axis="rows", k=1, replicas=1)),
        ("serve_load_hotkey", hotkey, dict(axis="rows", k=1, replicas=2)),
        ("serve_load_topk_k4", uniform(n_req, dim_rows),
         dict(axis="rows", k=4, replicas=1)),
        ("serve_load_cols", uniform(n_req, dim_cols),
         dict(axis="cols", k=1, replicas=1)),
    )
    for name, payloads, kw in mixes:
        d = _drive(model, payloads, batch=batch, **kw)
        if d["errors"]:
            raise RuntimeError(
                f"{name}: {d['errors']} well-formed requests rejected")
        per_rep = d["qps"] / kw["replicas"]
        report(f"{name},{d['p50_us']:.0f},qps={d['qps']:.0f};"
               f"per_replica_qps={per_rep:.0f};p99_us={d['p99_us']:.0f};"
               f"fill_pct={d['fill_pct']:.0f}")

    d = _swap_under_load(model, model2, batch=batch, n_requests=n_req)
    if d["errors"]:
        raise RuntimeError(
            f"serve_load_swap: {d['errors']} requests dropped/errored "
            "across the hot swap — the zero-drop guarantee is broken")
    if d["versions"] != ["v1", "v2"]:
        raise RuntimeError(
            f"serve_load_swap: expected responses from both model "
            f"versions, saw {d['versions']}")
    report(f"serve_load_swap,{d['p50_us']:.0f},qps={d['qps']:.0f};"
           f"p99_us={d['p99_us']:.0f};errors={d['errors']};"
           f"versions={len(d['versions'])};rows={d['rows']}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller model + shorter streams")
    ap.add_argument("--dry-run", action="store_true",
                    help="smoke scale, full invariant checks, no file "
                         "writes (the CI serve-smoke lane)")
    ap.add_argument("--bench-out", default="BENCH_stream.json",
                    help="merge rows into this file ('' to skip)")
    args = ap.parse_args(argv)

    rows: dict[str, float] = {}

    def report(line: str) -> None:
        print(line, flush=True)
        parts = line.split(",")
        if len(parts) >= 2:
            try:
                rows[parts[0]] = float(parts[1])
            except ValueError:
                pass

    run(report, quick=args.quick, dry_run=args.dry_run)

    if args.dry_run:
        print("bench_serve --dry-run OK (all invariants held)")
        return
    if args.bench_out:
        from repro.benchio import merge_rows

        # serve_load_* regenerates whole per run: replace, don't accrete
        total = merge_rows(args.bench_out, rows,
                           own_prefixes=("stream_", "serve_"),
                           replace_prefixes=("serve_load_",))
        print(f"wrote {args.bench_out} ({len(rows)} new / {total} total "
              "entries)")


if __name__ == "__main__":
    main()
