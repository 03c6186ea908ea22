"""Online co-cluster assignment server (thin driver).

``python -m repro.launch.serve_lamc --ckpt /tmp/lamc_model --fit-demo``
fits a small planted model out-of-core (``streaming.fit``), saves it, and
then serves batched ``assign_rows``/``assign_cols`` requests *from the
restored checkpoint* — proving the full fit → save → load → serve loop.
Against an existing checkpoint, drop ``--fit-demo``.

This module is deliberately thin: request validation, admission,
batching, and hot swap live in ``repro.streaming.serve`` (DESIGN.md
§15); the default mode here is the single-process direct loop (the
per-PR latency trajectory in BENCH_stream.json), and ``--service`` runs
the same synthetic stream through a full :class:`streaming.AssignService`
(admission queue + coalescer + worker replicas). The adversarial load
mix and swap-under-load live in ``benchmarks/bench_serve.py``.

Modeled on ``launch.serve``: the assignment function is jitted once,
warmed up, and driven by a request loop; per-batch wall-clock latencies
are aggregated into p50/p99 and QPS (requests = rows assigned). Rows are
merged into ``BENCH_stream.json`` (same contract as ``benchmarks/run.py``)
so serving latency is tracked per-PR next to the chunked-fit throughput.

Malformed requests (wrong width/rank, non-finite payloads) are *rejected
per request* — counted in ``serve_assign_*_errors`` next to p50/p99 —
instead of crashing the loop or poisoning the latency stats with NaN
scores. ``--adversarial N`` interleaves N bad batches into the stream to
demonstrate the path (the smoke lane runs it).

Latency aggregation runs on an ``obs.Histogram`` (fixed geometric
buckets), not a materialized sample list: memory stays O(buckets)
however long the request stream runs — an adversarial flood cannot grow
the process — and p50/p99 come from the bucket interpolation the oracle
test in ``tests/test_obs.py`` pins against ``np.percentile``. With
``REPRO_OBS=1`` (or ``--trace-out``) the loop also emits a span trace.
"""

from __future__ import annotations

import argparse
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, streaming
from repro.data import planted_cocluster_matrix

__all__ = ["fit_demo_model", "validate_request", "serve", "serve_service",
           "main"]


def fit_demo_model(ckpt_dir: str, *, n_rows: int = 1024, n_cols: int = 512,
                   k: int = 5, chunk_rows: int = 256, seed: int = 0) -> None:
    """Out-of-core fit on a planted matrix and save the model artifact."""
    rng = np.random.default_rng(seed)
    data = planted_cocluster_matrix(rng, n_rows, n_cols, k=k, d=k,
                                    signal=4.0, noise=0.6)
    cfg = streaming.StreamConfig(n_row_clusters=k, n_col_clusters=k, seed=seed)
    model, stats = streaming.fit(
        streaming.iter_row_chunks(data.matrix, chunk_rows), cfg)
    streaming.save_model(ckpt_dir, model, extra={
        "fit_stats": {"rows_seen": stats.rows_seen, "chunks": stats.chunks,
                      "rows_per_s": round(stats.rows_per_s, 1)}})
    print(f"fit-demo: {stats.rows_seen}x{stats.n_cols} in {stats.chunks} "
          f"chunks ({stats.rows_per_s:.0f} rows/s) -> saved to {ckpt_dir}")


def validate_request(x, dim: int) -> str | None:
    """Reject reason for one request batch, or None if servable.

    Thin wrapper over the service layer's reason-coded validator
    (``streaming.serve.validate_request``) — one taxonomy for the
    direct loop and the admission queue; this driver keeps the legacy
    flat-string form.
    """
    bad = streaming.validate_request(x, dim)
    if bad is None:
        return None
    code, detail = bad
    return f"{code}: {detail}"


def _adversarial_batch(i: int, batch: int, dim: int):
    """Deterministic rotation of the malformed-request taxonomy."""
    kind = i % 3
    if kind == 0:
        return np.zeros((batch, dim + 3), np.float32)       # wrong width
    if kind == 1:
        x = np.zeros((batch, dim), np.float32)
        x[0, 0] = np.nan                                    # poisoned payload
        return x
    return np.zeros((batch * dim,), np.float32)             # wrong rank


def serve(ckpt_dir: str, *, batch: int = 64, requests: int = 32,
          rows: int | None = None, warmup: int = 3, axis: str = "rows",
          seed: int = 1, adversarial: int = 0,
          registry: obs.Registry | None = None) -> dict:
    """Serve a stream of synthetic request batches; report latency/QPS.

    The stream is ``requests`` full ``batch``-row batches, unless
    ``rows`` is given — then exactly ``rows`` rows are served in
    ``batch``-row batches with a final *partial* batch for the
    remainder, which is why QPS is computed from the rows actually
    served (summed per batch), never ``batch * hist.count``: the old
    formula over-reported whenever the tail batch was short.

    ``adversarial`` extra malformed batches are interleaved into the
    stream; each is rejected (logged + counted), never timed — the
    error counter rides next to the latency stats so a deploy that
    starts bouncing requests is visible in the same bench row.

    Latencies fold into a ``serve_assign_{axis}_latency_us`` histogram on
    ``registry`` (default: a fresh per-call :class:`obs.Registry`, so one
    serve's stats never bleed into another's); rejections increment
    ``serve_assign_{axis}_errors``. Memory is O(buckets) regardless of
    stream length. When every batch was rejected the percentiles are NaN
    (empty histogram) — the error counter is the whole story.
    """
    reg = registry if registry is not None else obs.Registry()
    hist = reg.histogram(f"serve_assign_{axis}_latency_us",
                         help="per-batch assign latency, µs")
    err_ct = reg.counter(f"serve_assign_{axis}_errors",
                         help="rejected request batches")
    if rows is not None:
        sizes = [batch] * (rows // batch) + ([rows % batch]
                                             if rows % batch else [])
    else:
        sizes = [batch] * requests
    with obs.span("serve", axis=axis, batch=batch, requests=len(sizes),
                  adversarial=adversarial) as root:
        model, meta = streaming.load_model(ckpt_dir)
        dim = model.n_cols if axis == "rows" else model.n_rows
        assign = (streaming.assign_rows if axis == "rows"
                  else streaming.assign_cols)
        step = jax.jit(lambda x: assign(model, x))

        rng = np.random.default_rng(seed)
        reqs = jnp.asarray(rng.normal(size=(batch, dim)).astype(np.float32))
        with obs.span("warmup", iters=warmup):
            for _ in range(warmup):
                jax.block_until_ready(step(reqs))
            if sizes and sizes[-1] != batch:
                # pre-compile the tail shape so the partial batch's
                # latency sample measures serving, not tracing
                jax.block_until_ready(step(reqs[:sizes[-1]]))

        # interleave adversarial batches roughly uniformly through the stream
        stream: list[tuple[bool, object]] = list(enumerate(sizes))
        for i in range(adversarial):
            pos = min(len(stream),
                      1 + i * max(1, len(sizes) // max(adversarial, 1)))
            stream.insert(pos, (i, None))

        out = None
        rows_served = 0
        with obs.span("request_loop", total=len(stream)):
            for i, size in stream:
                x = ((reqs[:size] + jnp.float32(i)) if size is not None
                     else _adversarial_batch(i, batch, dim))
                reason = validate_request(x, dim)
                if reason is not None:
                    err_ct.inc()
                    obs.event("request_rejected", reason=reason)
                    print(f"serve[{axis}]: rejected request: {reason}")
                    continue
                t0 = time.perf_counter()
                out = jax.block_until_ready(step(x))
                hist.observe((time.perf_counter() - t0) * 1e6)
                rows_served += int(np.shape(x)[0])

        # percentiles straight off the bucket counts; NaN when every batch
        # was rejected (empty histogram) — same contract as before. QPS is
        # rows actually served over time actually measured: a final
        # partial batch contributes its true row count.
        p50 = hist.percentile(50)
        p99 = hist.percentile(99)
        qps = (rows_served / max(hist.sum / 1e6, 1e-9)
               if hist.count else 0.0)
        root.set(served=hist.count, rows=rows_served,
                 errors=int(err_ct.value),
                 p50_us=None if math.isnan(p50) else round(p50, 1))
    return {
        f"serve_assign_{axis}_p50_us": p50,
        f"serve_assign_{axis}_p99_us": p99,
        f"serve_assign_{axis}_qps": qps,
        f"serve_assign_{axis}_rows": rows_served,
        f"serve_assign_{axis}_errors": int(err_ct.value),
        "_labels_sample": (np.asarray(out.labels[:8]).tolist()
                           if out is not None else []),
        "_model_kind": meta.get("kind"),
        "_batch": batch,
    }


def serve_service(ckpt_dir: str, *, batch: int = 64, requests: int = 32,
                  warmup: int = 3, axis: str = "rows", seed: int = 1,
                  replicas: int = 2, k: int = 1) -> dict:
    """Drive the same synthetic stream through a full ``AssignService``.

    Unlike :func:`serve` (the direct jit loop), this path exercises the
    whole service stack — admission, coalescing into fixed-shape jit
    batches, worker replicas — and reports the *service's* latency
    percentiles (submit → fulfil, which includes queueing). Requests are
    quarter-batch sized so the coalescer has real work to do; every
    ticket, warm-up included, is awaited and checked, so a reject or a
    dropped request fails loudly rather than skewing the stats.
    """
    model, meta = streaming.load_model(ckpt_dir)
    reg = obs.Registry()
    cfg = streaming.ServeConfig(batch=batch, replicas=replicas)
    size = max(1, batch // 4)
    dim = model.n_cols if axis == "rows" else model.n_rows
    rng = np.random.default_rng(seed)
    base = rng.normal(size=(size, dim)).astype(np.float32)
    t_wall = time.perf_counter()
    with streaming.AssignService(model, version="serve_lamc",
                                 config=cfg, metrics=reg) as svc:
        def served_rows(ticket) -> int:
            res = ticket.result(timeout=60.0)
            if not res.ok:
                raise RuntimeError(
                    f"service rejected a well-formed request: "
                    f"{res.reason}: {res.detail}")
            return len(res.labels)

        for _ in range(warmup):
            served_rows(svc.submit(base, axis=axis, k=k))
        t_wall = time.perf_counter()
        tickets = [svc.submit(base + np.float32(i), axis=axis, k=k)
                   for i in range(requests)]
        rows_served = sum(served_rows(t) for t in tickets)
        wall_s = time.perf_counter() - t_wall
        stats = svc.stats()
    qps = rows_served / max(wall_s, 1e-9)
    return {
        f"serve_svc_{axis}_p50_us": stats["p50_request_us"],
        f"serve_svc_{axis}_p99_us": stats["p99_request_us"],
        f"serve_svc_{axis}_qps": qps,
        f"serve_svc_{axis}_rows": rows_served,
        f"serve_svc_{axis}_fill_pct": stats["mean_batch_fill_pct"],
        "_model_kind": meta.get("kind"),
        "_replicas": replicas,
        "_batch": batch,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", required=True, help="model checkpoint directory")
    ap.add_argument("--fit-demo", action="store_true",
                    help="fit + save a small planted model first")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rows", type=int, default=None,
                    help="serve exactly this many rows (final batch may be "
                         "partial) instead of --requests full batches")
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--axis", choices=["rows", "cols", "both"], default="both")
    ap.add_argument("--adversarial", type=int, default=0,
                    help="interleave N malformed request batches (rejected + "
                         "counted, never crash the loop)")
    ap.add_argument("--service", action="store_true",
                    help="route the stream through streaming.AssignService "
                         "(admission queue + coalescer + replicas) instead "
                         "of the direct jit loop")
    ap.add_argument("--replicas", type=int, default=2,
                    help="worker replicas for --service")
    ap.add_argument("--bench-out", default="BENCH_stream.json",
                    help="merge latency rows into this file ('' to skip)")
    ap.add_argument("--trace-out", default="",
                    help="write the serve span trace as JSONL here "
                         "(implies enabling obs spans)")
    args = ap.parse_args(argv)

    from repro.runtime import compile_cache

    compile_cache.enable()
    if args.trace_out:
        obs.configure(enabled=True)
    if obs.enabled():
        obs.reset_trace()
    if args.fit_demo:
        fit_demo_model(args.ckpt)
    axes = ["rows", "cols"] if args.axis == "both" else [args.axis]
    report = {}
    for axis in axes:
        if args.service:
            out = serve_service(args.ckpt, batch=args.batch,
                                requests=args.requests, warmup=args.warmup,
                                axis=axis, replicas=args.replicas)
        else:
            out = serve(args.ckpt, batch=args.batch, requests=args.requests,
                        rows=args.rows, warmup=args.warmup, axis=axis,
                        adversarial=args.adversarial)
        report.update(out)
    bench_rows = {k: round(v, 1) for k, v in report.items()
                  if not k.startswith("_")}
    if args.bench_out:
        from repro.benchio import merge_rows

        merge_rows(args.bench_out, bench_rows,
                   own_prefixes=("stream_", "serve_"))
    if args.trace_out:
        obs.write_trace_jsonl(args.trace_out)
        print(f"serve trace -> {args.trace_out}")
    print(json.dumps({**bench_rows, "batch": args.batch,
                      "requests": args.requests}, indent=2))


if __name__ == "__main__":
    main()
