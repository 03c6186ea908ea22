#!/usr/bin/env python3
"""Bring-up run of LAMC on one TPU chip, through its public entry points.

    python chip_smoke.py [--seed 0]          # one chip: four phases
    python chip_smoke.py --chips 4 [--seed 0]  # four chips: the mesh phase

One chip, in order:

1. dense fit: ``lamc_cocluster`` on a planted 65,536 x 16,384 f32 matrix
   (4 GiB, one chip's share of ``launch.dryrun.LAMC_SHAPES["lamc_1m"]``),
   k = d = 16, plan from the plan search; once with the ``LAMCConfig``
   defaults and once with the Pallas k-means and CholeskyQR; then on a
   fixed 2 x 2 block plan with two resamples (extraction and merging);
2. sparse fit: ``lamc_cocluster(input_format="bcoo", spmm_impl="tiled")``
   on ``data.rcv1_proxy()`` (100,000 x 5,000 at 5%) with a single-block
   plan, against the dense path on the same data, plan and seed;
3. streaming fold: ``streaming.fit`` over the dense matrix in 8 row
   chunks of 8,192;
4. serving: both fitted models published through ``ModelRegistry``,
   ``AssignService`` answering a few hundred row and column requests with
   k = 1 and k = 4, and one hot swap started against a queued backlog
   with traffic flowing until it is published.

``--chips 4`` runs only ``distributed_lamc`` on a 2 x 2 mesh with a fixed
4 x 4 block plan, and ``lamc_cocluster`` on one device with the same
matrix, plan and seed to compare against.

Every phase prints its numbers on one line; the last line of standard
output is one JSON object naming the device. The run fails (non-zero
exit, no JSON line) when JAX finds no TPU, when ``REPRO_FORCE_INTERPRET``
is set, when a phase misses its check, when a request is rejected, or
when a main-path kernel dispatched to any tier but ``pallas``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

DENSE_ROWS, DENSE_COLS, DENSE_K = 65_536, 16_384, 16
CHUNK_ROWS = 8_192
SIGNAL, NOISE = 4.0, 0.6           # the planted band of the CPU e2e tests
SPARSE_K = 10                      # data.rcv1_proxy plants 10 x 10
MIN_NMI = 0.5                      # the CPU e2e tests' quality floor
#: sparse-vs-dense label agreement (NMI) on rcv1_proxy; 0.80 measured on
#: a v5e, the floor leaves room for a different seed, not for a wrong scale
MIN_SPARSE_AGREEMENT = 0.6
SERVE_BATCH, SERVE_REQ_ROWS, SERVE_REQUESTS = 64, 16, 400
SERVE_POOL = 1_024                 # distinct rows / columns requests draw on
SERVE_WINDOW = 8                   # outstanding requests after the swap
#: kernels the one-chip phases must run, each on the ``pallas`` tier only
MAIN_PATH_KERNELS = ("kmeans_update", "kmeans_assign", "cosine_assign",
                     "cosine_topk", "spmm_tiled", "spmm_ata")


class SmokeFailure(RuntimeError):
    """A phase missed its check."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _require_tpu(jax) -> None:
    platform = jax.devices()[0].platform
    _check(platform == "tpu", f"JAX sees no TPU (platform {platform!r})")


class _Compiles:
    """Seconds spent in XLA backend compiles, read per phase."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


@contextlib.contextmanager
def _timed(compiles, out: dict):
    c0, t0 = compiles.seconds, time.perf_counter()
    yield
    out["wall_s"] = time.perf_counter() - t0
    out["compile_s"] = compiles.seconds - c0


def _fmt(**kv) -> str:
    return " ".join(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in kv.items())


def _device_line(jax) -> str:
    return _fmt(device=repr(jax.devices()[0].device_kind),
                count=len(jax.devices()))


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def plant_dense(jax, seed: int, n_rows: int, n_cols: int, k: int):
    """Planted k x k checkerboard made on the device: ``(a, rows, cols)``.

    The same model as ``data.planted_cocluster_matrix`` (balanced,
    shuffled labels, cell means uniform in [0, SIGNAL], Gaussian noise),
    with the noise drawn by ``jax.random`` so the 4 GiB matrix never
    crosses from the host.
    """
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    rows = np.arange(n_rows) % k
    cols = np.arange(n_cols) % k
    rng.shuffle(rows)
    rng.shuffle(cols)
    mu = rng.uniform(0.0, SIGNAL, (k, k)).astype(np.float32)

    @jax.jit
    def make(key, mu, r, c):
        noise = jax.random.normal(key, (r.shape[0], c.shape[0]), jnp.float32)
        return mu[r][:, c] + NOISE * noise

    a = make(jax.random.key(seed), jnp.asarray(mu), jnp.asarray(rows),
             jnp.asarray(cols))
    return a.block_until_ready(), rows.astype(np.int32), cols.astype(np.int32)


def dense_fit(jax, compiles, a, truth, seed: int, k: int = DENSE_K):
    """Phase 1: ``lamc_cocluster`` with the plan search, twice, then on a
    fixed 2 x 2 block plan with two resamples, so block extraction and
    the merge across blocks and resamples run on the chip too."""
    import numpy as np

    from repro.core import LAMCConfig, cocluster_scores, lamc_cocluster
    from repro.core.partition import PartitionPlan

    _require_tpu(jax)
    n_rows, n_cols = a.shape
    base = dict(n_row_clusters=k, n_col_clusters=k, seed=seed,
                min_cocluster_rows=n_rows // k, min_cocluster_cols=n_cols // k)
    multi = PartitionPlan(n_rows, n_cols, m=2, n=2, phi=n_rows // 2,
                          psi=n_cols // 2, t_p=2, seed=seed)
    fits = {}
    for run, extra, plan in (
            ("defaults", {}, None),
            ("pallas_cholesky",
             dict(assign_impl="pallas", qr_method="cholesky"), None),
            ("multi_block", {}, multi)):
        cfg = LAMCConfig(**base, **extra)
        cold, warm = {}, {}
        with _timed(compiles, cold):
            out = lamc_cocluster(a, cfg, plan=plan)
            rl, cl = np.asarray(out.row_labels), np.asarray(out.col_labels)
        with _timed(compiles, warm):
            again = lamc_cocluster(a, cfg, plan=plan)
            rl2 = np.asarray(again.row_labels)
        s = cocluster_scores(rl, cl, *truth)
        p = out.plan
        print("dense_fit", _fmt(
            run=run, rows=n_rows, cols=n_cols, k=k,
            plan=f"{p.m}x{p.n}/{p.phi}x{p.psi}/t_p={p.t_p}",
            cold_wall_s=cold["wall_s"], cold_compile_s=cold["compile_s"],
            warm_wall_s=warm["wall_s"], warm_compile_s=warm["compile_s"],
            nmi=s["nmi"], ari=s["ari"], row_nmi=s["row_nmi"],
            col_nmi=s["col_nmi"], peak_bytes_in_use=_peak_bytes(
                jax.devices()[0])), _device_line(jax), flush=True)
        _check(np.array_equal(rl, rl2), f"dense_fit[{run}]: repeat differs")
        _check(s["nmi"] > MIN_NMI,
               f"dense_fit[{run}]: NMI {s['nmi']:.4f} <= {MIN_NMI}")
        fits[run] = out
    return fits


def sparse_fit(jax, compiles, seed: int, data=None, k: int = SPARSE_K):
    """Phase 2: the tiled sparse route on a single-block plan, with the
    dense path on the same data, plan and seed as its reference."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from repro import obs
    from repro.core import LAMCConfig, cocluster_scores, lamc_cocluster, nmi
    from repro.core import sparse as core_sparse
    from repro.core.partition import make_plan
    from repro.data import rcv1_proxy, to_bcoo

    _require_tpu(jax)
    data = data if data is not None else rcv1_proxy(seed)
    n_rows, n_cols = data.shape
    bcoo = to_bcoo(data.matrix)
    density = core_sparse.density(bcoo)
    cfg = LAMCConfig(n_row_clusters=k, n_col_clusters=k, seed=seed,
                     min_cocluster_rows=n_rows // k,
                     min_cocluster_cols=n_cols // k,
                     input_format="bcoo", spmm_impl="tiled",
                     assign_impl="pallas", qr_method="cholesky")
    plan = make_plan(n_rows, n_cols, min_cocluster_rows=cfg.min_cocluster_rows,
                     min_cocluster_cols=cfg.min_cocluster_cols, k=k, seed=seed,
                     density=density, spmm_impl="tiled")
    _check(plan.blocks_per_resample == 1 and plan.phi == n_rows
           and plan.psi == n_cols,
           f"sparse_fit: plan search gave a multi-block plan {plan}")
    fallback = obs.get_registry().counter("spmm_ata_vmem_fallback")
    fallback0 = fallback.value
    sp, dn = {}, {}
    with _timed(compiles, sp):
        out_s = lamc_cocluster(bcoo, cfg, plan=plan)
        rs, cs = np.asarray(out_s.row_labels), np.asarray(out_s.col_labels)
    dense_cfg = dataclasses.replace(cfg, input_format="dense", spmm_impl="auto")
    with _timed(compiles, dn):
        out_d = lamc_cocluster(jnp.asarray(data.matrix), dense_cfg, plan=plan)
        rd, cd = np.asarray(out_d.row_labels), np.asarray(out_d.col_labels)
    s = cocluster_scores(rs, cs, data.row_labels, data.col_labels)
    sd = cocluster_scores(rd, cd, data.row_labels, data.col_labels)
    agree = 0.5 * (nmi(rs, rd) + nmi(cs, cd))
    print("sparse_fit", _fmt(
        rows=n_rows, cols=n_cols, density=density, nnz=int(bcoo.nse), k=k,
        plan=f"{plan.m}x{plan.n}/t_p={plan.t_p}/route={out_s.plan.spmm_route}",
        wall_s=sp["wall_s"], compile_s=sp["compile_s"],
        nmi=s["nmi"], ari=s["ari"], dense_wall_s=dn["wall_s"],
        dense_compile_s=dn["compile_s"], dense_nmi=sd["nmi"],
        dense_ari=sd["ari"], agreement_nmi=agree,
        spmm_ata_vmem_fallback=int(fallback.value - fallback0),
        peak_bytes_in_use=_peak_bytes(jax.devices()[0])),
        _device_line(jax), flush=True)
    _check(out_s.plan.spmm_route == "tiled",
           f"sparse_fit: ran route {out_s.plan.spmm_route!r}, not tiled")
    _check(all(0 <= x.min() and x.max() < k for x in (rs, cs, rd, cd)),
           "sparse_fit: labels out of range")
    _check(s["nmi"] > MIN_NMI and sd["nmi"] > MIN_NMI,
           f"sparse_fit: NMI {s['nmi']:.4f} sparse / {sd['nmi']:.4f} dense "
           f"<= {MIN_NMI}")
    _check(agree > MIN_SPARSE_AGREEMENT,
           f"sparse_fit: sparse and dense labels agree at NMI {agree:.4f} "
           f"<= {MIN_SPARSE_AGREEMENT}")
    return out_s


def streaming_fold(jax, compiles, a, batch, truth, seed: int,
                   chunk_rows: int = CHUNK_ROWS):
    """Phase 3: ``streaming.fit`` over device row chunks of the matrix."""
    import numpy as np

    from repro import streaming
    from repro.core import LAMCConfig, cocluster_scores, nmi

    _require_tpu(jax)
    k = batch.row_votes.shape[1]
    n_rows, n_cols = a.shape
    cfg = streaming.stream_config_from_lamc(LAMCConfig(
        n_row_clusters=k, n_col_clusters=k, seed=seed))
    chunks = (a[i:i + chunk_rows] for i in range(0, n_rows, chunk_rows))
    t = {}
    with _timed(compiles, t):
        model, stats = streaming.fit(chunks, cfg)
        rl = np.asarray(model.row_labels)
    s = cocluster_scores(rl, np.asarray(model.col_labels), *truth)
    vs_batch = nmi(rl, np.asarray(batch.row_labels))
    print("streaming_fold", _fmt(
        rows=stats.rows_seen, cols=n_cols, chunks=stats.chunks,
        chunk_rows=chunk_rows, rows_per_s=stats.rows_per_s,
        wall_s=t["wall_s"], compile_s=t["compile_s"],
        row_nmi_vs_batch=vs_batch, nmi=s["nmi"], ari=s["ari"],
        peak_bytes_in_use=_peak_bytes(jax.devices()[0])),
        _device_line(jax), flush=True)
    _check(stats.chunks == -(-n_rows // chunk_rows),
           f"streaming_fold: folded {stats.chunks} chunks")
    _check(s["nmi"] > MIN_NMI,
           f"streaming_fold: NMI {s['nmi']:.4f} <= {MIN_NMI}")
    return model


def _percentile(hist: dict, p: float) -> float | None:
    """``p``-th percentile of a histogram snapshot (or a ``Registry.diff``
    of two), interpolated linearly inside the bucket that holds it."""
    counts, bounds = hist["counts"], hist["buckets"]
    total = sum(counts)
    if not total:
        return None
    want, cum = p / 100.0 * total, 0
    for i, c in enumerate(counts):
        if c and cum + c >= want:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i] if i < len(bounds) else hist["max"]
            return lo + (hi - lo) * (want - cum) / c
        cum += c
    return hist["max"]


def serving(jax, compiles, a, model_v1, model_v2, truth, seed: int,
            requests: int = SERVE_REQUESTS):
    """Phase 4: publish, serve rows and columns at k = 1 and k = 4, and
    hot-swap to the second version with a backlog queued.

    Half of ``requests`` is queued while the workers are held off the
    queue, so the swap starts against a known backlog; traffic goes on,
    closed-loop with ``SERVE_WINDOW`` requests outstanding, while the
    successor warms, and for another ``requests // 2`` after the swap.
    Latency percentiles are read from the service histogram for the swap
    window and the steady window apart, warm-ups excluded.
    """
    import numpy as np

    from repro import obs, streaming
    from repro.core import nmi

    _require_tpu(jax)
    rng = np.random.default_rng(seed + 1)
    n_rows, n_cols = a.shape
    r = SERVE_REQ_ROWS
    row_ids = rng.choice(n_rows, size=SERVE_POOL, replace=False)
    col_ids = rng.choice(n_cols, size=SERVE_POOL, replace=False)
    row_pool = np.asarray(a[row_ids])                   # (P, n_cols)
    col_pool = np.asarray(a[:, col_ids]).T              # (P, n_rows)
    pools = {"rows": (row_pool, row_ids, truth[0]),
             "cols": (col_pool, col_ids, truth[1])}
    mixes = [(axis, k) for axis in ("rows", "cols") for k in (1, 4)]

    with tempfile.TemporaryDirectory() as root:
        reg = streaming.ModelRegistry(root)
        e1 = reg.publish("lamc", model_v1)
        e2 = reg.publish("lamc", model_v2)
        m1, _ = reg.load("lamc", e1.version)
        m2, _ = reg.load("lamc", e2.version)
    cfg = streaming.ServeConfig(batch=SERVE_BATCH, replicas=2,
                                max_queue_rows=requests * r)
    metrics = obs.Registry()
    lat_name = "serve_svc_request_latency_us"

    def request(svc, i):
        axis, k = mixes[i % len(mixes)]
        lo = (i // len(mixes)) * r % SERVE_POOL
        return axis, k, lo, svc.submit(pools[axis][0][lo:lo + r],
                                       axis=axis, k=k)

    t = {}
    with _timed(compiles, t):
        with streaming.AssignService(m1, version=e1.version, config=cfg,
                                     metrics=metrics) as svc:
            for axis, k in mixes:               # compile every scorer shape
                x = pools[axis][0][:r]
                res = svc.submit(x, axis=axis, k=k).result(600.0)
                _check(res.ok, f"serving: warm-up {axis} k={k} rejected: "
                               f"{res.reason}: {res.detail}")
            snap_warm = metrics.snapshot()
            t_traffic = time.perf_counter()
            half = requests // 2
            # the workers pop batches under the service's condition lock;
            # holding it (submit re-enters it) queues the whole first half
            with svc._cond:
                backlog = [request(svc, i) for i in range(half)]
                in_flight = sum(not tk.done() for *_, tk in backlog)
            swap = svc.swap_async(lambda: m2, e2.version)
            i, during = half, []
            while not swap.done():              # traffic while it warms
                window = [request(svc, i + j) for j in range(SERVE_WINDOW)]
                i += SERVE_WINDOW
                during += [(axis, k, lo, tk.result(600.0))
                           for axis, k, lo, tk in window]
            swapped = swap.result(600.0)
            backlog = [(axis, k, lo, tk.result(600.0))
                       for axis, k, lo, tk in backlog]
            snap_swap = metrics.snapshot()
            steady = []
            for i0 in range(i, i + half, SERVE_WINDOW):
                window = [request(svc, j) for j in
                          range(i0, min(i0 + SERVE_WINDOW, i + half))]
                steady += [(axis, k, lo, tk.result(600.0))
                           for axis, k, lo, tk in window]
            snap_end = metrics.snapshot()
            traffic_s = time.perf_counter() - t_traffic
    _check(swapped.ok, f"serving: swap failed: {swapped.detail}")
    results = backlog + during + steady
    lat_swap = obs.Registry.diff(snap_swap, snap_warm)[lat_name]
    lat_steady = obs.Registry.diff(snap_end, snap_swap)[lat_name]
    bad = [res for *_, res in results if not res.ok]
    internal = sum(res.reason == "internal_error" for res in bad)
    versions = sorted({res.version for *_, res in results if res.ok})
    steady_versions = sorted({res.version for *_, res in steady})
    by_version = {v: sum(res.version == v for *_, res in backlog + during)
                  for v in (e1.version, e2.version)}
    served: dict = {mix: [] for mix in mixes}
    for axis, k, lo, res in results:
        if res.ok:
            labels = res.labels if k == 1 else res.labels[:, 0]
            served[(axis, k)].append((lo, labels))
    agree = {}
    for (axis, k), got in served.items():
        ids, truth_axis = pools[axis][1], pools[axis][2]
        pred = np.concatenate([lab for _, lab in got])
        want = np.concatenate([truth_axis[ids[lo:lo + r]] for lo, _ in got])
        agree[f"{axis}_k{k}_nmi"] = nmi(pred, want)
    print("serving", _fmt(
        requests=len(results), rows_per_request=r, batch=SERVE_BATCH,
        ok=len(results) - len(bad), rejected=len(bad),
        internal_errors=internal, backlog=len(backlog),
        in_flight_at_swap=in_flight, during_swap=len(during),
        after_swap=len(steady), swapped_from=swapped.detail.split()[-1],
        before_swap_answered_v1=by_version[e1.version],
        before_swap_answered_v2=by_version[e2.version],
        versions="/".join(versions),
        traffic_s=traffic_s, wall_s=t["wall_s"], compile_s=t["compile_s"],
        swap_window_p50_us=_percentile(lat_swap, 50),
        swap_window_p99_us=_percentile(lat_swap, 99),
        steady_window=SERVE_WINDOW,
        steady_p50_us=_percentile(lat_steady, 50),
        steady_p99_us=_percentile(lat_steady, 99), **agree),
        _device_line(jax), flush=True)
    _check(not bad, f"serving: {len(bad)} rejects "
                    f"({sorted({b.reason for b in bad})}), "
                    f"{internal} of them internal errors")
    _check(in_flight == half,
           f"serving: {in_flight} of {half} backlog requests queued at "
           "the swap")
    _check(versions == sorted((e1.version, e2.version)),
           f"serving: versions {versions}, expected both {e1.version} "
           f"(the backlog drained while the successor warmed) and "
           f"{e2.version}")
    _check(steady_versions == [e2.version],
           f"serving: requests after the swap answered by {steady_versions}")
    _check(min(agree.values()) > MIN_NMI,
           f"serving: served labels disagree with the planted ones {agree}")


def kernel_oracles(jax, seed: int) -> None:
    """Each main-path kernel on the chip against its ``kernels.ref``
    oracle (or the dense product) on a small input. References run at
    ``highest`` matmul precision; the kernels' f32 dots run at the chip's
    default precision (one bf16 pass, 2**-9 relative per product), and a
    v5e measured at most 3.1e-3 relative error. The bounds, 1e-2
    relative and 99% equal labels (near-ties may flip), allow for that,
    not for a wrong index or scale, which moves entries by O(1)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import sparse as core_sparse
    from repro.core.spectral import normalize_bipartite
    from repro.data import planted_cocluster_matrix, to_bcoo
    from repro.kernels import ops, ref

    _require_tpu(jax)
    rng = np.random.default_rng(seed + 2)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)

    def rel(got, want) -> float:
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        return float(np.max(np.abs(got - want))
                     / max(np.max(np.abs(want)), 1e-30))

    def same(got, want) -> float:
        return float(np.mean(np.asarray(got) == np.asarray(want)))

    x, c, sig = f32(4000, 5), f32(16, 5), f32(16, 64)
    sig = sig / jnp.linalg.norm(sig, axis=1, keepdims=True)
    q = f32(300, 64)
    planted = planted_cocluster_matrix(rng, 1000, 600, k=4, density=0.05)
    tiled, _, _ = normalize_bipartite(core_sparse.to_tiled(to_bcoo(
        planted.matrix)))
    dense, _, _ = normalize_bipartite(jnp.asarray(planted.matrix))
    xs, ys = f32(600, 9), f32(1000, 9)
    got = dict(
        km_update=ops.kmeans_update(x, c), km_assign=ops.kmeans_assign(x, c),
        cos_assign=ops.cosine_assign(q, sig), cos_topk=ops.cosine_topk(q, sig, 4),
        spmm=ops.spmm_tiled(tiled, xs),
        spmm_t=ops.spmm_tiled(tiled, ys, transpose=True),
        ata=ops.spmm_ata(tiled, xs, with_gram=True))
    with jax.default_matmul_precision("highest"):
        ata_ref = dense.T @ (dense @ xs)
        want = dict(
            km_update=ref.kmeans_update_ref(x, c),
            km_assign=ref.kmeans_assign_ref(x, c),
            cos_assign=ref.cosine_assign_ref(q, sig),
            cos_topk=ref.cosine_topk_ref(q, sig, 4),
            spmm=dense @ xs, spmm_t=dense.T @ ys, ata=(ata_ref,
                                                      ata_ref.T @ ata_ref))
    checks = {
        "km_update_labels_equal": same(got["km_update"][0],
                                       want["km_update"][0]),
        "km_update_sums_rel": rel(got["km_update"][2], want["km_update"][2]),
        "km_assign_labels_equal": same(got["km_assign"][0],
                                       want["km_assign"][0]),
        "cos_assign_labels_equal": same(got["cos_assign"][0],
                                        want["cos_assign"][0]),
        "cos_topk_labels_equal": same(got["cos_topk"][0], want["cos_topk"][0]),
        "spmm_scaled_rel": rel(got["spmm"], want["spmm"]),
        "spmm_t_scaled_rel": rel(got["spmm_t"], want["spmm_t"]),
        "spmm_ata_gram_rel": max(rel(got["ata"][0], want["ata"][0]),
                                 rel(got["ata"][1], want["ata"][1])),
    }
    print("kernel_oracles", _fmt(**checks), _device_line(jax), flush=True)
    off = {k: v for k, v in checks.items()
           if (v < 0.99 if k.endswith("_equal") else v > 1e-2)}
    _check(not off, f"kernel_oracles: kernels disagree with their oracles: {off}")


def check_dispatch(kernels=MAIN_PATH_KERNELS) -> None:
    """Every main-path kernel dispatched, and only to the ``pallas`` tier."""
    from repro import obs

    snap = obs.get_registry().counter("kernel_dispatch").snapshot()
    series = snap.get("series", {})
    tiers: dict[str, set] = {}
    for key in series:
        kv = dict(part.split("=", 1) for part in key.split(","))
        tiers.setdefault(kv["op"], set()).add(kv["tier"])
    print("dispatch", _fmt(**{op: "/".join(sorted(tiers.get(op, ())))
                              for op in kernels}), flush=True)
    wrong = {op: sorted(tiers.get(op, ())) for op in kernels
             if tiers.get(op) != {"pallas"}}
    _check(not wrong, f"kernels off the pallas tier or never run: {wrong}")


def four_chips(jax, compiles, seed: int, n_rows: int = DENSE_ROWS,
               n_cols: int = DENSE_COLS, k: int = DENSE_K):
    """``distributed_lamc`` on a 2 x 2 mesh against ``lamc_cocluster`` on
    one device, same matrix, 4 x 4 block plan and seed."""
    import numpy as np

    from repro.core import LAMCConfig, cocluster_scores, lamc_cocluster, nmi
    from repro.core.distributed import distributed_lamc
    from repro.core.partition import PartitionPlan

    _require_tpu(jax)
    devs = jax.devices()
    _check(len(devs) >= 4, f"--chips 4 needs four devices, JAX sees {len(devs)}")
    a, rows, cols = plant_dense(jax, seed, n_rows, n_cols, k)
    cfg = LAMCConfig(n_row_clusters=k, n_col_clusters=k, seed=seed,
                     min_cocluster_rows=n_rows // k,
                     min_cocluster_cols=n_cols // k)
    plan = PartitionPlan(n_rows, n_cols, m=4, n=4, phi=n_rows // 4,
                         psi=n_cols // 4, t_p=1, seed=seed)
    mesh = jax.make_mesh((2, 2), ("data", "model"), devices=devs[:4])
    td, ts = {}, {}
    with _timed(compiles, td):
        out_d = distributed_lamc(mesh, a, cfg, plan)
        rd, cd = np.asarray(out_d.row_labels), np.asarray(out_d.col_labels)
    peaks = [_peak_bytes(d) for d in devs[:4]]
    with _timed(compiles, ts):
        out_s = lamc_cocluster(jax.device_put(a, devs[0]), cfg, plan=plan)
        rs, cs = np.asarray(out_s.row_labels), np.asarray(out_s.col_labels)
    sd = cocluster_scores(rd, cd, rows, cols)
    ss = cocluster_scores(rs, cs, rows, cols)
    same = float(np.mean(np.concatenate([rd == rs, cd == cs])))
    agree = 0.5 * (nmi(rd, rs) + nmi(cd, cs))
    print("four_chips", _fmt(
        rows=n_rows, cols=n_cols, k=k, mesh="2x2",
        plan=f"{plan.m}x{plan.n}/{plan.phi}x{plan.psi}/t_p={plan.t_p}",
        dist_wall_s=td["wall_s"], dist_compile_s=td["compile_s"],
        dist_nmi=sd["nmi"], dist_ari=sd["ari"],
        single_wall_s=ts["wall_s"], single_compile_s=ts["compile_s"],
        single_nmi=ss["nmi"], single_ari=ss["ari"],
        label_equal_frac=same, agreement_nmi=agree,
        peak_bytes_per_device="/".join(str(p) for p in peaks)),
        _device_line(jax), flush=True)
    _check(all(p and p > 0 for p in peaks[1:]),
           f"four_chips: devices 1-3 held nothing (peaks {peaks})")
    _check(sd["nmi"] > MIN_NMI and ss["nmi"] > MIN_NMI,
           f"four_chips: NMI {sd['nmi']:.4f} / {ss['nmi']:.4f} <= {MIN_NMI}")
    _check(agree > 0.99, f"four_chips: runs disagree (NMI {agree:.4f})")


def one_chip(jax, compiles, seed: int) -> None:
    from repro import streaming
    from repro.core import opcache

    a, rows, cols = plant_dense(jax, seed, DENSE_ROWS, DENSE_COLS, DENSE_K)
    truth = (rows, cols)
    fits = dense_fit(jax, compiles, a, truth, seed)
    # the 4 GiB matrix leaves the device while the sparse phase runs;
    # it is made again, bit for bit, from the same seed afterwards
    del a
    sparse_fit(jax, compiles, seed)
    # and the sparse phase's 2 GiB tiled operator leaves the pattern cache
    opcache.default_cache().clear()
    a, _, _ = plant_dense(jax, seed, DENSE_ROWS, DENSE_COLS, DENSE_K)
    batch = fits["defaults"]
    stream_model = streaming_fold(jax, compiles, a, batch, truth, seed)
    serving(jax, compiles, a, streaming.model_from_result(batch),
            stream_model, truth, seed)
    kernel_oracles(jax, seed)
    check_dispatch()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_FORCE_INTERPRET"):
        print("chip_smoke: REPRO_FORCE_INTERPRET is set; it forces the "
              "kernels off the chip", file=sys.stderr)
        return 2
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no LAMC sources at {src}; run it from a "
              "checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    try:
        _require_tpu(jax)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    from repro.runtime import compile_cache

    cache_dir = compile_cache.enable()
    compiles = _Compiles(jax)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(jax, compiles, args.seed)
        else:
            one_chip(jax, compiles, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print("total", _fmt(wall_s=time.perf_counter() - t0,
                        compile_s=compiles.seconds, cache_dir=cache_dir,
                        cache_hits=compiles.cache_hits,
                        cache_misses=compiles.cache_misses), flush=True)
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
