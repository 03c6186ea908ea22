"""Roofline rows for the repo's own hot kernels (-> BENCH_roofline.json).

Per kernel (``spmm_tiled``, ``spmm_ata``, ``kmeans_update``) at its bench
shape: measured wall time next to the *analytic* FLOPs and minimum HBM
bytes of the launch, reduced to achieved FLOP/s and bytes/s against the
chip's published peaks (``launch/roofline.HW``, keyed by device kind).
This states every kernel win against the hardware ceiling instead of
the previous run: the
``us`` column tracks regressions, the ``pk`` fractions say how much
headroom is even left to chase, and the ``ai`` (arithmetic intensity,
FLOPs/byte) column says which wall — 240 FLOP/B is the v5e ridge — the
kernel lives under.

Off-TPU the kernels dispatch to their jnp tile-reference tier, so the
achieved numbers are the CPU production path's and the peak fractions
read ``not_measured``; the analytic FLOPs/bytes columns are
backend-independent. Row contract (benchmarks/run.py):
``roofline_<kernel>,us_per_call,derived``.
"""

from __future__ import annotations

import time

#: reps per timed row (after one warmup call).
_REPS = 3


def _time(fn, *args) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(_REPS):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / _REPS


def _row(report, name: str, secs: float, flops: float, bytes_: float,
         extra: str) -> None:
    import jax

    from repro.launch.roofline import peaks

    ach_flops = flops / secs
    ach_bw = bytes_ / secs
    ai = flops / bytes_ if bytes_ else 0.0
    if jax.default_backend() == "tpu":
        hw = peaks(jax.devices()[0].device_kind)
        pk = (f"pk_flops={ach_flops / hw['flops_bf16']:.2e} "
              f"pk_hbm={ach_bw / hw['hbm_bw']:.2e}")
    else:
        # a CPU time divided by a chip's peak is no roofline share
        pk = "pk=not_measured"
    report(
        f"roofline_{name},{secs * 1e6:.0f},"
        f"flops={flops:.3g} bytes={bytes_:.3g} ai={ai:.1f} "
        f"ach_gflops={ach_flops / 1e9:.1f} ach_gbps={ach_bw / 1e9:.1f} "
        f"{pk} {extra}")


def _bcoo(rng, m: int, n: int, d: float):
    import jax.numpy as jnp
    from jax.experimental import sparse as jsparse

    dense = (rng.random((m, n)) < d) * rng.standard_normal((m, n))
    return jsparse.BCOO.fromdense(jnp.asarray(dense, jnp.float32))


def run(report=print, quick: bool = False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import sparse as core_sparse
    from repro.kernels import ops as kops

    backend = jax.default_backend()
    rng = np.random.default_rng(0)
    m, n = (2048, 1024) if quick else (4096, 2048)
    r = 9
    d = 0.2

    a = _bcoo(rng, m, n, d)
    tiled = core_sparse.to_tiled(a)
    g, bm, bk = tiled.blocks.shape
    n_tr, n_tc = tiled.n_tiles
    # RHS width as launched: the Pallas tiers pad the skinny sketch to one
    # bn=128 column stripe; the jnp tile reference contracts the real r
    wn = r if kops._tiled_backend() == "jnp" else 128

    # -- spmm_tiled: one (bm, bk) @ (bk, wn) MXU contraction per payload.
    # HBM floor: payload stack + one rhs stripe + one output stripe.
    x = jnp.asarray(rng.standard_normal((n, r)).astype(np.float32))
    f = jax.jit(lambda b: kops.spmm_tiled(tiled, b))
    secs = _time(f, x)
    flops = 2.0 * g * bm * bk * wn
    bytes_ = 4.0 * (g * bm * bk + n_tc * bk * wn + n_tr * bm * wn)
    _row(report, "spmm_tiled", secs, flops, bytes_,
         f"backend={backend} g={g} grid={n_tr}x{n_tc}")

    # -- spmm_ata: both products of A.T @ (A @ x) in one launch; the Y
    # intermediate stays in VMEM, so HBM traffic is the payloads (read
    # once per phase) + x + the output stripe — Y never counts.
    f = jax.jit(lambda b: kops.spmm_ata(tiled, b))
    secs = _time(f, x)
    flops = 4.0 * g * bm * bk * wn
    bytes_ = 4.0 * (2 * g * bm * bk + 2 * n_tc * bk * wn)
    _row(report, "spmm_ata", secs, flops, bytes_,
         f"backend={backend} g={g} fused_y_vmem={n_tr * bm * wn * 4}")

    # -- kmeans_update: fused one-pass Lloyd iteration (DESIGN.md §4).
    # FLOPs: the (P, K) distance matrix via the 2xy matmul term; HBM
    # floor: x read once (the point of the fusion) + centroids + outputs.
    p, dim, k = (2048, 64, 16) if quick else (4096, 64, 16)
    xs = jnp.asarray(rng.standard_normal((p, dim)).astype(np.float32))
    cs = jnp.asarray(rng.standard_normal((k, dim)).astype(np.float32))
    f = jax.jit(lambda xx, cc: kops.kmeans_update(xx, cc))
    secs = _time(f, xs, cs)
    flops = 2.0 * p * k * dim + 2.0 * p * dim  # distances + sums scatter
    bytes_ = 4.0 * (p * dim + k * dim + p * 2 + k * dim + k)
    _row(report, "kmeans_update", secs, flops, bytes_,
         f"backend={backend} p={p} d={dim} k={k}")


if __name__ == "__main__":
    run()
