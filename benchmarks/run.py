"""Benchmark harness entry point: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--quick]``

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract) and, at
exit, writes machine-readable ``{name: µs/call}`` trajectory files so
per-PR perf trajectories can be diffed without parsing stdout. Each file
owns one key namespace — ``sparse_*`` rows go (only) to
``BENCH_sparse.json``, ``stream_*``/``serve_*`` rows to
``BENCH_stream.json``, ``roofline_*`` rows to ``BENCH_roofline.json``,
and every other row to ``BENCH_atoms.json`` — and stale foreign keys are
scrubbed on rewrite. Sections (described in benchmarks/README.md):
  table2_*      running-time reproduction (paper Table II)
  table3_*      NMI/ARI reproduction (paper Table III)
  prob_bound_*  Theorem-1 bound tightness (paper Eq. 3)
  roofline_*    achieved-vs-peak FLOPs/bytes for the repo's hot kernels
                (-> ``BENCH_roofline.json``)
  kernel_*      Pallas kernel micro-benches (interpret-mode correctness +
                jnp-path wall time; TPU wall time requires hardware)
  sparse_*      sparse atom phase: routed SpMM backends vs the
                densify-then-run baseline (-> ``BENCH_sparse.json``)
  stream_*      out-of-core chunked-fit throughput + assignment QPS
                (-> ``BENCH_stream.json``)
  serve_load_*  assignment-service load tests: traffic mixes through the
                admission queue + coalescer, swap-under-load
                (-> ``BENCH_stream.json``)

``--list`` prints the available section names and exits.
"""

from __future__ import annotations

import argparse
import time


def _kernel_micro(report):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import kmeans as km
    from repro.models.attention import chunked_causal_attention

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4096, 64)).astype(np.float32))
    key = jax.random.key(0)
    f = jax.jit(lambda: km.kmeans(key, x, 16, n_iter=10).labels)
    f().block_until_ready()
    t0 = time.perf_counter()
    for _ in range(3):
        f().block_until_ready()
    report(f"kernel_kmeans_4096x64_k16,{(time.perf_counter()-t0)/3*1e6:.0f},jnp_path")

    q = jnp.asarray(rng.normal(size=(1, 8, 1024, 64)).astype(np.float32))
    g = jax.jit(lambda: chunked_causal_attention(q, q, q, chunk_size=256))
    g().block_until_ready()
    t0 = time.perf_counter()
    for _ in range(3):
        g().block_until_ready()
    report(f"kernel_chunked_attn_1k,{(time.perf_counter()-t0)/3*1e6:.0f},jnp_path")

    _kernel_kmeans_fused(report)


def _kernel_kmeans_fused(report):
    """One Lloyd iteration: jnp 3-pass update vs fused one-pass kernel.

    On TPU the fused path reads ``x`` from HBM once instead of three times
    and never materializes the ``(P, K)`` one-hot (DESIGN.md §4). On CPU
    the kernel runs in Pallas interpret mode, so its wall time here is a
    correctness proxy only — the jnp row is the meaningful CPU number.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    rng = np.random.default_rng(1)
    p, d, k = 4096, 64, 16
    x = jnp.asarray(rng.normal(size=(p, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))

    f_jnp = jax.jit(lambda: kref.kmeans_update_ref(x, c))
    f_fused = jax.jit(lambda: kops.kmeans_update(x, c))
    for name, fn in (("kernel_kmeans_update_jnp", f_jnp),
                     ("kernel_kmeans_update_fused", f_fused)):
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(fn())
        backend = "jnp_3pass" if name.endswith("jnp") else (
            "fused_1pass" if jax.default_backend() == "tpu"
            else "fused_1pass_interpret")
        report(f"{name},{(time.perf_counter()-t0)/3*1e6:.0f},{backend}")


SECTIONS = ("prob", "roofline", "kernel", "sparse", "stream", "serve",
            "table3", "table2")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller table2/3 problem sizes")
    ap.add_argument("--only", default=None,
                    help="run a single section: " + "|".join(SECTIONS))
    ap.add_argument("--list", action="store_true",
                    help="print available section names and exit")
    args = ap.parse_args(argv)

    if args.list:
        for name in SECTIONS:
            print(name)
        return

    from repro.runtime import compile_cache

    compile_cache.enable()

    rows: dict[str, float] = {}

    def report(line: str) -> None:
        print(line, flush=True)
        # rows follow the "name,us_per_call,derived" contract; keep every
        # one whose second field parses as a number
        parts = line.split(",")
        if len(parts) >= 2:
            try:
                rows[parts[0]] = float(parts[1])
            except ValueError:
                pass

    sections = args.only.split(",") if args.only else list(SECTIONS)
    unknown = [s for s in sections if s not in SECTIONS]
    if unknown:
        ap.error(f"unknown section(s) {unknown}; available: {', '.join(SECTIONS)}")

    if "prob" in sections:
        from benchmarks import bench_probability
        bench_probability.run(report)
    if "roofline" in sections:
        from benchmarks import bench_roofline
        bench_roofline.run(report, quick=args.quick)
    if "kernel" in sections:
        _kernel_micro(report)
    if "sparse" in sections:
        from benchmarks import bench_sparse
        bench_sparse.run(report, quick=args.quick)
    if "stream" in sections:
        from benchmarks import bench_stream
        bench_stream.run(report, quick=args.quick)
    if "serve" in sections:
        from benchmarks import bench_serve
        bench_serve.run(report, quick=args.quick)
    if "table3" in sections:
        from benchmarks import bench_table3
        bench_table3.run(report, rcv1_scale=0.05 if args.quick else 0.2)
    if "table2" in sections:
        from benchmarks import bench_table2
        bench_table2.run(report)

    # merge into any existing file so `--only` runs refresh their section
    # without clobbering the rest of the trajectory record. Each file owns
    # one key namespace: sparse_* -> BENCH_sparse.json, stream_*/serve_*
    # -> BENCH_stream.json, everything else -> BENCH_atoms.json. Each
    # section writes only its own keys to its own file, and stale foreign
    # keys (left by older, differently-routed writers) are scrubbed on
    # rewrite.
    from repro.benchio import merge_rows

    def _merge_write(path: str, new_rows: dict, **scrub) -> None:
        total = merge_rows(path, new_rows, **scrub)
        print(f"wrote {path} ({len(new_rows)} new / {total} total entries)",
              flush=True)

    sparse_rows = {k: v for k, v in rows.items() if k.startswith("sparse_")}
    stream_rows = {k: v for k, v in rows.items()
                   if k.startswith(("stream_", "serve_"))}
    roofline_rows = {k: v for k, v in rows.items()
                     if k.startswith("roofline_")}
    atom_rows = {k: v for k, v in rows.items()
                 if k not in sparse_rows and k not in stream_rows
                 and k not in roofline_rows}
    if atom_rows:
        _merge_write("BENCH_atoms.json", atom_rows,
                     foreign_prefixes=("sparse_", "stream_", "serve_",
                                       "roofline_"))
    if sparse_rows:
        # replace_prefixes: the sparse section regenerates its whole row
        # family every run, so renamed/retired rows can't accrete
        _merge_write("BENCH_sparse.json", sparse_rows,
                     own_prefixes=("sparse_",),
                     replace_prefixes=("sparse_",))
    if stream_rows:
        # the serve-load family regenerates whole when its section ran:
        # replace it so renamed/retired mixes cannot accrete
        _merge_write("BENCH_stream.json", stream_rows,
                     own_prefixes=("stream_", "serve_"),
                     replace_prefixes=(("serve_load_",)
                                       if "serve" in sections else ()))
    if roofline_rows:
        _merge_write("BENCH_roofline.json", roofline_rows,
                     own_prefixes=("roofline_",),
                     replace_prefixes=("roofline_",))


if __name__ == "__main__":
    main()
