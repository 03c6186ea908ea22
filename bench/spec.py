"""What a run is: the cell, its configuration and traffic, found by name.

``BENCHMARK.json`` at the checkout root names each cell (``workloads``),
the configuration it runs (``bench/configs/<config>.json``) and its
traffic mix (``bench/traffic/<traffic>.json``). A per-layer metric
``<name>`` is read by ``bench/metrics/<name>.py``, which defines
``read(ctx) -> float | None``. Adding a cell, a configuration, a mix or
a metric adds files and entries; no file here names one.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict, dict]:
    """``(benchmark, workload, config, traffic)`` of the cell ``name``."""
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return bench, w, config, traffic


def metrics_of(bench: dict, workload: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metric entries that ``workload`` reports."""
    def applies(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if applies(m) and m["moves"] in moved]
    return e2e, layer


def metric_reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def apply_precision(config: dict) -> None:
    """Run JAX at the matmul precision the configuration states
    (``"matmul_precision"``, a value of ``jax_default_matmul_precision``).
    The program computes at JAX's default, which on the TPU multiplies
    float32 in one bfloat16 pass; a configuration that states float32
    results sets ``"highest"``."""
    import jax

    if "matmul_precision" in config:
        jax.config.update("jax_default_matmul_precision",
                          config["matmul_precision"])
