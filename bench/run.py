#!/usr/bin/env python3
"""Run one benchmark cell of LAMC on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, in order: refuse to run without enough TPU chips (or with
``REPRO_FORCE_INTERPRET`` set); turn on JAX's persistent compilation
cache; make the cell's data on the device from ``--seed``; warm up the
cell's own programs; measure for ``--seconds``; check what the window
produced against ``bench/reference.py``; print the numbers compared
beside their limits as the last lines of standard error, and one JSON
object as the last line of standard output.

``--trace 0`` reports the cell's end-to-end metrics with the program's
spans off. ``--trace 1`` is a run of its own: it turns the spans on,
takes a profiler trace of a steady part of the window, and reports the
per-layer metrics, the device's busy time and a breakdown.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_FOR_S = 3.0          # longest traced part of a window


class Refused(RuntimeError):
    """The run cannot measure here (no chip, wrong checkout)."""


class Compiles:
    """XLA compilations and persistent-cache lookups, from ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, int]:
        return self.count, self.cache_hits


class GcPauses:
    """Python's garbage collections and the time they held the host."""

    def __init__(self):
        import gc

        self.count, self.seconds, self.longest, self._t = 0, 0.0, 0.0, None
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            pause = time.perf_counter() - self._t
            self.count += 1
            self.seconds += pause
            self.longest = max(self.longest, pause)

    def snapshot(self) -> tuple[int, float, float]:
        return self.count, self.seconds, self.longest


def require_chips(jax, chips: int) -> list:
    if os.environ.get("REPRO_FORCE_INTERPRET"):
        raise Refused("REPRO_FORCE_INTERPRET is set; it forces the kernels "
                      "off the chip")
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def fmt(v) -> str:
    return repr(float(v)) if isinstance(v, float) else str(v)


class Tracer:
    """Start and stop a profiler trace around a steady part of the window."""

    def __init__(self, jax):
        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.annotation = None
        self.t_host = None

    def start(self):
        self.jax.profiler.start_trace(self.dir)
        self.annotation = self.jax.profiler.TraceAnnotation("bench.window")
        self.annotation.__enter__()
        self.t_host = time.perf_counter()

    def stop(self):
        self.annotation.__exit__(None, None, None)
        self.jax.profiler.stop_trace()

    def planes(self):
        import trace_reduce

        paths = sorted(Path(self.dir).rglob("*.xplane.pb"))
        planes = []
        for p in paths:
            planes += trace_reduce.load(str(p))
        return planes

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def obs_spans(obs, win, t_host) -> list[tuple[str, float, float]]:
    """The program's spans on the trace's clock, anchored at ``bench.window``."""
    if win is None or t_host is None:
        return []
    off = win[0] - t_host * 1e9
    return [("obs." + s.name, s.t_start * 1e9 + off, s.t_end * 1e9 + off)
            for s, _, _ in obs.current_trace().walk() if s.t_end is not None]


def run(args) -> dict:
    import jax

    devs = require_chips(jax, args.chips_needed)
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"no program sources at {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro import obs
    from repro.runtime import compile_cache

    import cells
    import spec
    import trace_reduce

    compile_cache.enable()
    # every program of the cell, small ones too, is found in the cache by
    # the next run, so set-up does the same work in every run but the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = Compiles(jax)
    bench, workload, config, traffic = args.cell
    spec.apply_precision(config)
    e2e, layer = spec.metrics_of(bench, workload["name"])
    obs.configure(enabled=bool(args.trace))
    cell = cells.make(config, traffic, args.seed, workload["chips"])
    cell.setup()
    tracer = Tracer(jax) if args.trace else None
    trace = None
    if tracer is not None:
        lead = args.seconds / 3.0
        trace = {"start": tracer.start, "stop": tracer.stop,
                 "from_s": lead, "for_s": min(TRACE_FOR_S, lead)}
        obs.reset_trace()
    gc_pauses = GcPauses()
    c0 = compiles.snapshot()
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    cell.window(args.seconds, trace)
    c1 = compiles.snapshot()
    gc_count, gc_s, gc_longest = gc_pauses.snapshot()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    cell.release()
    checks, failed = cell.check(config["limits"])
    result = {"attempted": cell.attempted(), "failed": failed}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    print(f"window compiles={c1[0] - c0[0]} cache_lookups={c1[1] - c0[1]} "
          f"setup_s={setup_s!r} gc={gc_count} gc_s={gc_s!r} "
          f"gc_longest_s={gc_longest!r}", file=sys.stderr, flush=True)
    if cell.kind == "serve":
        late = cell.sender_late_ms()
        print(f"sender late_ms p50={float(sorted(late)[len(late) // 2])!r} "
              f"max={float(max(late))!r} requests={len(late)}",
              file=sys.stderr, flush=True)
    else:
        each = cell.stats["each_fit_s"]
        slow = max(range(len(each)), key=each.__getitem__)
        print(f"fits={len(each)} each_fit_s min={min(each)!r} "
              f"max={max(each)!r} slowest=#{slow} at_s={sum(each[:slow])!r} "
              f"plan={cell.plan}", file=sys.stderr, flush=True)
        ret, wait = zip(*cell.phases[-len(each):])
        print(f"fit phases ms: to return median={1e3 * sorted(ret)[len(ret) // 2]!r} "
              f"waiting median={1e3 * sorted(wait)[len(wait) // 2]!r} "
              f"cpus={len(os.sched_getaffinity(0))}", file=sys.stderr, flush=True)
    metrics = {}
    if not args.trace:
        values = dict(cell.end_to_end(), setup_s=setup_s)
        for m in e2e:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    else:
        planes = tracer.planes()
        win = trace_reduce.window(planes)
        red = trace_reduce.reduce(
            planes, obs_spans(obs, win, tracer.t_host))
        tracer.close()
        spans: dict[str, list[float]] = {}
        for s, _, _ in obs.current_trace().walk():
            if s.t_end is not None:
                spans.setdefault(s.name, []).append(s.t_end - s.t_start)
        ctx = {"kind": cell.kind, "trace": red, "device_kind": devs[0].device_kind,
               "spans": spans, "stats": cell.stats,
               "work": cell.work() if cell.kind == "fit" else None,
               "registry": getattr(cell, "registry_diff", None)}
        for m in layer:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if red is not None:
            device.update(busy_s=red["mean_busy_s"], window_s=red["window_s"])
            result["breakdown"] = red["breakdown"]
            print(f"trace busiest={red['busiest']} idle_share="
                  f"{red['idle_share']!r} busy_s={red['busy_s']} "
                  f"collective_s={red['collective_s']}", file=sys.stderr,
                  flush=True)
    limits = config["limits"]
    correct = all(checks[n] <= limits[n] for n in checks) and failed == 0
    result.update(correct=correct, metrics=metrics, device=device)
    result["checks"] = {n: {"value": checks[n], "limit": limits[n]}
                        for n in checks}
    for n in checks:
        print(f"check {n}={fmt(checks[n])} limit={fmt(limits[n])}",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import spec

    try:
        args.cell = spec.cell(args.workload)
        args.chips_needed = args.cell[1]["chips"]
        result = run(args)
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 1
    order = ("correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks")
    line = {k: result[k] for k in order if k in result}
    print(json.dumps(line, allow_nan=True), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
