#!/usr/bin/env python3
"""Readings of the numbers that decide ``correct``: the program's and the
control's, on several seeds, at a cell's own size.

    python bench/control.py --workload dense_fit --seconds 2 --seeds 11 12 13

For each seed, in one process: the cell's set-up and a short window at
its own load, as ``run.py`` does; then every number, for the program, for
the control (``reference.py`` in bfloat16 put in the program's place)
and, for a fit, for the atom's faults (the reference's atom with no
subspace iterations, and with half of the columns left out). Prints one
JSON line per seed. The limits in a configuration file are set between
the largest reading of sound runs and the smallest reading of the
control or a fault (``PERF.md`` gives both). The benchmark's own runs
never run the control.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))


#: faults of the atom, read with the reference in the program's place
ATOM_FAULTS = {"no_iterations": {"iters": 0}, "half_columns": {"keep_cols": 0.5}}


def _worst(a: dict, b: dict) -> dict:
    return {k: max(v, a.get(k, -math.inf)) for k, v in b.items()}


def readings(cell, limits: dict) -> dict:
    """After the cell's window: every number of the program's answers, of
    the control's and, for a fit, of each of the atom's faults."""
    import cells
    import jax.numpy as jnp

    cell.release()
    if cell.kind == "serve":
        program, failed = cell.check(limits)
        control, _ = cell.check(limits, control=True)
        return {"program": program, "failed": failed, "control": control}
    truth = (cell.planted.row_labels, cell.planted.col_labels)
    t0 = time.perf_counter()
    atom = cell.atom_reference()
    atom_s = time.perf_counter() - t0
    program = {}
    for ans, _ in cell.distinct_answers():
        program = _worst(program, cells.fit_numbers(
            ans, cell.slivers(ans), truth, cell.q, atom))
    ans = cell.distinct_answers()[0][0]
    slivers = cell.slivers(ans)

    def numbers(control=False, **fault):
        sub = cells.substitute_fit(ans, slivers, cell.atom_reference(**fault),
                                   control=control)
        return cells.fit_numbers(sub, slivers, truth, cell.q, atom)

    out = {"program": program, "control": numbers(True, dtype=jnp.bfloat16),
           "atom_s": atom_s}
    for name, fault in ATOM_FAULTS.items():
        out[name] = numbers(**fault)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    import run
    import spec

    bench, w, config, traffic = spec.cell(args.workload)
    try:
        run.require_chips(jax, w["chips"])
    except run.Refused as e:
        print(f"control: refused: {e}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(BENCH.parent / "src"))
    from repro.runtime import compile_cache

    import cells

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    spec.apply_precision(config)
    for seed in args.seeds:
        cell = cells.make(config, traffic, seed, w["chips"])
        cell.setup()
        cell.window(args.seconds)
        out = readings(cell, config["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
