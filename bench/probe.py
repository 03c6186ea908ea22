#!/usr/bin/env python3
"""Draw a sparse configuration's planted data and read what sizes it.

    python bench/probe.py --config bench/configs/<name>.json --seeds 11 12 \\
        --k 4 --out chiprun_out/probe.jsonl

``--config`` is a configuration file or the same JSON inline. One
process, one JSON line each (stdout, and ``--out`` if given):

- ``draw``, per ``--seed``: the planted matrix made as a cell's set-up
  makes it (``data.make``), its seconds (the first seed's include every
  compile) and the device's peak bytes after it; the support's summary
  (``data.support_summary``), ``nnz`` against ``density * rows * cols``,
  the ten fullest columns, quantiles of the row lengths, a hash of the
  indices and one of the values, and the bytes of the operand each SpMM
  route would build (``described.operand_bytes``).
- ``reference``, per ``--seed`` and ``--k``: the matrix planted again with
  ``k = d = K`` (same support), ``reference.scc`` on it with its seconds,
  its singular values, the sizes of its clusters, its NMI against the
  planted truth and the peak bytes; then the answer check's path on the
  reference's own labels (``reference.anchor_slivers`` over ``--q``
  anchors drawn from the seed, ``reference.signatures`` of both sides)
  with its seconds.

Every line names the device it ran on. A time on another platform than
``tpu`` is a time of that platform, never of the chip.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

QUANTILES = (0.5, 0.9, 0.99, 0.999, 1.0)
ROUTES = ("dual_ell", "tiled")


def _config(text: str) -> dict:
    if text.lstrip().startswith("{"):
        return json.loads(text)
    return json.loads(Path(text).read_text())


def _sha(a) -> str:
    import numpy as np

    buf = np.ascontiguousarray(np.asarray(a))
    return hashlib.sha256(buf.tobytes()).hexdigest()


def _peak(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def draw(config: dict, seed: int, device) -> dict:
    import numpy as np

    import data
    import described

    t0 = time.perf_counter()
    planted = data.make(config, seed)
    seconds = time.perf_counter() - t0
    a = planted.a
    rows, cols, _ = data.support_counts(a)
    summary = data.support_summary(a)
    shape = (config["rows"], config["cols"])
    return {"probe": "draw", "seed": seed, "seconds": seconds,
            "peak_bytes": _peak(device), **summary,
            "nnz_share": summary["nnz"] / (config["density"] * shape[0]
                                           * shape[1]),
            "top_cols": np.sort(cols)[::-1][:10].tolist(),
            "row_quantiles": dict(zip(map(str, QUANTILES),
                                      np.quantile(rows, QUANTILES).tolist())),
            "empty_cols": int(np.sum(cols == 0)),
            "support_sha256": _sha(a.indices), "values_sha256": _sha(a.data),
            "operand_bytes": {r: described.operand_bytes(r, shape, summary)
                              for r in ROUTES}}


def reference_run(config: dict, seed: int, k: int, q: int, device) -> dict:
    import numpy as np

    import data
    import reference

    planted = data.make(dict(config, k=k, d=k), seed)
    a = planted.a
    t0 = time.perf_counter()
    atom = reference.scc(a, k, k, [seed, 3])
    scc_s = time.perf_counter() - t0
    peak = _peak(device)
    m, n = a.shape
    rng = np.random.default_rng([seed, 1])
    t0 = time.perf_counter()
    anchor_rows = np.sort(rng.choice(m, min(q, m), replace=False))
    anchor_cols = np.sort(rng.choice(n, min(q, n), replace=False))
    rf, cf = reference.anchor_slivers(a, anchor_rows, anchor_cols)
    reference.signatures(rf, atom["row_labels"], k)
    reference.signatures(cf, atom["col_labels"], k)
    check_s = time.perf_counter() - t0
    nmi_rows = reference.nmi(planted.row_labels, atom["row_labels"])
    nmi_cols = reference.nmi(planted.col_labels, atom["col_labels"])
    return {"probe": "reference", "seed": seed, "k": k, "scc_s": scc_s,
            "peak_bytes": peak, "singular": atom["singular"].tolist(),
            "row_sizes": np.bincount(atom["row_labels"], minlength=k).tolist(),
            "col_sizes": np.bincount(atom["col_labels"], minlength=k).tolist(),
            "nmi_rows": nmi_rows, "nmi_cols": nmi_cols, "check_s": check_s,
            "signal": config["signal"], "noise": config["noise"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--k", type=int, nargs="*", default=[])
    ap.add_argument("--q", type=int, default=64)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    import spec

    config = _config(args.config)
    spec.apply_precision(config)
    device = jax.devices()[0]
    where = {"platform": device.platform, "kind": device.device_kind}
    runs = [lambda s=s: draw(config, s, device) for s in args.seeds]
    runs += [lambda s=s, k=k: reference_run(config, s, k, args.q, device)
             for s in args.seeds for k in args.k]
    out = open(args.out, "a") if args.out else None
    try:
        for run in runs:
            text = json.dumps({**run(), "device": where})
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
