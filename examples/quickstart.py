"""Quickstart: co-cluster a planted matrix with LAMC, persist the fitted
model, and assign new rows against the restored artifact.

    PYTHONPATH=src python examples/quickstart.py

Walks the full production loop: batch fit -> score -> save the
CoclusterModel checkpoint -> load it back -> out-of-sample assign_rows —
then prints the fit's phase-span trace (repro.obs, DESIGN.md §14) so the
wall-clock breakdown of what just ran is part of the demo.
"""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, streaming
from repro.core import LAMCConfig, lamc_cocluster, cocluster_scores
from repro.core.baselines import scc_full
from repro.core.metrics import nmi
from repro.data import planted_cocluster_matrix
from repro.runtime import compile_cache


def main():
    compile_cache.enable()
    obs.configure(enabled=True)  # span-trace the whole loop (DESIGN.md §14)
    obs.reset_trace()
    rng = np.random.default_rng(0)
    # 1400 rows planted; fit on the first 1200, hold out 200 for serving
    data = planted_cocluster_matrix(rng, 1400, 900, k=5, d=5,
                                    signal=4.0, noise=0.7)
    a = jnp.asarray(data.matrix[:1200])
    heldout = jnp.asarray(data.matrix[1200:])

    # the probabilistic model picks (m, n, T_p) for a 95% detection floor
    cfg = LAMCConfig(
        n_row_clusters=5, n_col_clusters=5,
        min_cocluster_rows=240,   # the smallest co-cluster we care about
        min_cocluster_cols=180,
        p_thresh=0.95,
        workers=4,                # pretend 4 parallel units; plan adapts
    )
    out = lamc_cocluster(a, cfg)
    plan = out.plan
    print(f"plan: {plan.m}x{plan.n} blocks of {plan.phi}x{plan.psi}, "
          f"T_p={plan.t_p} resamples, detection>= {plan.detection_p:.3f}")

    s = cocluster_scores(np.asarray(out.row_labels), np.asarray(out.col_labels),
                         data.row_labels[:1200], data.col_labels)
    print(f"LAMC     : NMI={s['nmi']:.3f} ARI={s['ari']:.3f}")

    base = scc_full(jax.random.key(0), a, 5)
    sb = cocluster_scores(np.asarray(base.row_labels), np.asarray(base.col_labels),
                          data.row_labels[:1200], data.col_labels)
    print(f"full SCC : NMI={sb['nmi']:.3f} ARI={sb['ari']:.3f}")

    # fit -> save -> load -> assign: the serving loop (DESIGN.md §10)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        model = streaming.model_from_result(out)
        streaming.save_model(ckpt_dir, model, cfg=cfg, plan=plan)
        restored, meta = streaming.load_model(ckpt_dir)
        print(f"saved + restored model ({meta['kind']}, "
              f"{restored.n_rows}x{restored.n_cols})")
        res = streaming.assign_rows(restored, heldout)
        agree = nmi(np.asarray(res.labels), data.row_labels[1200:])
        print(f"held-out assign_rows: NMI vs planted truth = {agree:.3f}, "
              f"mean score {float(np.mean(np.asarray(res.score))):.3f}")

    # where the time went: the fenced span tree of everything above
    print("\nfit trace (python -m repro.obs renders saved traces):")
    print(obs.render_trace())


if __name__ == "__main__":
    main()
