"""End-to-end LAMC pipeline behaviour (replaces the placeholder system test)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LAMCConfig, lamc_cocluster
from repro.core.baselines import nmtf_full, scc_full
from repro.core.metrics import cocluster_scores
from repro.core.partition import PartitionPlan
from repro.data import planted_cocluster_matrix


@pytest.fixture(scope="module")
def planted():
    rng = np.random.default_rng(0)
    return planted_cocluster_matrix(rng, 600, 500, k=5, d=5, signal=4.0, noise=0.6)


class TestLAMCEndToEnd:
    def test_scc_atom_quality_close_to_full(self, planted):
        a = jnp.asarray(planted.matrix)
        cfg = LAMCConfig(n_row_clusters=5, n_col_clusters=5,
                         min_cocluster_rows=120, min_cocluster_cols=100)
        plan = PartitionPlan(600, 500, m=2, n=2, phi=300, psi=250, t_p=3, seed=0)
        out = lamc_cocluster(a, cfg, plan=plan)
        s_lamc = cocluster_scores(np.array(out.row_labels), np.array(out.col_labels),
                                  planted.row_labels, planted.col_labels)
        base = scc_full(jax.random.key(0), a, 5)
        s_full = cocluster_scores(np.array(base.row_labels), np.array(base.col_labels),
                                  planted.row_labels, planted.col_labels)
        # Table III behaviour: partitioned quality within a modest gap of full
        assert s_lamc["nmi"] > s_full["nmi"] - 0.2, (s_lamc, s_full)
        assert s_lamc["nmi"] > 0.5

    def test_nmtf_atom_runs(self, planted):
        a = jnp.asarray(planted.matrix)
        cfg = LAMCConfig(n_row_clusters=5, n_col_clusters=5, atom="nmtf",
                         min_cocluster_rows=120, min_cocluster_cols=100)
        plan = PartitionPlan(600, 500, m=2, n=2, phi=300, psi=250, t_p=2, seed=0)
        out = lamc_cocluster(a, cfg, plan=plan)
        s = cocluster_scores(np.array(out.row_labels), np.array(out.col_labels),
                             planted.row_labels, planted.col_labels)
        assert s["nmi"] > 0.4, s

    def test_auto_plan_respects_threshold(self, planted):
        a = jnp.asarray(planted.matrix)
        cfg = LAMCConfig(n_row_clusters=5, n_col_clusters=5,
                         min_cocluster_rows=120, min_cocluster_cols=100,
                         p_thresh=0.9, workers=4)
        out = lamc_cocluster(a, cfg)
        assert out.plan.detection_p >= 0.9

    def test_deterministic_given_seed(self, planted):
        a = jnp.asarray(planted.matrix)
        cfg = LAMCConfig(n_row_clusters=5, n_col_clusters=5,
                         min_cocluster_rows=120, min_cocluster_cols=100)
        plan = PartitionPlan(600, 500, m=2, n=2, phi=300, psi=250, t_p=2, seed=7)
        out1 = lamc_cocluster(a, cfg, plan=plan)
        out2 = lamc_cocluster(a, cfg, plan=plan)
        np.testing.assert_array_equal(np.array(out1.row_labels), np.array(out2.row_labels))
        np.testing.assert_array_equal(np.array(out1.col_labels), np.array(out2.col_labels))

    def test_fused_pallas_path_matches_jnp(self, planted):
        """assign_impl='pallas' (fused Lloyd kernel) must reproduce the jnp
        path's end-to-end labels — identical up to cluster permutation."""
        from repro.core.metrics import nmi

        a = jnp.asarray(planted.matrix)
        plan = PartitionPlan(600, 500, m=2, n=2, phi=300, psi=250, t_p=2, seed=0)
        base = dict(n_row_clusters=5, n_col_clusters=5,
                    min_cocluster_rows=120, min_cocluster_cols=100)
        out_j = lamc_cocluster(a, LAMCConfig(**base, assign_impl="jnp"), plan=plan)
        out_p = lamc_cocluster(a, LAMCConfig(**base, assign_impl="pallas"), plan=plan)
        assert nmi(np.array(out_j.row_labels), np.array(out_p.row_labels)) > 0.999
        assert nmi(np.array(out_j.col_labels), np.array(out_p.col_labels)) > 0.999

    def test_cholesky_qr_path_quality(self, planted):
        """qr_method='cholesky' (Gram-based batched subspace iteration) must
        keep consensus quality on par with the LAPACK-QR path."""
        a = jnp.asarray(planted.matrix)
        plan = PartitionPlan(600, 500, m=2, n=2, phi=300, psi=250, t_p=3, seed=0)
        base = dict(n_row_clusters=5, n_col_clusters=5,
                    min_cocluster_rows=120, min_cocluster_cols=100)
        out_q = lamc_cocluster(a, LAMCConfig(**base, qr_method="qr"), plan=plan)
        out_c = lamc_cocluster(a, LAMCConfig(**base, qr_method="cholesky"), plan=plan)
        s_q = cocluster_scores(np.array(out_q.row_labels), np.array(out_q.col_labels),
                               planted.row_labels, planted.col_labels)
        s_c = cocluster_scores(np.array(out_c.row_labels), np.array(out_c.col_labels),
                               planted.row_labels, planted.col_labels)
        assert s_c["nmi"] > s_q["nmi"] - 0.1, (s_c, s_q)

    def test_labels_in_range_no_nans(self, planted):
        a = jnp.asarray(planted.matrix)
        cfg = LAMCConfig(n_row_clusters=5, n_col_clusters=5,
                         min_cocluster_rows=120, min_cocluster_cols=100)
        plan = PartitionPlan(600, 500, m=2, n=2, phi=300, psi=250, t_p=2, seed=0)
        out = lamc_cocluster(a, cfg, plan=plan)
        rl = np.array(out.row_labels)
        cl = np.array(out.col_labels)
        assert rl.min() >= 0 and rl.max() < 5
        assert cl.min() >= 0 and cl.max() < 5
        assert np.all(np.isfinite(np.array(out.row_votes)))


class TestBaselines:
    def test_nmtf_full_quality(self, planted):
        a = jnp.asarray(planted.matrix)
        res = nmtf_full(jax.random.key(0), a, 5, n_iter=64)
        s = cocluster_scores(np.array(res.row_labels), np.array(res.col_labels),
                             planted.row_labels, planted.col_labels)
        assert s["nmi"] > 0.5, s

    def test_scc_full_quality(self, planted):
        a = jnp.asarray(planted.matrix)
        res = scc_full(jax.random.key(0), a, 5)
        s = cocluster_scores(np.array(res.row_labels), np.array(res.col_labels),
                             planted.row_labels, planted.col_labels)
        assert s["nmi"] > 0.6, s


def _tiny(rows=64, cols=48):
    a = jnp.asarray(np.random.default_rng(0).standard_normal((rows, cols)),
                    jnp.float32)
    cfg = LAMCConfig(n_row_clusters=2, n_col_clusters=2, svd_iters=2,
                     kmeans_iters=2, merge_kmeans_iters=2, merge_restarts=1,
                     signature_dim=8)
    return a, cfg


class TestWholeMatrixExtraction:
    """A plan whose one block is the whole matrix extracts nothing
    (``partition.whole_matrix``); every other plan gathers as before."""

    @pytest.mark.parametrize("m,n,phi,psi,gathers", [
        (1, 1, 64, 48, False),    # whole matrix
        (1, 1, 48, 48, True),     # 1 x 1, rows subsampled
        (2, 2, 32, 24, True),     # multi-block
    ])
    def test_extract_phase_gathers_only_when_partitioning(
            self, m, n, phi, psi, gathers):
        import re

        from jax.experimental.compilation_cache import compilation_cache
        from repro.core.lamc import _lamc_jit
        a, cfg = _tiny()
        plan = PartitionPlan(64, 48, m=m, n=n, phi=phi, psi=psi, t_p=1,
                             seed=0)
        # the persistent cache's key leaves op_name out (tests/test_obs.py)
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            text = _lamc_jit.lower(a, cfg, plan).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()
        ops = {re.search(r"\s(\S+?)\(", line.split(" = ", 1)[1]).group(1)
               for line in text.splitlines()
               if "/extract/" in line and " = " in line}
        assert ("gather" in ops) == gathers, ops
        if not gathers:
            assert not ops & {"sort", "scatter", "transpose", "copy"}, ops

    @pytest.mark.parametrize("fmt,phi,psi,impl,kind", [
        ("dense", 64, 48, "auto", "whole"),
        ("dense", 48, 48, "auto", "gather"),
        ("dense", 32, 24, "auto", "gather"),
        ("bcoo", 64, 48, "dense", "whole"),
        ("bcoo", 64, 48, "dual_ell", "whole"),
        ("bcoo", 32, 24, "auto", "scatter"),
    ])
    def test_root_span_names_the_extraction(self, fmt, phi, psi, impl, kind):
        import dataclasses

        from repro import obs
        from repro.data import to_bcoo
        a, cfg = _tiny()
        cfg = dataclasses.replace(cfg, input_format=fmt, spmm_impl=impl)
        if fmt == "bcoo":    # sparse, so a pinned sparse route is kept
            keep = np.random.default_rng(1).random(a.shape) < 0.3
            a = to_bcoo(np.where(keep, np.asarray(a), 0.0))
        plan = PartitionPlan(64, 48, m=64 // phi, n=48 // psi, phi=phi,
                             psi=psi, t_p=1, seed=0)
        was = obs.enabled()
        obs.configure(enabled=True)
        tr = obs.reset_trace()
        try:
            out = lamc_cocluster(a, cfg, plan=plan)
            root = tr.find("lamc")[0]
        finally:
            obs.configure(enabled=was)
            obs.reset_trace()
        assert root.attrs["extract"] == kind
        if impl == "dual_ell":
            assert out.plan.spmm_route == "dual_ell"

    @pytest.mark.parametrize("data_seed", [0, 1, 2])
    def test_whole_matrix_fit_is_the_reference_atom(self, data_seed):
        """With one whole-matrix block and T_p = 1, the fit is the plain
        SCC atom on ``A`` under the block's key (``baselines.scc_full``):
        the same labels, so the same recovery of the planted truth."""
        data = planted_cocluster_matrix(np.random.default_rng(data_seed),
                                        240, 200, k=4, d=4, signal=8.0,
                                        noise=0.2)
        a = jnp.asarray(data.matrix)
        plan = PartitionPlan(240, 200, m=1, n=1, phi=240, psi=200, t_p=1,
                             seed=0)
        out = lamc_cocluster(a, LAMCConfig(n_row_clusters=4, n_col_clusters=4),
                             plan=plan)
        key = jax.random.fold_in(                   # resample 0, block 0
            jax.random.fold_in(jax.random.key(plan.seed + 1), 0), 0)
        ref = scc_full(key, a, 4)
        from repro.core.metrics import nmi
        for got, want, truth in (
                (out.row_labels, ref.row_labels, data.row_labels),
                (out.col_labels, ref.col_labels, data.col_labels)):
            got, want = np.asarray(got), np.asarray(want)
            assert nmi(got, want) > 0.999
            assert nmi(got, truth) == pytest.approx(nmi(want, truth))
