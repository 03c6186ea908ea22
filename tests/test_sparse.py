"""Sparse-path (BCOO) suite: dense/sparse parity, SpMM kernel vs oracle,
plan-cost density behaviour, and the anchor-gather-order regression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import LAMCConfig, lamc_cocluster, partition, probability
from repro.core import sparse as core_sparse
from repro.core.lamc import anchor_features
from repro.core.metrics import nmi
from repro.core.partition import PartitionPlan
from repro.core.spectral import normalize_bipartite, randomized_svd, scc
from repro.data import planted_cocluster_matrix, to_bcoo
from repro.kernels import ops as kops


@pytest.fixture(scope="module")
def planted():
    rng = np.random.default_rng(0)
    return planted_cocluster_matrix(rng, 240, 200, k=4, d=4,
                                    signal=4.0, noise=0.5, density=0.15)


def _rand_sparse(rng, m, n, density):
    mat = np.where(rng.random((m, n)) < density,
                   rng.normal(size=(m, n)), 0.0).astype(np.float32)
    return mat


class TestBcooHelpers:
    def test_to_bcoo_roundtrip(self, planted):
        a = to_bcoo(planted.matrix)
        np.testing.assert_array_equal(np.asarray(a.todense()), planted.matrix)
        assert a.nse == int((planted.matrix != 0).sum())

    def test_gather_cols_dense(self, planted):
        a = to_bcoo(planted.matrix)
        cols = jnp.asarray([3, 190, 0, 77])
        out = core_sparse.gather_cols_dense(a, cols)
        np.testing.assert_array_equal(np.asarray(out),
                                      planted.matrix[:, np.array(cols)])

    def test_gather_rows_dense(self, planted):
        a = to_bcoo(planted.matrix)
        rows = jnp.asarray([10, 0, 239])
        out = core_sparse.gather_rows_dense(a, rows)
        np.testing.assert_array_equal(np.asarray(out),
                                      planted.matrix[np.array(rows)])

    def test_abs_degree_sums(self, planted):
        a = to_bcoo(planted.matrix)
        d1, d2 = core_sparse.abs_degree_sums(a)
        np.testing.assert_allclose(np.asarray(d1),
                                   np.abs(planted.matrix).sum(1), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(d2),
                                   np.abs(planted.matrix).sum(0), rtol=1e-5)

    def test_validate_rejects_non_2d(self):
        from jax.experimental import sparse as jsparse
        a3 = jsparse.BCOO.fromdense(jnp.ones((2, 3, 4)))
        with pytest.raises(ValueError, match="2-D"):
            core_sparse.validate_bcoo(a3)


class TestExtractBlocksSparse:
    @pytest.mark.parametrize("m,n", [(2, 2), (1, 1)])  # (1, 1): whole matrix
    def test_exact_parity_full_grid(self, planted, m, n):
        a = jnp.asarray(planted.matrix)
        a_sp = to_bcoo(planted.matrix)
        plan = PartitionPlan(240, 200, m=m, n=n, phi=240 // m, psi=200 // n,
                             t_p=2, seed=0)
        bd, ri, ci = partition.extract_blocks(a, plan, 1)
        bs, ri2, ci2 = partition.extract_blocks_sparse(a_sp, plan, 1)
        np.testing.assert_array_equal(np.asarray(bd), np.asarray(bs))
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(ri2))
        np.testing.assert_array_equal(np.asarray(ci), np.asarray(ci2))

    def test_exact_parity_with_dropped_rows_cols(self, planted):
        """Non-divisible grid: dropped indices must vanish, not alias."""
        a = jnp.asarray(planted.matrix)
        a_sp = to_bcoo(planted.matrix)
        # 240 % (3*79) and 200 % (3*66) both leave a remainder
        plan = PartitionPlan(240, 200, m=3, n=3, phi=79, psi=66, t_p=1, seed=5)
        bd, _, _ = partition.extract_blocks(a, plan, 0)
        bs, _, _ = partition.extract_blocks_sparse(a_sp, plan, 0)
        np.testing.assert_array_equal(np.asarray(bd), np.asarray(bs))

    @given(density=st.sampled_from([0.01, 0.1, 0.5]), seed=st.integers(0, 5))
    @settings(max_examples=8, deadline=None)
    def test_parity_sweep(self, density, seed):
        rng = np.random.default_rng(seed)
        mat = _rand_sparse(rng, 64, 48, density)
        plan = PartitionPlan(64, 48, m=2, n=2, phi=32, psi=24, t_p=1, seed=seed)
        bd, _, _ = partition.extract_blocks(jnp.asarray(mat), plan, 0)
        bs, _, _ = partition.extract_blocks_sparse(to_bcoo(mat), plan, 0)
        np.testing.assert_array_equal(np.asarray(bd), np.asarray(bs))

    def test_traced_resample_index(self, planted):
        """Must work under jit with a traced resample id (scan in lamc)."""
        a_sp = to_bcoo(planted.matrix)
        plan = PartitionPlan(240, 200, m=2, n=2, phi=120, psi=100, t_p=2, seed=0)
        f = jax.jit(lambda t: partition.extract_blocks_sparse(a_sp, plan, t)[0])
        assert f(jnp.int32(1)).shape == (4, 120, 100)


class TestSpmmKernel:
    @pytest.mark.parametrize("m,k,n", [(128, 128, 128), (200, 300, 130),
                                       (64, 512, 16), (300, 70, 250)])
    @pytest.mark.parametrize("density", [0.01, 0.05, 0.2])
    def test_tiled_kernel_matches_ref(self, m, k, n, density):
        rng = np.random.default_rng(m + k + n)
        mat = _rand_sparse(rng, m, k, density)
        a = to_bcoo(mat)
        b = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
        want = np.asarray(kops.spmm(a, b))
        np.testing.assert_allclose(want, mat @ np.asarray(b), atol=1e-3)
        bs = kops.bcoo_to_block_sparse(a, bm=64, bk=64)
        got = np.asarray(kops.spmm_tiled(bs, b, bn=64))
        np.testing.assert_allclose(got, want, atol=1e-3)

    def test_empty_tile_rows_are_zeroed(self):
        """Rows with no nonzeros at all must come out as exact zeros."""
        mat = np.zeros((128, 64), np.float32)
        mat[5, 3] = 2.0      # only the first tile-row is occupied
        b = np.ones((64, 32), np.float32)
        bs = kops.bcoo_to_block_sparse(to_bcoo(mat), bm=32, bk=32)
        out = np.asarray(kops.spmm_tiled(bs, jnp.asarray(b), bn=32))
        np.testing.assert_array_equal(out, mat @ b)

    def test_spmm_transpose(self):
        rng = np.random.default_rng(2)
        mat = _rand_sparse(rng, 90, 110, 0.1)
        b = jnp.asarray(rng.normal(size=(90, 12)).astype(np.float32))
        got = np.asarray(kops.spmm(to_bcoo(mat), b, transpose=True))
        np.testing.assert_allclose(got, mat.T @ np.asarray(b), atol=1e-3)

    def test_sddmm_matches_dense(self):
        rng = np.random.default_rng(3)
        x = rng.normal(size=(40, 9)).astype(np.float32)
        y = rng.normal(size=(55, 9)).astype(np.float32)
        idx = np.stack([rng.integers(0, 40, 200), rng.integers(0, 55, 200)], 1)
        got = np.asarray(kops.sddmm(jnp.asarray(x), jnp.asarray(y),
                                    jnp.asarray(idx)))
        want = (x @ y.T)[idx[:, 0], idx[:, 1]]
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_spmm_ref_is_jittable(self):
        rng = np.random.default_rng(4)
        mat = _rand_sparse(rng, 60, 80, 0.1)
        a = to_bcoo(mat)
        f = jax.jit(lambda b: kops.spmm(a, b))
        out = f(jnp.ones((80, 4), jnp.float32))
        np.testing.assert_allclose(np.asarray(out), mat @ np.ones((80, 4)),
                                   atol=1e-3)


class TestSparseSpectral:
    def test_normalize_bipartite_parity(self, planted):
        a = jnp.asarray(planted.matrix)
        a_sp = to_bcoo(planted.matrix)
        an_d, d1_d, d2_d = normalize_bipartite(a)
        an_s, d1_s, d2_s = normalize_bipartite(a_sp)
        assert core_sparse.is_bcoo(an_s)          # stays sparse
        np.testing.assert_allclose(np.asarray(an_s.todense()),
                                   np.asarray(an_d), atol=1e-5)
        np.testing.assert_allclose(np.asarray(d1_s), np.asarray(d1_d), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(d2_s), np.asarray(d2_d), rtol=1e-5)

    def test_randomized_svd_spmm_subspace(self, planted):
        """Sparse-path singular triplets must match the dense path's."""
        a = jnp.asarray(planted.matrix)
        key = jax.random.key(0)
        u_d, s_d, vt_d = randomized_svd(key, a, rank=5, n_iter=6)
        u_s, s_s, vt_s = randomized_svd(key, to_bcoo(planted.matrix),
                                        rank=5, n_iter=6)
        np.testing.assert_allclose(np.asarray(s_s), np.asarray(s_d), rtol=1e-3)
        # compare subspaces (signs/rotations free): |u_d^T u_s| ~ I
        ov = np.abs(np.asarray(u_d.T @ u_s))
        np.testing.assert_allclose(np.diag(ov), 1.0, atol=1e-2)

    def test_scc_bcoo_matches_dense_labels(self, planted):
        key = jax.random.key(0)
        res_d = scc(key, jnp.asarray(planted.matrix), 4)
        res_s = scc(key, to_bcoo(planted.matrix), 4)
        assert nmi(np.asarray(res_d.row_labels), np.asarray(res_s.row_labels)) > 0.999
        assert nmi(np.asarray(res_d.col_labels), np.asarray(res_s.col_labels)) > 0.999

    def test_scc_bcoo_rejects_exact_svd(self, planted):
        with pytest.raises(ValueError, match="dense"):
            scc(jax.random.key(0), to_bcoo(planted.matrix), 4,
                svd_method="exact")

    def test_ell_operator_products(self, planted):
        """Dual-ELL gather-only products must match dense exactly enough."""
        ell = core_sparse.to_ell(to_bcoo(planted.matrix))
        assert ell.shape == planted.matrix.shape
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(200, 6)).astype(np.float32))
        y = jnp.asarray(rng.normal(size=(240, 6)).astype(np.float32))
        np.testing.assert_allclose(np.asarray(core_sparse.ell_matvec(ell, x)),
                                   planted.matrix @ np.asarray(x), atol=1e-4)
        np.testing.assert_allclose(np.asarray(core_sparse.ell_rmatvec(ell, y)),
                                   planted.matrix.T @ np.asarray(y), atol=1e-4)

    def test_scc_ell_matches_dense_labels(self, planted):
        """The amortized repeated-product operator drives scc end to end."""
        key = jax.random.key(0)
        res_d = scc(key, jnp.asarray(planted.matrix), 4)
        res_e = scc(key, core_sparse.to_ell(to_bcoo(planted.matrix)), 4)
        assert nmi(np.asarray(res_d.row_labels), np.asarray(res_e.row_labels)) > 0.999
        assert nmi(np.asarray(res_d.col_labels), np.asarray(res_e.col_labels)) > 0.999

    def test_ell_normalize_parity(self, planted):
        a = jnp.asarray(planted.matrix)
        ell = core_sparse.to_ell(to_bcoo(planted.matrix))
        an_d, d1_d, d2_d = normalize_bipartite(a)
        an_e, d1_e, d2_e = normalize_bipartite(ell)
        assert core_sparse.is_ell(an_e)
        np.testing.assert_allclose(np.asarray(d1_e), np.asarray(d1_d), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(d2_e), np.asarray(d2_d), rtol=1e-5)
        # scaled operator still multiplies correctly
        x = jnp.ones((200, 1), jnp.float32)
        np.testing.assert_allclose(np.asarray(core_sparse.ell_matvec(an_e, x)),
                                   np.asarray(an_d @ x), atol=1e-4)


class TestSparseLAMC:
    def test_e2e_exact_label_parity(self, planted):
        """Acceptance: bcoo pipeline == dense pipeline labels, same seed."""
        a = jnp.asarray(planted.matrix)
        a_sp = to_bcoo(planted.matrix)
        plan = PartitionPlan(240, 200, m=2, n=2, phi=120, psi=100, t_p=2, seed=0)
        base = dict(n_row_clusters=4, n_col_clusters=4,
                    min_cocluster_rows=48, min_cocluster_cols=40)
        out_d = lamc_cocluster(a, LAMCConfig(**base), plan=plan)
        out_s = lamc_cocluster(a_sp, LAMCConfig(**base, input_format="bcoo"),
                               plan=plan)
        np.testing.assert_array_equal(np.asarray(out_d.row_labels),
                                      np.asarray(out_s.row_labels))
        np.testing.assert_array_equal(np.asarray(out_d.col_labels),
                                      np.asarray(out_s.col_labels))
        np.testing.assert_array_equal(np.asarray(out_d.row_votes),
                                      np.asarray(out_s.row_votes))

    def test_e2e_auto_plan_runs(self):
        # easier planting than the parity fixture: the auto plan picks a
        # single-block grid here, whose one-shot full-matrix SCC needs
        # more signal to recover structure at these densities (direct scc
        # on this data scores ~0.45; the vote merge lifts it to ~0.64)
        rng = np.random.default_rng(1)
        data = planted_cocluster_matrix(rng, 240, 200, k=4, d=4,
                                        signal=8.0, noise=0.2, density=0.4)
        cfg = LAMCConfig(n_row_clusters=4, n_col_clusters=4,
                         min_cocluster_rows=48, min_cocluster_cols=40,
                         input_format="bcoo")
        out = lamc_cocluster(to_bcoo(data.matrix), cfg)
        assert out.row_labels.shape == (240,)
        # the auto route keeps the single block in its sparse operator form
        assert out.plan.spmm_route == "tiled"
        s = nmi(np.asarray(out.row_labels), data.row_labels)
        assert s > 0.5, s

    def test_format_mismatch_raises(self, planted):
        cfg_sparse = LAMCConfig(n_row_clusters=4, n_col_clusters=4,
                                input_format="bcoo")
        with pytest.raises(ValueError, match="BCOO"):
            lamc_cocluster(jnp.asarray(planted.matrix), cfg_sparse,
                           plan=PartitionPlan(240, 200, 2, 2, 120, 100, 1))
        cfg_dense = LAMCConfig(n_row_clusters=4, n_col_clusters=4)
        with pytest.raises(ValueError, match="input_format"):
            lamc_cocluster(to_bcoo(planted.matrix), cfg_dense,
                           plan=PartitionPlan(240, 200, 2, 2, 120, 100, 1))

    def test_distributed_format_guard(self, planted):
        """distributed_lamc must fail loudly before jit on a format mismatch."""
        from repro.core.distributed import _validate_input_format
        with pytest.raises(ValueError, match="BCOO"):
            _validate_input_format(
                jnp.asarray(planted.matrix),
                LAMCConfig(n_row_clusters=4, n_col_clusters=4,
                           input_format="bcoo"))
        with pytest.raises(ValueError, match="input_format"):
            _validate_input_format(
                to_bcoo(planted.matrix),
                LAMCConfig(n_row_clusters=4, n_col_clusters=4))


class TestAnchorGatherRegression:
    def test_gather_order_identical_output(self, planted):
        """anchor-first gather must equal the old rows-first expression."""
        a = jnp.asarray(planted.matrix)
        anchor_cols = jnp.asarray([5, 60, 199, 0])
        anchor_rows = jnp.asarray([7, 0, 150])
        plan = PartitionPlan(240, 200, m=2, n=2, phi=120, psi=100, t_p=1, seed=0)
        row_idx, col_idx = partition.resample_indices(plan, 0)
        row_sliver, col_sliver = anchor_features(a, anchor_rows, anchor_cols)
        new_row = row_sliver[row_idx]                       # (m, phi, q)
        old_row = a[row_idx][:, :, anchor_cols]             # (m, phi, N) interm.
        np.testing.assert_array_equal(np.asarray(new_row), np.asarray(old_row))
        new_col = col_sliver[:, col_idx]
        old_col = a[anchor_rows][:, col_idx]
        np.testing.assert_array_equal(np.asarray(new_col), np.asarray(old_col))

    def test_anchor_features_sparse_parity(self, planted):
        a = jnp.asarray(planted.matrix)
        a_sp = to_bcoo(planted.matrix)
        kar, kac = jax.random.split(jax.random.key(1))
        from repro.core.merging import anchor_indices
        anchor_rows = anchor_indices(kar, 240, 64)
        anchor_cols = anchor_indices(kac, 200, 64)
        rd, cd = anchor_features(a, anchor_rows, anchor_cols)
        rs, cs = anchor_features(a_sp, anchor_rows, anchor_cols)
        np.testing.assert_array_equal(np.asarray(rd), np.asarray(rs))
        np.testing.assert_array_equal(np.asarray(cd), np.asarray(cs))


class TestSparsePlanCost:
    def test_atom_cost_monotone_in_density(self):
        costs = [probability._atom_cost(512, 512, 8, 4, 16, 8,
                                        density=d)
                 for d in (0.01, 0.05, 0.2, 1.0)]
        assert costs == sorted(costs)
        assert costs[0] < costs[-1]

    def test_exact_svd_ignores_density(self):
        c1 = probability._atom_cost(512, 512, 8, 4, 16, 8,
                                    svd_method="exact", density=0.01)
        c2 = probability._atom_cost(512, 512, 8, 4, 16, 8,
                                    svd_method="exact", density=1.0)
        assert c1 == c2

    def test_plan_cost_monotone_in_density(self):
        # workers=1 so the single-block sparse route is the best plan and
        # its density-scaled cost is what the search surfaces; with many
        # workers a multi-block plan (dense blocks, density-independent
        # cost by construction) can win at every density and the curve
        # legitimately plateaus
        kw = dict(min_cocluster_rows=256, min_cocluster_cols=256,
                  p_thresh=0.95, workers=1, k=8)
        costs = [probability.plan_partition(4096, 4096, density=d, **kw).est_cost
                 for d in (0.01, 0.05, 0.2, 1.0)]
        assert costs == sorted(costs)
        assert costs[0] < costs[-1]

    def test_multiblock_priced_dense(self):
        """Multi-block candidates densify their blocks: est_cost and the
        surfaced route must say so, whatever the density/knob."""
        kw = dict(min_cocluster_rows=256, min_cocluster_cols=256,
                  p_thresh=0.95, workers=8, k=8)
        cand = probability.plan_partition(4096, 4096, density=0.01,
                                          grid_candidates=(4,), **kw)
        assert (cand.m, cand.n) != (1, 1)
        assert cand.spmm_route == "dense"
        sparse_priced = probability.plan_partition(
            4096, 4096, density=1.0, grid_candidates=(4,), **kw)
        assert cand.est_cost == sparse_priced.est_cost  # density-independent

    def test_sparse_speedup_asymmetry(self):
        """The planner's predicted partitioning win must shrink with
        sparsity: (1,1)-grid cost / best-grid cost is the modelled
        speedup, which the paper reports ~83% (dense, exact SVD) vs ~30%
        (sparse). Dense-exact must gain strictly more than sparse."""
        kw = dict(min_cocluster_rows=512, min_cocluster_cols=512,
                  p_thresh=0.9, workers=8, k=8)
        def gain(svd_method, density):
            best = probability.plan_partition(
                8192, 8192, svd_method=svd_method, density=density,
                **kw).est_cost
            full = probability.plan_partition(
                8192, 8192, svd_method=svd_method, density=density,
                grid_candidates=(1,), **kw).est_cost
            return 1.0 - best / full
        dense_gain = gain("exact", 1.0)
        sparse_gain = gain("randomized", 0.01)
        assert dense_gain > sparse_gain, (dense_gain, sparse_gain)


class TestSpmmRouting:
    def test_route_by_density(self):
        """Calibrated crossovers: gathers below, tile GEMMs above."""
        cells = 4096.0 * 2048
        assert probability.spmm_route(0.01, cells) == "dual_ell"
        assert probability.spmm_route(0.05, cells) == "dual_ell"
        assert probability.spmm_route(0.2, cells) == "tiled"
        assert probability.spmm_route(0.95, cells) == "dense"

    def test_route_small_blocks_densify(self):
        """Sub-64x64 blocks never pay back sparse-format prep."""
        assert probability.spmm_route(0.01, 32.0 * 32) == "dense"

    def test_crossover_constant_brackets_bench(self):
        """The published crossover sits inside the measured (0.05, 0.2)
        win/loss bracket and at the cost model's parity point."""
        assert 0.05 < probability.SPMM_ELL_CROSSOVER < 0.2
        cells = 4096.0 * 2048
        below = probability.spmm_costs(0.05, cells)
        above = probability.spmm_costs(0.2, cells)
        assert below["dual_ell"] < below["tiled"]
        assert above["tiled"] < above["dual_ell"]

    def test_atom_cost_pinned_impl(self):
        """Pinning the backend prices it even when it is not cheapest."""
        kw = dict(density=0.2)
        auto = probability._atom_cost(512, 512, 8, 4, 16, 8, **kw)
        ell = probability._atom_cost(512, 512, 8, 4, 16, 8,
                                     spmm_impl="dual_ell", **kw)
        assert auto < ell

    def test_plan_surfaces_route(self):
        """make_plan exposes the per-block dispatch decision."""
        low = partition.make_plan(4096, 4096, min_cocluster_rows=256,
                                  min_cocluster_cols=256, density=0.01)
        high = partition.make_plan(4096, 4096, min_cocluster_rows=256,
                                   min_cocluster_cols=256, density=0.2)
        dense = partition.make_plan(4096, 4096, min_cocluster_rows=256,
                                    min_cocluster_cols=256)
        assert low.spmm_route == "dual_ell"
        assert high.spmm_route == "tiled"
        assert dense.spmm_route == "dense"
        pinned = partition.make_plan(4096, 4096, min_cocluster_rows=256,
                                     min_cocluster_cols=256, density=0.01,
                                     spmm_impl="tiled")
        assert pinned.spmm_route == "tiled"


class TestTiledSpectral:
    def test_randomized_svd_tiled_matches_dense(self, planted):
        """Tiled normal-equations iteration reaches the dense subspace."""
        a = jnp.asarray(planted.matrix)
        key = jax.random.key(0)
        u_d, s_d, _ = randomized_svd(key, a, rank=5, n_iter=6)
        tiled = core_sparse.to_tiled(to_bcoo(planted.matrix), bm=64, bk=64)
        u_t, s_t, _ = randomized_svd(key, tiled, rank=5, n_iter=6)
        np.testing.assert_allclose(np.asarray(s_t), np.asarray(s_d), rtol=1e-3)
        ov = np.abs(np.asarray(u_d.T @ u_t))
        np.testing.assert_allclose(np.diag(ov), 1.0, atol=1e-2)

    def test_normalize_bipartite_tiled_parity(self, planted):
        a = jnp.asarray(planted.matrix)
        tiled = core_sparse.to_tiled(to_bcoo(planted.matrix), bm=64, bk=64)
        an_d, d1_d, d2_d = normalize_bipartite(a)
        an_t, d1_t, d2_t = normalize_bipartite(tiled)
        assert core_sparse.is_tiled(an_t)
        np.testing.assert_allclose(np.asarray(d1_t), np.asarray(d1_d), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(d2_t), np.asarray(d2_d), rtol=1e-5)
        x = jnp.ones((200, 1), jnp.float32)
        from repro.kernels import ops as _kops
        np.testing.assert_allclose(np.asarray(_kops.spmm_tiled(an_t, x)),
                                   np.asarray(an_d @ x), atol=1e-4)

    def test_scc_tiled_matches_dense_labels(self, planted):
        key = jax.random.key(0)
        res_d = scc(key, jnp.asarray(planted.matrix), 4)
        res_t = scc(key, core_sparse.to_tiled(to_bcoo(planted.matrix)), 4)
        assert nmi(np.asarray(res_d.row_labels), np.asarray(res_t.row_labels)) > 0.999
        assert nmi(np.asarray(res_d.col_labels), np.asarray(res_t.col_labels)) > 0.999

    def test_scc_tiled_rejects_exact_svd(self, planted):
        with pytest.raises(ValueError, match="dense"):
            scc(jax.random.key(0), core_sparse.to_tiled(to_bcoo(planted.matrix)),
                4, svd_method="exact")


class TestSpmmImplLAMC:
    def test_multiblock_exact_parity_any_impl(self, planted):
        """Multi-block plans densify their blocks: the knob must not
        perturb the exact dense/sparse label parity."""
        a = jnp.asarray(planted.matrix)
        a_sp = to_bcoo(planted.matrix)
        plan = PartitionPlan(240, 200, m=2, n=2, phi=120, psi=100, t_p=2, seed=0)
        base = dict(n_row_clusters=4, n_col_clusters=4,
                    min_cocluster_rows=48, min_cocluster_cols=40)
        out_d = lamc_cocluster(a, LAMCConfig(**base), plan=plan)
        for impl in ("tiled", "dual_ell", "auto", "dense"):
            out_s = lamc_cocluster(
                a_sp, LAMCConfig(**base, input_format="bcoo", spmm_impl=impl),
                plan=plan)
            np.testing.assert_array_equal(np.asarray(out_d.row_labels),
                                          np.asarray(out_s.row_labels))
            np.testing.assert_array_equal(np.asarray(out_d.col_labels),
                                          np.asarray(out_s.col_labels))

    def test_single_block_operator_path(self):
        """(1,1) plans keep A in sparse-operator form; tiled and dual-ELL
        routes agree with each other and recover the planted structure."""
        rng = np.random.default_rng(1)
        data = planted_cocluster_matrix(rng, 240, 200, k=4, d=4,
                                        signal=8.0, noise=0.2, density=0.4)
        a_sp = to_bcoo(data.matrix)
        plan = PartitionPlan(240, 200, m=1, n=1, phi=240, psi=200, t_p=2,
                             seed=0)
        base = dict(n_row_clusters=4, n_col_clusters=4,
                    min_cocluster_rows=48, min_cocluster_cols=40,
                    input_format="bcoo")
        out_t = lamc_cocluster(a_sp, LAMCConfig(**base, spmm_impl="tiled"),
                               plan=plan)
        out_e = lamc_cocluster(a_sp, LAMCConfig(**base, spmm_impl="dual_ell"),
                               plan=plan)
        assert out_t.plan.spmm_route == "tiled"
        assert out_e.plan.spmm_route == "dual_ell"
        # same operator semantics -> same labels across product backends
        assert nmi(np.asarray(out_t.row_labels),
                   np.asarray(out_e.row_labels)) > 0.99
        assert nmi(np.asarray(out_t.row_labels), data.row_labels) > 0.5

    @pytest.mark.parametrize("impl", ["dense", "dual_ell"])
    def test_whole_matrix_plan_matches_dense_fit(self, impl):
        """A whole-matrix plan runs the unpermuted atom on every route: the
        densify route (``spmm_impl="dense"``) gives the dense path's labels
        exactly, and the sparse operator agrees with them."""
        rng = np.random.default_rng(1)
        data = planted_cocluster_matrix(rng, 240, 200, k=4, d=4,
                                        signal=8.0, noise=0.2, density=0.4)
        plan = PartitionPlan(240, 200, m=1, n=1, phi=240, psi=200, t_p=1,
                             seed=0)
        base = dict(n_row_clusters=4, n_col_clusters=4,
                    min_cocluster_rows=48, min_cocluster_cols=40)
        out_d = lamc_cocluster(jnp.asarray(data.matrix), LAMCConfig(**base),
                               plan=plan)
        out_s = lamc_cocluster(
            to_bcoo(data.matrix),
            LAMCConfig(**base, input_format="bcoo", spmm_impl=impl),
            plan=plan)
        assert out_s.plan.spmm_route == impl
        if impl == "dense":
            np.testing.assert_array_equal(np.asarray(out_d.row_labels),
                                          np.asarray(out_s.row_labels))
            np.testing.assert_array_equal(np.asarray(out_d.col_labels),
                                          np.asarray(out_s.col_labels))
        for got, want in ((out_s.row_labels, out_d.row_labels),
                          (out_s.col_labels, out_d.col_labels)):
            assert nmi(np.asarray(got), np.asarray(want)) > 0.99
        assert nmi(np.asarray(out_s.row_labels), data.row_labels) > 0.5

    def test_single_block_subsampling_plan_falls_back(self, planted):
        """A (1,1) plan with phi < M / psi < N subsamples per resample —
        the operator path cannot represent that, so it must fall back to
        the extraction path (bit-identical to spmm_impl='dense')."""
        a_sp = to_bcoo(planted.matrix)
        plan = PartitionPlan(240, 200, m=1, n=1, phi=200, psi=160, t_p=2,
                             seed=0)
        base = dict(n_row_clusters=4, n_col_clusters=4,
                    min_cocluster_rows=48, min_cocluster_cols=40,
                    input_format="bcoo")
        out_auto = lamc_cocluster(a_sp, LAMCConfig(**base), plan=plan)
        out_dense = lamc_cocluster(a_sp, LAMCConfig(**base,
                                                    spmm_impl="dense"),
                                   plan=plan)
        assert out_auto.plan.spmm_route == "dense"
        np.testing.assert_array_equal(np.asarray(out_auto.row_labels),
                                      np.asarray(out_dense.row_labels))

    def test_invalid_impl_raises(self, planted):
        cfg = LAMCConfig(n_row_clusters=4, n_col_clusters=4,
                         spmm_impl="csr")
        with pytest.raises(ValueError, match="spmm_impl"):
            lamc_cocluster(jnp.asarray(planted.matrix), cfg,
                           plan=PartitionPlan(240, 200, 2, 2, 120, 100, 1))
        from repro.core.distributed import _validate_input_format
        with pytest.raises(ValueError, match="spmm_impl"):
            _validate_input_format(jnp.asarray(planted.matrix), cfg)

    def test_streaming_config_carries_impl(self):
        from repro.streaming import StreamingCocluster
        from repro.streaming.fit import StreamConfig, stream_config_from_lamc
        lamc_cfg = LAMCConfig(n_row_clusters=4, n_col_clusters=4,
                              spmm_impl="tiled")
        scfg = stream_config_from_lamc(lamc_cfg)
        assert scfg.spmm_impl == "tiled"
        with pytest.raises(ValueError, match="spmm_impl"):
            StreamingCocluster(StreamConfig(n_row_clusters=4,
                                            n_col_clusters=4,
                                            spmm_impl="csr"))


class TestCoverageProbability:
    def test_min_of_axes(self):
        # rows fully covered, cols drop 20 of 100 per resample
        plan = PartitionPlan(90, 100, m=3, n=4, phi=30, psi=20, t_p=1)
        assert partition.coverage_probability(plan, axis="row") == 1.0
        assert partition.coverage_probability(plan, axis="col") == pytest.approx(0.8)
        assert partition.coverage_probability(plan) == pytest.approx(0.8)

    def test_bad_axis_raises(self):
        plan = PartitionPlan(90, 100, m=3, n=4, phi=30, psi=20, t_p=1)
        with pytest.raises(ValueError):
            partition.coverage_probability(plan, axis="diag")
