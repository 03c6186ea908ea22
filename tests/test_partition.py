"""Partition plan + block extraction invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import partition


def _plan(M=120, N=90, m=3, n=3, t_p=2, seed=0):
    return partition.PartitionPlan(
        n_rows=M, n_cols=N, m=m, n=n, phi=M // m, psi=N // n, t_p=t_p, seed=seed
    )


class TestResampleIndices:
    def test_shapes(self):
        plan = _plan()
        row_idx, col_idx = partition.resample_indices(plan, 0)
        assert row_idx.shape == (3, 40)
        assert col_idx.shape == (3, 30)

    def test_indices_are_disjoint_within_resample(self):
        plan = _plan()
        row_idx, col_idx = partition.resample_indices(plan, 0)
        assert len(np.unique(np.array(row_idx))) == plan.rows_used
        assert len(np.unique(np.array(col_idx))) == plan.cols_used

    def test_deterministic_in_seed_and_resample(self):
        plan = _plan()
        r1, c1 = partition.resample_indices(plan, 3)
        r2, c2 = partition.resample_indices(plan, 3)
        assert np.array_equal(np.array(r1), np.array(r2))
        r3, _ = partition.resample_indices(plan, 4)
        assert not np.array_equal(np.array(r1), np.array(r3))

    def test_traced_resample_index(self):
        """Must work under jit with a traced resample id (scan in lamc)."""
        plan = _plan()
        f = jax.jit(lambda t: partition.resample_indices(plan, t)[0])
        assert f(jnp.int32(1)).shape == (3, 40)

    @pytest.mark.parametrize("m,n,phi,psi,whole", [
        (1, 1, 120, 90, True),     # the one block is the whole matrix
        (1, 1, 100, 90, False),    # 1 x 1, but rows subsampled
        (3, 3, 40, 30, False),     # multi-block
    ])
    def test_index_maps_follow_plan_shape(self, m, n, phi, psi, whole):
        """A whole-matrix plan's maps are the identity in every resample;
        any other plan's are a random permutation of the used rows/cols."""
        plan = partition.PartitionPlan(120, 90, m=m, n=n, phi=phi, psi=psi,
                                       t_p=2, seed=3)
        assert partition.whole_matrix(plan) == whole
        for t in (0, 1):
            row_idx, col_idx = (np.array(x) for x in
                                partition.resample_indices(plan, t))
            assert row_idx.shape == (m, phi) and col_idx.shape == (n, psi)
            rows, cols = row_idx.reshape(-1), col_idx.reshape(-1)
            assert len(np.unique(rows)) == plan.rows_used
            assert len(np.unique(cols)) == plan.cols_used
            iota = (np.array_equal(rows, np.arange(plan.rows_used))
                    and np.array_equal(cols, np.arange(plan.cols_used)))
            assert iota == whole


class TestExtractBlocks:
    def test_block_content_matches_indices(self):
        plan = _plan()
        a = jnp.arange(120 * 90, dtype=jnp.float32).reshape(120, 90)
        blocks, row_idx, col_idx = partition.extract_blocks(a, plan, 0)
        assert blocks.shape == (9, 40, 30)
        a_np = np.array(a)
        for i in range(3):
            for j in range(3):
                expect = a_np[np.array(row_idx[i])][:, np.array(col_idx[j])]
                np.testing.assert_array_equal(np.array(blocks[i * 3 + j]), expect)

    @given(
        m=st.sampled_from([1, 2, 4]),
        n=st.sampled_from([1, 2, 4]),
        seed=st.integers(0, 10),
    )
    @settings(max_examples=10, deadline=None)
    def test_every_element_appears_exactly_once(self, m, n, seed):
        M, N = 32, 24
        plan = partition.PartitionPlan(M, N, m=m, n=n, phi=M // m, psi=N // n,
                                       t_p=1, seed=seed)
        a = jnp.arange(M * N, dtype=jnp.float32).reshape(M, N)
        blocks, _, _ = partition.extract_blocks(a, plan, 0)
        vals = np.sort(np.array(blocks).ravel())
        np.testing.assert_array_equal(vals, np.arange(M * N, dtype=np.float32))

    @pytest.mark.parametrize("M,N,m,n,phi,psi", [
        (40, 400, 2, 2, 15, 20),    # wide: cols-first gather is cheaper
        (400, 40, 2, 2, 20, 15),    # tall: rows-first gather is cheaper
        (400, 40, 1, 1, 300, 40),   # 1 x 1 subsampling: still gathers
        (40, 400, 1, 1, 40, 400),   # whole matrix: no gather at all
    ])
    def test_gather_order_is_content_invariant(self, M, N, m, n, phi, psi):
        """Cheaper-axis-first gather must produce the exact same blocks;
        a whole-matrix plan's one block is ``a`` itself."""
        plan = partition.PartitionPlan(M, N, m=m, n=n, phi=phi, psi=psi,
                                       t_p=1, seed=5)
        a = jnp.asarray(np.random.default_rng(0).normal(size=(M, N)).astype(np.float32))
        blocks, row_idx, col_idx = partition.extract_blocks(a, plan, 0)
        rows = np.array(row_idx).reshape(-1)
        cols = np.array(col_idx).reshape(-1)
        expect = (np.array(a)[rows][:, cols]
                  .reshape(m, phi, n, psi).transpose(0, 2, 1, 3)
                  .reshape(m * n, phi, psi))
        np.testing.assert_array_equal(np.array(blocks), expect)
        if partition.whole_matrix(plan):
            np.testing.assert_array_equal(np.array(blocks[0]), np.array(a))


class TestCoverage:
    def test_full_grid_covers_everything(self):
        plan = _plan()
        assert partition.coverage_probability(plan) == 1.0

    def test_partial_grid_coverage_grows_with_resamples(self):
        # 100 rows, m=3 -> phi=33 -> 99 used, 1 dropped per resample
        p1 = partition.PartitionPlan(100, 90, 3, 3, 33, 30, t_p=1)
        p5 = partition.PartitionPlan(100, 90, 3, 3, 33, 30, t_p=5)
        assert partition.coverage_probability(p5) > partition.coverage_probability(p1)

    def test_col_coverage_bounds_the_default(self):
        # rows fully covered but cols drop 12 of 96 per resample: the
        # default (min over axes) must report the col-side risk, which the
        # old row-only formula hid entirely.
        plan = partition.PartitionPlan(90, 96, 3, 4, 30, 21, t_p=2)
        assert partition.coverage_probability(plan, axis="row") == 1.0
        col = partition.coverage_probability(plan, axis="col")
        assert col == pytest.approx(1.0 - (12 / 96) ** 2)
        assert partition.coverage_probability(plan) == pytest.approx(col)


class TestMakePlan:
    def test_make_plan_smoke(self):
        plan = partition.make_plan(
            2048, 2048, min_cocluster_rows=256, min_cocluster_cols=256,
            p_thresh=0.95, workers=8, k=8,
        )
        assert plan.detection_p >= 0.95
        assert plan.rows_used <= 2048 and plan.cols_used <= 2048
