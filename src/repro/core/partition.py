"""Large-matrix partitioning (paper §IV-B).

A :class:`PartitionPlan` describes how ``A (M x N)`` is cut into an ``m x n``
grid of uniform ``phi x psi`` blocks, repeated for ``T_p`` independent random
resamples. Permutations are derived from a counter-based PRNG
(``jax.random.fold_in``) so that in the distributed runtime every device can
re-derive its block's row/col indices from ``(seed, resample_index)`` alone —
no index lists ever cross the interconnect (DESIGN.md §2).

Rows/cols that do not fit the uniform grid (``M mod m*phi``) are simply left
out of that resample; across ``T_p`` random resamples every index is covered
with overwhelming probability, and the Theorem-1 budget already accounts for
per-resample misses. ``coverage_probability`` quantifies it.

**Whole-matrix plans** (one block per resample, ``phi == M`` and
``psi == N``; ``whole_matrix``) take the identity instead of a random
permutation: the one block is ``A`` itself, and a permutation would only
reorder points *within* it, which block membership ignores. Extraction
is then no gather and no scatter — ``extract_blocks`` hands over ``A``
unchanged — and every entry point (``lamc``'s dense, BCOO and sparse-operator
routes, ``distributed``) follows this one rule. The atom sees the points
in stored order, so k-means seeding, and only that, differs from a
permuted block's.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import probability

__all__ = ["PartitionPlan", "make_plan", "whole_matrix", "extraction",
           "resample_indices", "extract_blocks", "extract_blocks_sparse",
           "coverage_probability"]


@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    n_rows: int
    n_cols: int
    m: int            # row-blocks per resample
    n: int            # col-blocks per resample
    phi: int          # rows per block
    psi: int          # cols per block
    t_p: int          # number of resamples
    seed: int = 0
    detection_p: float = 1.0  # Theorem-1 lower bound used to pick t_p
    # SpMM backend the plan priced its blocks with ("dense" | "dual_ell" |
    # "tiled") — the density-adaptive dispatch decision, surfaced for
    # callers and tests. "dense" for dense inputs and user-built plans.
    spmm_route: str = "dense"

    @property
    def blocks_per_resample(self) -> int:
        return self.m * self.n

    @property
    def total_blocks(self) -> int:
        return self.m * self.n * self.t_p

    @property
    def rows_used(self) -> int:
        return self.m * self.phi

    @property
    def cols_used(self) -> int:
        return self.n * self.psi


def make_plan(
    n_rows: int,
    n_cols: int,
    *,
    min_cocluster_rows: int,
    min_cocluster_cols: int,
    p_thresh: float = 0.95,
    workers: int = 1,
    seed: int = 0,
    k: int = 8,
    expected_failed_blocks: int = 0,
    grid_candidates=(1, 2, 4, 8, 16, 32),
    svd_method: str = "randomized",
    density: float = 1.0,
    spmm_impl: str = "auto",
) -> PartitionPlan:
    """Optimal plan via the probabilistic model (Eq. 4 + cost search).

    ``density`` (nnz fraction) feeds the sparse-aware atom cost model —
    the SpMM subspace iteration scales with nnz (gather backends) or tile
    occupancy (tiled backend), not block area (``probability._atom_cost``).
    ``spmm_impl`` pins the backend the blocks are priced with; ``"auto"``
    resolves per block density (``probability.spmm_route``) and the
    decision is surfaced on ``PartitionPlan.spmm_route``.
    """
    cand = probability.plan_partition(
        n_rows,
        n_cols,
        min_cocluster_rows=min_cocluster_rows,
        min_cocluster_cols=min_cocluster_cols,
        p_thresh=p_thresh,
        workers=workers,
        k=k,
        expected_failed_blocks=expected_failed_blocks,
        grid_candidates=grid_candidates,
        svd_method=svd_method,
        density=density,
        spmm_impl=spmm_impl,
    )
    return PartitionPlan(
        n_rows=n_rows,
        n_cols=n_cols,
        m=cand.m,
        n=cand.n,
        phi=cand.phi,
        psi=cand.psi,
        t_p=cand.t_p,
        seed=seed,
        detection_p=cand.detection_p,
        spmm_route=cand.spmm_route,
    )


def coverage_probability(plan: PartitionPlan, axis: str | None = None) -> float:
    """P(a given index appears in >= 1 of the T_p resamples).

    ``axis='row'`` / ``'col'`` gives the per-axis coverage; the default
    (``None``) returns their min — the guarantee that holds for *every*
    index of the matrix. (The row-only form silently overstated coverage
    whenever the column grid dropped more of its axis than the row grid.)
    """
    miss_row = 1.0 - plan.rows_used / plan.n_rows
    miss_col = 1.0 - plan.cols_used / plan.n_cols
    row_cov = 1.0 - miss_row**plan.t_p
    col_cov = 1.0 - miss_col**plan.t_p
    if axis == "row":
        return row_cov
    if axis == "col":
        return col_cov
    if axis is not None:
        raise ValueError(f"axis must be 'row', 'col' or None, got {axis!r}")
    return min(row_cov, col_cov)


def whole_matrix(plan: PartitionPlan) -> bool:
    """True when a resample's one block is the whole matrix (module doc)."""
    return (plan.blocks_per_resample == 1 and plan.phi == plan.n_rows
            and plan.psi == plan.n_cols)


def extraction(plan: PartitionPlan, sparse: bool) -> str:
    """Which extraction a resample runs: ``"whole"`` (none, the matrix
    itself), ``"scatter"`` (``extract_blocks_sparse``) or ``"gather"``."""
    if whole_matrix(plan):
        return "whole"
    return "scatter" if sparse else "gather"


def resample_indices(plan: PartitionPlan, resample: jax.Array | int):
    """Row/col index groups for one resample.

    Returns ``(row_idx, col_idx)`` of shapes ``(m, phi)`` / ``(n, psi)``:
    ``row_idx[i]`` are the global row ids landing in block-row ``i``.
    Deterministic in ``(plan.seed, resample)`` — re-derivable anywhere.
    A whole-matrix plan's maps are the identity, ``arange`` of each axis.
    """
    if whole_matrix(plan):
        return (jnp.arange(plan.n_rows, dtype=jnp.int32)[None],
                jnp.arange(plan.n_cols, dtype=jnp.int32)[None])
    key = jax.random.fold_in(jax.random.key(plan.seed), resample)
    krow, kcol = jax.random.split(key)
    row_perm = jax.random.permutation(krow, plan.n_rows)[: plan.rows_used]
    col_perm = jax.random.permutation(kcol, plan.n_cols)[: plan.cols_used]
    row_idx = row_perm.reshape(plan.m, plan.phi)
    col_idx = col_perm.reshape(plan.n, plan.psi)
    return row_idx, col_idx


def extract_blocks(a: jax.Array, plan: PartitionPlan, resample: jax.Array | int):
    """Extract the ``(m*n, phi, psi)`` block stack for one resample.

    Also returns the index maps so labels can be scattered back:
    ``blocks[i * n + j] == a[row_idx[i]][:, col_idx[j]]``. A whole-matrix
    plan's stack is ``a[None]``, with no gather.
    """
    row_idx, col_idx = resample_indices(plan, resample)
    if whole_matrix(plan):
        return a[None], row_idx, col_idx
    rows, cols = row_idx.reshape(-1), col_idx.reshape(-1)
    # Two gathers; the first one materializes an intermediate whose size
    # depends on order — (rows_used, N) rows-first vs (M, cols_used)
    # cols-first. Gather the axis that shrinks the matrix most first, so
    # peak gather traffic is min(rows_used*N, M*cols_used) + blocks, not
    # always rows_used*N (which loses badly when N >> cols_used).
    if plan.rows_used * plan.n_cols <= plan.n_rows * plan.cols_used:
        sub = a[rows][:, cols]                            # (m*phi, n*psi)
    else:
        sub = a[:, cols][rows]                            # (m*phi, n*psi)
    blocks = (
        sub.reshape(plan.m, plan.phi, plan.n, plan.psi)
        .transpose(0, 2, 1, 3)
        .reshape(plan.m * plan.n, plan.phi, plan.psi)
    )
    return blocks, row_idx, col_idx


def extract_blocks_sparse(a, plan: PartitionPlan, resample: jax.Array | int):
    """``extract_blocks`` for a BCOO matrix — O(nnz), never densifies A.

    Instead of gathering a ``(m*phi, n*psi)`` dense submatrix, every
    stored nonzero computes its own destination through the *inverse*
    resample permutation — ``(block, row-in-block, col-in-block)`` — and
    scatters straight into the dense block stack. Nonzeros whose row or
    column misses this resample's uniform grid map to an out-of-range
    block id and are dropped (``mode='drop'``), mirroring the dense
    path's "rows that don't fit are left out". The blocks themselves
    densify (they are the atom work unit and must be MXU-shaped), but
    peak memory is ``m*n*phi*psi + O(nnz)`` — the dense ``M x N`` matrix
    never exists.

    Bit-exact vs ``extract_blocks`` on the densified input: each block
    cell receives exactly one stored value or stays zero (BCOO indices
    are unique), so there is no summation-order drift. A whole-matrix
    plan's stack is the densified matrix, with no permutation.
    """
    from . import sparse as _sparse  # local: keep partition importable sans jax.experimental

    _sparse.validate_bcoo(a)
    row_idx, col_idx = resample_indices(plan, resample)
    if whole_matrix(plan):
        return a.todense()[None], row_idx, col_idx
    inv_row = jnp.full((plan.n_rows,), plan.rows_used, jnp.int32).at[
        row_idx.reshape(-1)].set(jnp.arange(plan.rows_used, dtype=jnp.int32))
    inv_col = jnp.full((plan.n_cols,), plan.cols_used, jnp.int32).at[
        col_idx.reshape(-1)].set(jnp.arange(plan.cols_used, dtype=jnp.int32))
    pr = inv_row[a.indices[:, 0]]                 # position among used rows
    pc = inv_col[a.indices[:, 1]]
    i, p = pr // plan.phi, pr % plan.phi          # block-row, row-in-block
    j, s = pc // plan.psi, pc % plan.psi
    bid = i * plan.n + j
    # The row sentinel alone lands out of range (i == m -> bid >= m*n), but
    # the col sentinel gives j == n which can alias a valid block id for
    # i < m - 1 — force every dropped nonzero out of range explicitly.
    valid = (pr < plan.rows_used) & (pc < plan.cols_used)
    bid = jnp.where(valid, bid, plan.m * plan.n)
    blocks = jnp.zeros((plan.m * plan.n, plan.phi, plan.psi), a.data.dtype)
    blocks = blocks.at[bid, p, s].add(a.data, mode="drop")
    return blocks, row_idx, col_idx
