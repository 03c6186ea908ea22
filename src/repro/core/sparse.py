"""BCOO utilities for the sparse LAMC path (DESIGN.md §9).

The sparse execution path keeps the full ``M x N`` data matrix in
``jax.experimental.sparse`` BCOO form end-to-end; only *block-sized*
dense tensors (``phi x psi`` blocks, ``M x q`` anchor features) are ever
materialized. Everything here is O(nnz) gather/scatter work with static
shapes (``nse`` is static in a BCOO), so it composes with jit and
``lax.scan`` exactly like the dense path.

The inverse-permutation scatters use ``mode="drop"``: indices that fall
outside a resample's uniform grid (or outside the anchor set) are mapped
to an out-of-range sentinel and silently dropped — the same semantics as
the dense path's "rows that don't fit the grid are left out".

Assumes canonical 2-D BCOO (``n_batch == n_dense == 0``) with unique
index pairs, which is what ``BCOO.fromdense`` / ``data.synthetic.to_bcoo``
produce. Duplicate indices would sum (matching ``todense``) but break the
bit-exact dense/sparse parity contract, so ``validate_bcoo`` documents
the requirement.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from repro.kernels.spmm import BlockSparseMatrix

__all__ = [
    "is_bcoo",
    "validate_bcoo",
    "density",
    "abs_degree_sums",
    "scale_rows_cols",
    "gather_cols_dense",
    "gather_rows_dense",
    "EllOperator",
    "EllPlan",
    "ell_plan",
    "ell_apply",
    "to_ell",
    "is_ell",
    "ell_matvec",
    "ell_rmatvec",
    "ell_abs_degree_sums",
    "ell_scale_rows_cols",
    "is_tiled",
    "to_tiled",
    "tiled_abs_degree_sums",
    "tiled_scale_rows_cols",
    "SPMM_IMPLS",
    "validate_spmm_impl",
    "prepare_operator",
]


def is_bcoo(a) -> bool:
    """True if ``a`` is a ``jax.experimental.sparse`` BCOO matrix."""
    return isinstance(a, jsparse.BCOO)


def validate_bcoo(a: jsparse.BCOO) -> jsparse.BCOO:
    """Check the sparse path's input contract (2-D BCOO, no batch/dense dims)."""
    if not is_bcoo(a):
        raise ValueError(
            f"sparse path needs a jax.experimental.sparse BCOO matrix, got "
            f"{type(a).__name__}")
    if a.ndim != 2:
        raise ValueError(f"sparse path needs a 2-D BCOO matrix, got shape {a.shape}")
    if a.n_batch != 0 or a.n_dense != 0:
        raise ValueError(
            f"sparse path needs canonical BCOO (n_batch=n_dense=0), got "
            f"n_batch={a.n_batch}, n_dense={a.n_dense}")
    return a


def density(a: jsparse.BCOO) -> float:
    """Static nnz fraction (``nse`` is static, so this is a python float)."""
    m, n = a.shape
    return a.nse / float(m * n)


def abs_degree_sums(a: jsparse.BCOO) -> tuple[jax.Array, jax.Array]:
    """Row/col sums of ``|A|`` — the bipartite degrees of Eq. 5, O(nnz)."""
    rows, cols = a.indices[:, 0], a.indices[:, 1]
    av = jnp.abs(a.data)
    d1 = jax.ops.segment_sum(av, rows, num_segments=a.shape[0])
    d2 = jax.ops.segment_sum(av, cols, num_segments=a.shape[1])
    return d1, d2


def scale_rows_cols(a: jsparse.BCOO, s1: jax.Array, s2: jax.Array) -> jsparse.BCOO:
    """``diag(s1) @ A @ diag(s2)`` without leaving BCOO (same sparsity)."""
    rows, cols = a.indices[:, 0], a.indices[:, 1]
    data = a.data * s1[rows] * s2[cols]
    return jsparse.BCOO((data, a.indices), shape=a.shape,
                        indices_sorted=a.indices_sorted,
                        unique_indices=a.unique_indices)


def gather_cols_dense(a: jsparse.BCOO, cols: jax.Array) -> jax.Array:
    """Dense ``A[:, cols]`` of shape ``(M, q)`` from a BCOO, O(nnz).

    This is the anchor-feature gather of the merge phase: ``q`` is tiny
    (``signature_dim``), so the output is a sliver — the full matrix is
    never densified. Columns outside ``cols`` scatter to an out-of-range
    sentinel and are dropped.
    """
    m, n = a.shape
    q = cols.shape[0]
    inv = jnp.full((n,), q, jnp.int32).at[cols].set(
        jnp.arange(q, dtype=jnp.int32))
    pc = inv[a.indices[:, 1]]
    out = jnp.zeros((m, q), a.data.dtype)
    return out.at[a.indices[:, 0], pc].add(a.data, mode="drop")


def gather_rows_dense(a: jsparse.BCOO, rows: jax.Array) -> jax.Array:
    """Dense ``A[rows, :]`` of shape ``(q, N)`` from a BCOO, O(nnz)."""
    m, n = a.shape
    q = rows.shape[0]
    inv = jnp.full((m,), q, jnp.int32).at[rows].set(
        jnp.arange(q, dtype=jnp.int32))
    pr = inv[a.indices[:, 0]]
    out = jnp.zeros((q, n), a.data.dtype)
    return out.at[pr, a.indices[:, 1]].add(a.data, mode="drop")


# ---------------------------------------------------------------------------
# Dual-ELL operator: gather-only SpMM for repeated products
# ---------------------------------------------------------------------------


class EllOperator(NamedTuple):
    """Padded-row (ELL) layout of a sparse matrix, in *both* orientations.

    A COO scatter (segment-sum) pays the scatter unit on every product;
    the subspace iteration multiplies by the same matrix ~10 times per
    SVD, so the sparse atom phase converts once and makes every product
    gather-only: ``out[i] = sum_w vals[i, w] * x[cols[i, w]]`` — dense
    einsum over a ``(M, W)`` layout, W = max nonzeros per row. Padding
    slots carry value 0 / index 0, contributing exactly nothing. The
    transpose orientation is precomputed (``col_*``) so ``A.T @ Q`` is
    the same gather-only product; nothing is resorted at product time.

    Built host-side (``to_ell``) because W is data-dependent; the arrays
    are an ordinary pytree, so the operator passes straight into jitted
    code (retracing only when W changes). Skewed rows inflate W toward N
    — ELL is the right layout for the quasi-uniform document-term
    sparsity the benchmarks model, not for power-law adjacency.
    """

    row_vals: jax.Array    # (M, W)  values, 0-padded
    row_cols: jax.Array    # (M, W)  column of each value, 0-padded
    col_vals: jax.Array    # (N, Wt) transpose orientation
    col_rows: jax.Array    # (N, Wt)

    # shape is derived, not a field: NamedTuple fields are pytree leaves,
    # and a (m, n) int tuple would turn into tracers under jit.
    @property
    def shape(self) -> tuple[int, int]:
        return self.row_vals.shape[0], self.col_vals.shape[0]

    @property
    def dtype(self):
        return self.row_vals.dtype


def is_ell(a) -> bool:
    return isinstance(a, EllOperator)


class _EllSidePlan(NamedTuple):
    """Pattern half of one ELL orientation: where each value lands."""

    r_sorted: np.ndarray   # (nnz,) destination row per sorted value
    slot: np.ndarray       # (nnz,) destination slot per sorted value
    order: np.ndarray      # (nnz,) stable sort permutation of the values
    ell_idx: jax.Array     # (m, width) gather indices (pattern-only)
    m: int
    width: int


class EllPlan(NamedTuple):
    """Reusable pattern half of a BCOO -> dual-ELL conversion.

    The ``core.opcache`` analogue of ``kernels.spmm.BlockSparsePlan``:
    both orientations' sort/slot layouts plus the (values-independent)
    gather-index grids, so a values refresh is two fancy scatters.
    """

    row: _EllSidePlan
    col: _EllSidePlan


def _ell_side(rows: np.ndarray, cols: np.ndarray, m: int) -> _EllSidePlan:
    counts = np.bincount(rows, minlength=m)
    width = max(int(counts.max()) if counts.size else 0, 1)
    order = np.argsort(rows, kind="stable")
    r_sorted = rows[order]
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(len(rows)) - starts[r_sorted]
    ell_idx = np.zeros((m, width), np.int32)
    ell_idx[r_sorted, slot] = cols[order]
    return _EllSidePlan(r_sorted=r_sorted, slot=slot, order=order,
                        ell_idx=jnp.asarray(ell_idx), m=m, width=width)


def _ell_side_vals(p: _EllSidePlan, vals: np.ndarray) -> jax.Array:
    ell_vals = np.zeros((p.m, p.width), np.float32)
    ell_vals[p.r_sorted, p.slot] = vals[p.order]
    return jnp.asarray(ell_vals)


def ell_plan(a: jsparse.BCOO) -> EllPlan:
    """Pattern half of the dual-ELL conversion (sorting, slots, widths)."""
    m, n = a.shape
    rows = np.asarray(a.indices[:, 0])
    cols = np.asarray(a.indices[:, 1])
    return EllPlan(row=_ell_side(rows, cols, m), col=_ell_side(cols, rows, n))


def ell_apply(plan: EllPlan, data) -> EllOperator:
    """Values half: scatter fresh values through a cached pattern plan."""
    vals = np.asarray(data, dtype=np.float32)
    return EllOperator(
        row_vals=_ell_side_vals(plan.row, vals), row_cols=plan.row.ell_idx,
        col_vals=_ell_side_vals(plan.col, vals), col_rows=plan.col.ell_idx,
    )


def to_ell(a: jsparse.BCOO, cache=None) -> EllOperator:
    """One-time host-side conversion BCOO -> dual-ELL (O(nnz)).

    With a ``core.opcache.PatternCache``, repeated conversions of the
    same sparsity pattern skip the sort/slot pattern pass (values-only
    refresh) or the whole conversion (same data object).
    """
    validate_bcoo(a)
    if cache is None:
        return ell_apply(ell_plan(a), a.data)
    return cache.convert(
        a, ("ell",),
        plan_fn=lambda x: ((p := ell_plan(x)), ell_apply(p, x.data)),
        apply_fn=ell_apply)


def ell_matvec(a: EllOperator, x: jax.Array) -> jax.Array:
    """``A @ x`` — gather rows of ``x``, one fused multiply-reduce."""
    return jnp.einsum("mw,mwr->mr", a.row_vals, x[a.row_cols])


def ell_rmatvec(a: EllOperator, x: jax.Array) -> jax.Array:
    """``A.T @ x`` via the precomputed transpose orientation."""
    return jnp.einsum("nw,nwr->nr", a.col_vals, x[a.col_rows])


def ell_abs_degree_sums(a: EllOperator) -> tuple[jax.Array, jax.Array]:
    """Bipartite degrees — padding is exact zero, so plain row sums."""
    return jnp.sum(jnp.abs(a.row_vals), 1), jnp.sum(jnp.abs(a.col_vals), 1)


def ell_scale_rows_cols(a: EllOperator, s1: jax.Array,
                        s2: jax.Array) -> EllOperator:
    """``diag(s1) @ A @ diag(s2)`` in ELL form (both orientations)."""
    return a._replace(
        row_vals=a.row_vals * s1[:, None] * s2[a.row_cols],
        col_vals=a.col_vals * s2[:, None] * s1[a.col_rows],
    )


# ---------------------------------------------------------------------------
# Tiled block-sparse operator: MXU-resident SpMM for repeated products
# ---------------------------------------------------------------------------


def is_tiled(a) -> bool:
    """True if ``a`` is a ``kernels.spmm.BlockSparseMatrix`` operand."""
    return isinstance(a, BlockSparseMatrix)


def to_tiled(a: jsparse.BCOO, bm: int = 128, bk: int = 128, *, cache=None):
    """One-time conversion BCOO -> tile-level block-sparse.

    The counterpart of ``to_ell`` for the MXU regime: only tiles holding
    nonzeros keep a dense payload, and every subsequent product is a
    batched ``(bm, bk) @ (bk, r)`` contraction (``kernels.ops.spmm_tiled``
    / the fused ``spmm_ata``) whose cost scales with *tile occupancy*
    instead of per-element gathers. Preferred above the dual-ELL
    crossover density (``probability.spmm_route``), where gather width
    makes ELL products nnz-bound. Runs as a jitted device scan/scatter
    on TPU and vectorized numpy elsewhere (``kernels.spmm``); a
    ``core.opcache.PatternCache`` makes repeat conversions of a stable
    sparsity pattern values-only (or free for an identical matrix).
    """
    from repro.kernels.spmm import (
        block_sparse_apply,
        block_sparse_plan,
    )

    validate_bcoo(a)

    def _plan_fn(x):
        plan = block_sparse_plan(x, bm=bm, bk=bk)
        return plan, block_sparse_apply(plan, x.data)

    if cache is None:
        return _plan_fn(a)[1]
    return cache.convert(a, ("tiled", bm, bk), plan_fn=_plan_fn,
                         apply_fn=block_sparse_apply)


def _tile_pad(v: jax.Array, tiles: int, width: int) -> jax.Array:
    """(L,) vector -> (tiles, width) grid view, zero-padded."""
    return jnp.pad(v, (0, tiles * width - v.shape[0])).reshape(tiles, width)


def tiled_abs_degree_sums(a) -> tuple[jax.Array, jax.Array]:
    """Bipartite degrees of Eq. 5 from the payload tiles, O(G * bm * bk)."""
    a = a.materialize_scales()  # degrees of the *effective* operator
    bm, bk = a.tile_shape
    n_tr, n_tc = a.n_tiles
    av = jnp.abs(a.blocks)
    d1 = jax.ops.segment_sum(jnp.sum(av, axis=2), a.block_rows,
                             num_segments=n_tr).reshape(n_tr * bm)
    d2 = jax.ops.segment_sum(jnp.sum(av, axis=1), a.block_cols,
                             num_segments=n_tc).reshape(n_tc * bk)
    return d1[: a.shape[0]], d2[: a.shape[1]]


def tiled_scale_rows_cols(a, s1: jax.Array, s2: jax.Array):
    """``diag(s1) @ A @ diag(s2)`` on the payload tiles (same tiling).

    Padding cells hold exact zeros, so the (arbitrary) padded scale
    entries multiply nothing.

    On the Pallas/interpret tiers the scales are attached *lazily*
    (``row_scale``/``col_scale`` grid views) and applied to each tile in
    VMEM by the SpMM kernels — the normalized operator never exists as a
    second block stack in HBM. The jnp tier folds them into the payloads
    here, eagerly: its tile reference has no fused variant, and an
    unfused lazy scale inside the subspace iteration's ``fori_loop``
    would be re-applied every iteration. Both forms use the identical
    multiply order, so results are bit-exact across tiers.
    """
    bm, bk = a.tile_shape
    n_tr, n_tc = a.n_tiles
    rs = _tile_pad(s1, n_tr, bm)                       # (n_tr, bm)
    cs = _tile_pad(s2, n_tc, bk)                       # (n_tc, bk)
    import repro.kernels.spmm as _spmm
    from repro.kernels import ops as _kops

    if _kops.tiled_scale_fusion():
        if a.row_scale is not None:                    # compose scalings
            rs = a.row_scale * rs
            cs = a.col_scale * cs
        return _spmm.BlockSparseMatrix(
            blocks=a.blocks, block_rows=a.block_rows,
            block_cols=a.block_cols, t_order=a.t_order, shape=a.shape,
            row_scale=rs, col_scale=cs)
    am = a.materialize_scales()
    s1t = rs[a.block_rows]                             # (G, bm)
    s2t = cs[a.block_cols]                             # (G, bk)
    return _spmm.BlockSparseMatrix(
        blocks=am.blocks * s1t[:, :, None] * s2t[:, None, :],
        block_rows=a.block_rows, block_cols=a.block_cols,
        t_order=a.t_order, shape=a.shape)


# ---------------------------------------------------------------------------
# SpMM backend selection
# ---------------------------------------------------------------------------

#: Valid values for the ``spmm_impl`` knob threaded through LAMCConfig /
#: StreamConfig -> scc/randomized_svd. ``auto`` resolves per matrix from
#: its nnz density (``probability.spmm_route``).
SPMM_IMPLS = ("auto", "dense", "dual_ell", "tiled")


def validate_spmm_impl(impl: str) -> str:
    """Shared guard for the ``spmm_impl`` knob — one message, every driver."""
    if impl not in SPMM_IMPLS:
        raise ValueError(
            f"spmm_impl must be one of {SPMM_IMPLS}, got {impl!r}")
    return impl


def prepare_operator(a: jsparse.BCOO, impl: str, *, bm: int = 128,
                     bk: int = 128, cache="default"):
    """Conversion of a BCOO matrix to the routed SpMM operand.

    ``impl`` must be a *resolved* route (``dense`` | ``dual_ell`` |
    ``tiled`` — resolve ``auto`` first via ``probability.spmm_route``).
    Conversions go through the process-wide pattern cache
    (``core.opcache``) by default, so the resample loop and streaming
    re-chunks that keep a sparsity pattern pay the pattern pass once and
    refresh values only (``cache=None`` bypasses; ``REPRO_TILED_CACHE=0``
    disables globally). ``dense`` returns the densified matrix (the
    caller decided sparsity is not worth the format).
    """
    from repro.core import opcache

    validate_bcoo(a)
    if cache == "default":
        cache = opcache.default_cache() if opcache.cache_enabled() else None
    if impl == "dense":
        return a.todense()
    if impl == "dual_ell":
        return to_ell(a, cache=cache)
    if impl == "tiled":
        return to_tiled(a, bm=bm, bk=bk, cache=cache)
    raise ValueError(
        f"impl must be a resolved route ('dense', 'dual_ell' or 'tiled'), "
        f"got {impl!r}")
