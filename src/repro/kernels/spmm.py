"""Pallas TPU kernels: tiled block-sparse SpMM family (DESIGN.md §9).

The sparse atom phase's hot matmuls are ``A @ Omega`` / ``A.T @ Q`` with
``A`` sparse and the other operand a tall-skinny dense sketch. A BCOO's
per-element indices cannot drive TPU DMA, so the kernels consume a
*tile-level* sparse format: ``A`` is cut into a ``(M/bm, K/bk)`` grid
and only tiles containing nonzeros are kept, as

  * ``blocks``     (G, bm, bk) f32 — dense payload of each surviving tile
  * ``block_rows`` (G,) i32        — tile-row of each payload, sorted
  * ``block_cols`` (G,) i32        — tile-col of each payload
  * ``t_order``    (G,) i32        — payload visit order for transposed
                                     products (sorted by tile-col)
  * ``row_scale``  (n_tr, bm) f32  — optional per-row scale, applied to
    ``col_scale``  (n_tc, bk) f32    the tile *inside* the kernel so a
                                     normalized operator is never
                                     materialized as a second block stack

Conversion runs in two stages so the build is jittable (DESIGN.md §9):
the surviving-tile count ``G`` is data-dependent, so stage 1
(:func:`block_sparse_pattern_device`) reduces the nonzeros to a tile
occupancy bitmap whose population count is the *only* scalar synced to
the host; stage 2 (:func:`block_sparse_build_device`, static ``G``)
derives the tile id list by a prefix-scan over the bitmap and scatters
every value by a precomputed flat offset. Scanning the (small) tile-id
space instead of segment-sorting the nonzeros drops the O(nnz log nnz)
sort entirely — segment boundaries come from ``cumsum(occupancy)``, the
scan analogue of the shifted-compare trick on sorted ids. Off-TPU the
same plan/apply split runs as a vectorized numpy path
(:func:`block_sparse_plan`); ``bcoo_to_block_sparse_host`` keeps the
original union1d/lexsort formulation as the bit-exact oracle for both.
The plan (pattern work) and apply (value scatter) stages are separable
so the pattern-keyed conversion cache (``core.opcache``) can refresh
values only when a resample or re-chunk reuses a sparsity pattern.

Three kernels share the format:

``spmm_pallas``      ``A @ B``: grid ``(N/bn, G)`` — payloads innermost,
    so consecutive steps that share a tile-row revisit the *same* output
    block while it is resident in VMEM. ``block_rows``/``block_cols``
    ride in as scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``)
    so the index maps can route each payload's B-tile and out-tile before
    the body runs. The output block is zeroed exactly when the tile-row
    changes; the converter seeds every tile-row with at least one payload
    so every output block is visited and initialized.

``spmm_t_pallas``    ``A.T @ B``: the same sweep driven through
    ``t_order`` — payloads visited in tile-col order so the transposed
    product enjoys the identical out-block residency property. The
    converter seeds every tile-*col* too, so both orientations have all
    output tiles initialized.

``spmm_ata_pallas``  fused normal-equations pass ``A.T @ (A @ X)``: one
    kernel launch whose grid sweeps the tile list once per phase
    (``grid = (N/bn, 2, G)``). Phase 0 accumulates the intermediate
    ``Y = A @ X`` stripe into a VMEM scratch; phase 1 streams the same
    payloads again and applies ``out[col] += B.T @ Y[row]`` against the
    still-resident scratch. ``Y`` never round-trips through HBM and the
    two products cost one launch instead of two. With ``with_gram=True``
    the launch is a full fused *subspace-iteration step*: after the last
    payload, the ``(r, r)`` Gram ``Z.T @ Z`` of the still-resident output
    stripe is emitted as a second output, so the CholeskyQR
    orthonormalization (``core.spectral._orth_from_gram``) needs no
    extra pass over ``Z`` — SpMM, Gram and the Cholesky factor's operand
    all come out of one launch.

When ``row_scale``/``col_scale`` are attached (``normalize_bipartite``
on the Pallas tiers), each kernel rescales the payload tile in VMEM as
``tile * rs[:, None] * cs[None, :]`` — the exact multiply order of the
materialized ``tiled_scale_rows_cols`` path, so results stay bit-exact
while ``D_r^{-1/2} A D_c^{-1/2}`` costs no second HBM-resident operator.

Compute per grid step is one ``(bm, bk) @ (bk, bn)`` MXU contraction —
identical to a dense matmul kernel's inner step; the win is skipping the
empty tiles entirely: FLOPs and HBM traffic scale with the *tile-level*
occupancy instead of ``M*K``.

Like every kernel here they run under ``interpret=True`` off-TPU; the
semantics oracles are ``ref.spmm_ref`` (element-level segment-sum) and
``ref.spmm_block_ref`` (tile-level, also the fast jnp CPU path).
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["BlockSparseMatrix", "BlockSparsePlan", "bcoo_to_block_sparse",
           "bcoo_to_block_sparse_host", "block_sparse_plan",
           "block_sparse_apply", "block_sparse_pattern_device",
           "block_sparse_build_device", "spmm_pallas", "spmm_t_pallas",
           "spmm_ata_pallas"]


@jax.tree_util.register_pytree_node_class
class BlockSparseMatrix:
    """Tile-level sparse operand for the SpMM kernels.

    A registered pytree whose logical ``shape`` is static aux data, so the
    operand passes through ``jit``/``scan`` boundaries with ``.shape``
    usable for Python-level shape math (the same reason
    ``sparse.EllOperator`` derives its shape instead of storing it).

    ``row_scale``/``col_scale`` (optional, attached together) carry a
    pending diagonal scaling ``diag(rs) @ A @ diag(cs)`` as ``(n_tr, bm)``
    / ``(n_tc, bk)`` grid views. On the Pallas tiers the SpMM kernels
    apply them to the payload tile in VMEM; :meth:`materialize_scales`
    folds them into ``blocks`` (the jnp-tier / oracle form).
    """

    def __init__(self, blocks, block_rows, block_cols, t_order, shape,
                 row_scale=None, col_scale=None):
        self.blocks = blocks            # (G, bm, bk) dense tile payloads
        self.block_rows = block_rows    # (G,) i32 tile-row ids, sorted
        self.block_cols = block_cols    # (G,) i32 tile-col ids
        self.t_order = t_order          # (G,) i32, payloads in tile-col order
        self.shape = tuple(shape)       # logical (M, K) — unpadded, static
        self.row_scale = row_scale      # (n_tr, bm) f32 or None
        self.col_scale = col_scale      # (n_tc, bk) f32 or None

    @property
    def tile_shape(self) -> tuple[int, int]:
        return self.blocks.shape[1], self.blocks.shape[2]

    @property
    def n_tiles(self) -> tuple[int, int]:
        """Tile-grid shape ``(M/bm, K/bk)`` (ceil)."""
        bm, bk = self.tile_shape
        return -(-self.shape[0] // bm), -(-self.shape[1] // bk)

    @property
    def dtype(self):
        return self.blocks.dtype

    @property
    def has_scales(self) -> bool:
        return self.row_scale is not None

    def materialize_scales(self) -> "BlockSparseMatrix":
        """Fold pending scales into the payload stack (one new block stack).

        Multiply order matches the scale-fused kernels exactly
        (``blk * rs[:, None] * cs[None, :]``), so the lazy and
        materialized operators are bit-identical under every product.
        """
        if self.row_scale is None:
            return self
        rs = self.row_scale[self.block_rows]            # (G, bm)
        cs = self.col_scale[self.block_cols]            # (G, bk)
        return BlockSparseMatrix(
            blocks=self.blocks * rs[:, :, None] * cs[:, None, :],
            block_rows=self.block_rows, block_cols=self.block_cols,
            t_order=self.t_order, shape=self.shape)

    def tree_flatten(self):
        return ((self.blocks, self.block_rows, self.block_cols,
                 self.t_order, self.row_scale, self.col_scale), self.shape)

    @classmethod
    def tree_unflatten(cls, shape, children):
        blocks, block_rows, block_cols, t_order, row_scale, col_scale = children
        return cls(blocks, block_rows, block_cols, t_order, shape=shape,
                   row_scale=row_scale, col_scale=col_scale)


class BlockSparsePlan(NamedTuple):
    """Reusable pattern half of a BCOO -> block-sparse conversion.

    Everything derived from the *indices* alone: the surviving-tile list,
    the transposed visit order, and the per-nonzero flat scatter offset
    into the ``(G * bm * bk,)`` payload stack. ``block_sparse_apply``
    turns a plan plus a values vector into a ``BlockSparseMatrix`` — the
    values-only refresh path the pattern cache (``core.opcache``) takes
    when a matrix keeps its sparsity pattern across resamples.
    """

    block_rows: jax.Array       # (G,) i32, sorted
    block_cols: jax.Array       # (G,) i32
    t_order: jax.Array          # (G,) i32
    flat_idx: object            # (nnz,) scatter offsets — np i64 or jnp i32
    g: int                      # surviving tile count (static)
    bm: int
    bk: int
    shape: tuple[int, int]
    on_device: bool             # True -> jitted apply, False -> numpy apply


def bcoo_to_block_sparse_host(a, bm: int = 128,
                              bk: int = 128) -> BlockSparseMatrix:
    """Original host-side conversion — the bit-exact oracle.

    O(nnz) numpy (union1d over tile ids + fancy scatter); retained as the
    semantics reference for the fast plan/apply host path and the jitted
    device path, both tested field-for-field against it. Empty tile-rows
    get one zero payload (tile-col 0) and empty tile-cols one zero
    payload (tile-row 0) so both product orientations initialize every
    output block. Rows are padded up to a ``bm`` multiple, cols to ``bk``.
    """
    m, k = a.shape
    rows = np.asarray(a.indices[:, 0]).astype(np.int64)
    cols = np.asarray(a.indices[:, 1]).astype(np.int64)
    vals = np.asarray(a.data, dtype=np.float32)
    n_tr, n_tc = -(-m // bm), -(-k // bk)
    # linearized tile ids; seed every tile-row with (row, col 0) and every
    # tile-col with (row 0, col) so each output block of either product
    # orientation gets initialized even when its tile-row/-col is empty
    tile_of_nnz = (rows // bm) * n_tc + cols // bk
    seeds = np.concatenate([np.arange(n_tr, dtype=np.int64) * n_tc,
                            np.arange(n_tc, dtype=np.int64)])
    tile_ids = np.union1d(tile_of_nnz, seeds)
    g_of = np.searchsorted(tile_ids, tile_of_nnz)
    blocks = np.zeros((len(tile_ids), bm, bk), np.float32)
    blocks[g_of, rows % bm, cols % bk] = vals
    tile_rows = tile_ids // n_tc
    tile_cols = tile_ids % n_tc
    t_order = np.lexsort((tile_rows, tile_cols))  # tile-col-major visit order
    return BlockSparseMatrix(
        blocks=jnp.asarray(blocks),
        block_rows=jnp.asarray(tile_rows, jnp.int32),
        block_cols=jnp.asarray(tile_cols, jnp.int32),
        t_order=jnp.asarray(t_order, jnp.int32),
        shape=(m, k),
    )


def _plan_host(a, bm: int, bk: int) -> BlockSparsePlan:
    """Fast numpy pattern pass: occupancy bitmap + prefix-scan.

    Same tile list and ordering as the union1d oracle — the sorted unique
    tile ids *are* ``flatnonzero`` of the occupancy bitmap — without the
    O(nnz log nnz) sort union1d pays.
    """
    m, k = a.shape
    rows = np.asarray(a.indices[:, 0]).astype(np.int64)
    cols = np.asarray(a.indices[:, 1]).astype(np.int64)
    n_tr, n_tc = -(-m // bm), -(-k // bk)
    tile_of_nnz = (rows // bm) * n_tc + cols // bk
    occ = np.zeros(n_tr * n_tc, np.bool_)
    occ[tile_of_nnz] = True
    occ[np.arange(n_tr, dtype=np.int64) * n_tc] = True   # tile-row seeds
    occ[:n_tc] = True                                    # tile-col seeds
    lut = np.cumsum(occ, dtype=np.int64) - 1             # tile id -> g
    g = int(lut[-1]) + 1
    flat_idx = lut[tile_of_nnz] * (bm * bk) + (rows % bm) * bk + (cols % bk)
    tile_ids = np.flatnonzero(occ)
    tile_rows = tile_ids // n_tc
    tile_cols = tile_ids % n_tc
    t_order = np.lexsort((tile_rows, tile_cols))
    return BlockSparsePlan(
        block_rows=jnp.asarray(tile_rows, jnp.int32),
        block_cols=jnp.asarray(tile_cols, jnp.int32),
        t_order=jnp.asarray(t_order, jnp.int32),
        flat_idx=flat_idx, g=g, bm=bm, bk=bk, shape=(m, k), on_device=False)


@functools.partial(jax.jit, static_argnames=("n_tr", "n_tc", "bm", "bk"))
def block_sparse_pattern_device(rows: jax.Array, cols: jax.Array,
                                n_tr: int, n_tc: int, bm: int, bk: int):
    """Conversion stage 1 (jittable): tile occupancy bitmap + its popcount.

    The popcount is the single data-dependent scalar of the whole
    conversion — the wrapper syncs it once to fix the static ``G`` of
    stage 2.
    """
    tile_of = (rows // bm) * n_tc + (cols // bk)
    occ = jnp.zeros((n_tr * n_tc,), jnp.int32).at[tile_of].max(1)
    occ = occ.at[jnp.arange(n_tr) * n_tc].max(1)         # tile-row seeds
    occ = occ.at[jnp.arange(n_tc)].max(1)                # tile-col seeds
    return occ, jnp.sum(occ)


@functools.partial(jax.jit, static_argnames=("g", "n_tc", "bm", "bk"))
def block_sparse_build_device(rows: jax.Array, cols: jax.Array,
                              vals: jax.Array, occ: jax.Array,
                              g: int, n_tc: int, bm: int, bk: int):
    """Conversion stage 2 (jittable, static ``G``): scan + scatter.

    ``cumsum(occ) - 1`` is the segment scan that maps every tile id to
    its payload slot; values land by one flat scatter (indices unique by
    the BCOO contract). Returns the block stack, tile coordinates, the
    tile-col-major visit order and the reusable flat scatter offsets.
    """
    lut = jnp.cumsum(occ) - 1
    tile_of = (rows // bm) * n_tc + (cols // bk)
    flat_idx = lut[tile_of] * (bm * bk) + (rows % bm) * bk + (cols % bk)
    blocks = jnp.zeros((g * bm * bk,), jnp.float32).at[flat_idx].set(
        vals.astype(jnp.float32), unique_indices=True).reshape(g, bm, bk)
    tile_ids = jnp.nonzero(occ, size=g)[0].astype(jnp.int32)
    tile_rows = tile_ids // n_tc
    tile_cols = tile_ids % n_tc
    # unique ids are already row-major sorted, so a stable sort by
    # tile-col alone reproduces lexsort((tile_rows, tile_cols)) exactly
    t_order = jnp.argsort(tile_cols, stable=True).astype(jnp.int32)
    return blocks, tile_rows, tile_cols, t_order, flat_idx


@functools.partial(jax.jit, static_argnames=("g", "bm", "bk"))
def _apply_device(flat_idx: jax.Array, vals: jax.Array,
                  g: int, bm: int, bk: int) -> jax.Array:
    return jnp.zeros((g * bm * bk,), jnp.float32).at[flat_idx].set(
        vals.astype(jnp.float32), unique_indices=True).reshape(g, bm, bk)


def _device_conversion() -> bool:
    """Device path on TPU (and under the interpret-CI switch); numpy path
    on CPU, where XLA's serial scatter loses to the vectorized host
    scatter (measured ~2.5x at the bench shapes)."""
    if os.environ.get("REPRO_FORCE_INTERPRET"):
        return True
    return jax.default_backend() == "tpu"


def block_sparse_plan(a, bm: int = 128, bk: int = 128) -> BlockSparsePlan:
    """Pattern half of the conversion (dispatching device/host).

    One host sync of the surviving-tile popcount on the device path —
    ``G`` must be static for the stage-2 jit and the kernel grids.
    """
    m, k = a.shape
    n_tr, n_tc = -(-m // bm), -(-k // bk)
    if not _device_conversion() or n_tr * n_tc * bm * bk >= 2**31:
        # second clause: the i32 flat offsets of the device build would
        # overflow — the host plan carries i64 offsets
        return _plan_host(a, bm, bk)
    rows = a.indices[:, 0]
    cols = a.indices[:, 1]
    occ, count = block_sparse_pattern_device(rows, cols, n_tr, n_tc, bm, bk)
    g = int(count)                                       # the one host sync
    _, tile_rows, tile_cols, t_order, flat_idx = block_sparse_build_device(
        rows, cols, a.data, occ, g, n_tc, bm, bk)
    return BlockSparsePlan(block_rows=tile_rows, block_cols=tile_cols,
                           t_order=t_order, flat_idx=flat_idx, g=g, bm=bm,
                           bk=bk, shape=(m, k), on_device=True)


def block_sparse_apply(plan: BlockSparsePlan, data) -> BlockSparseMatrix:
    """Values half of the conversion: scatter ``data`` through a plan.

    This is the whole cost of a pattern-cache values refresh — no tile
    discovery, no sort, just one flat scatter sized by nnz.
    """
    bm, bk = plan.bm, plan.bk
    if plan.on_device:
        blocks = _apply_device(plan.flat_idx, data, plan.g, bm, bk)
    else:
        flat = np.zeros(plan.g * bm * bk, np.float32)
        flat[plan.flat_idx] = np.asarray(data, dtype=np.float32)
        blocks = jnp.asarray(flat.reshape(plan.g, bm, bk))
    return BlockSparseMatrix(blocks=blocks, block_rows=plan.block_rows,
                             block_cols=plan.block_cols,
                             t_order=plan.t_order, shape=plan.shape)


def bcoo_to_block_sparse(a, bm: int = 128, bk: int = 128) -> BlockSparseMatrix:
    """Tile a BCOO matrix, keeping only tiles with nonzeros.

    Two-stage plan/apply conversion: jitted on-device scan + scatter on
    TPU (one scalar sync for the surviving-tile count), vectorized numpy
    off-TPU. Bit-exact against :func:`bcoo_to_block_sparse_host` on both
    paths. Callers that convert the same sparsity pattern repeatedly
    should go through ``core.sparse.to_tiled``, which adds the
    pattern-keyed cache (``core.opcache``) on top of this.
    """
    return block_sparse_apply(block_sparse_plan(a, bm=bm, bk=bk), a.data)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _tile(blk_ref, rs_ref, cs_ref, g):
    """Payload tile ``g`` with any pending diagonal scales applied in VMEM.

    The multiply order (row scale, then col scale) matches
    ``BlockSparseMatrix.materialize_scales`` exactly — the fused and
    materialized operators stay bit-identical.
    """
    tile = blk_ref[0]
    if rs_ref is not None:
        tile = tile * rs_ref[0, 0][:, None] * cs_ref[0, 0][None, :]
    return tile


def _scale_views(row_scale, col_scale):
    """``(n_tr, 1, bm)`` / ``(n_tc, 1, bk)`` views of the pending scales.

    Each grid step reads one tile-row's ``bm`` scales. As a ``(1, bm)``
    block of the 2-D ``(n_tr, bm)`` array that breaks the TPU's (8, 128)
    block rule, and the chip's compiler refuses it; a ``(1, 1, bm)``
    block of the 3-D view is legal, since its last two dims equal the
    array's.
    """
    return row_scale[:, None, :], col_scale[:, None, :]


def _kernel(*refs, scaled: bool):
    if scaled:
        rows_ref, cols_ref, blk_ref, rs_ref, cs_ref, b_ref, out_ref = refs
    else:
        rows_ref, cols_ref, blk_ref, b_ref, out_ref = refs
        rs_ref = cs_ref = None
    g = pl.program_id(1)
    # New tile-row (payloads are row-sorted) -> fresh output block.
    first = jnp.logical_or(g == 0,
                           rows_ref[g] != rows_ref[jnp.maximum(g - 1, 0)])

    @pl.when(first)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot(
        _tile(blk_ref, rs_ref, cs_ref, g), b_ref[...],
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("m_out", "bn", "interpret"))
def spmm_pallas(
    block_rows: jax.Array,   # (G,) i32, sorted
    block_cols: jax.Array,   # (G,) i32
    blocks: jax.Array,       # (G, bm, bk) f32
    b: jax.Array,            # (K_padded, N_padded) dense rhs
    m_out: int,              # padded output rows (n_tile_rows * bm)
    bn: int = 128,
    interpret: bool = False,
    row_scale: jax.Array | None = None,   # (n_tr, bm) f32
    col_scale: jax.Array | None = None,   # (n_tc, bk) f32
) -> jax.Array:
    """Raw kernel invocation: ``out (m_out, N) = A_blocksparse @ b``.

    Use ``repro.kernels.ops.spmm_tiled`` for the shape-safe wrapper
    (padding, unpadding, backend dispatch). When scales are given the
    payload tile is rescaled in VMEM before the contraction.
    """
    g_total, bm, bk = blocks.shape
    _, n = b.shape
    grid = (n // bn, g_total)
    scaled = row_scale is not None
    in_specs = [pl.BlockSpec((1, bm, bk), lambda j, g, rows, cols: (g, 0, 0))]
    operands = [blocks]
    if scaled:
        in_specs += [
            pl.BlockSpec((1, 1, bm), lambda j, g, rows, cols: (rows[g], 0, 0)),
            pl.BlockSpec((1, 1, bk), lambda j, g, rows, cols: (cols[g], 0, 0)),
        ]
        operands += _scale_views(row_scale, col_scale)
    in_specs.append(
        pl.BlockSpec((bk, bn), lambda j, g, rows, cols: (cols[g], j)))
    operands.append(b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda j, g, rows, cols: (rows[g], j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, scaled=scaled),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_out, n), jnp.float32),
        interpret=interpret,
        name="spmm",
    )(block_rows, block_cols, *operands)


def _kernel_t(*refs, scaled: bool):
    if scaled:
        rows_ref, cols_ref, order_ref, blk_ref, rs_ref, cs_ref, b_ref, out_ref = refs
    else:
        rows_ref, cols_ref, order_ref, blk_ref, b_ref, out_ref = refs
        rs_ref = cs_ref = None
    g = pl.program_id(1)
    # Payloads are visited in tile-col order (order_ref): a new tile-col
    # means a fresh output block, mirroring the row-sorted forward sweep.
    here = cols_ref[order_ref[g]]
    prev = cols_ref[order_ref[jnp.maximum(g - 1, 0)]]
    first = jnp.logical_or(g == 0, here != prev)

    @pl.when(first)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    # (bm, bk).T @ (bm, bn): contract the sublane (row) dim of the payload.
    out_ref[...] += jax.lax.dot_general(
        _tile(blk_ref, rs_ref, cs_ref, g), b_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("k_out", "bn", "interpret"))
def spmm_t_pallas(
    block_rows: jax.Array,   # (G,) i32, sorted by tile-row
    block_cols: jax.Array,   # (G,) i32
    t_order: jax.Array,      # (G,) i32 — payload visit order, tile-col major
    blocks: jax.Array,       # (G, bm, bk) f32
    b: jax.Array,            # (M_padded, N_padded) dense rhs
    k_out: int,              # padded output rows (n_tile_cols * bk)
    bn: int = 128,
    interpret: bool = False,
    row_scale: jax.Array | None = None,   # (n_tr, bm) f32
    col_scale: jax.Array | None = None,   # (n_tc, bk) f32
) -> jax.Array:
    """Raw transposed product: ``out (k_out, N) = A_blocksparse.T @ b``.

    The scalar-prefetched ``t_order`` permutation re-sorts the sweep by
    tile-col without materializing a transposed payload copy: the DMA
    engine fetches ``blocks[t_order[g]]`` and the MXU contracts its row
    dimension against the matching tile-row of ``b``.
    """
    g_total, bm, bk = blocks.shape
    _, n = b.shape
    grid = (n // bn, g_total)
    scaled = row_scale is not None
    in_specs = [pl.BlockSpec((1, bm, bk),
                             lambda j, g, rows, cols, order: (order[g], 0, 0))]
    operands = [blocks]
    if scaled:
        in_specs += [
            pl.BlockSpec((1, 1, bm),
                         lambda j, g, rows, cols, order: (rows[order[g]], 0, 0)),
            pl.BlockSpec((1, 1, bk),
                         lambda j, g, rows, cols, order: (cols[order[g]], 0, 0)),
        ]
        operands += _scale_views(row_scale, col_scale)
    in_specs.append(
        pl.BlockSpec((bm, bn),
                     lambda j, g, rows, cols, order: (rows[order[g]], j)))
    operands.append(b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (bk, bn), lambda j, g, rows, cols, order: (cols[order[g]], j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel_t, scaled=scaled),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k_out, n), jnp.float32),
        interpret=interpret,
        name="spmm_t",
    )(block_rows, block_cols, t_order, *operands)


def _kernel_ata(*refs, scaled: bool, with_gram: bool):
    if scaled:
        rows_ref, cols_ref, blk_ref, rs_ref, cs_ref, x_ref, *outs = refs
    else:
        rows_ref, cols_ref, blk_ref, x_ref, *outs = refs
        rs_ref = cs_ref = None
    if with_gram:
        out_ref, gram_ref, y_ref = outs
    else:
        out_ref, y_ref = outs
    p = pl.program_id(1)
    g = pl.program_id(2)
    bm = blk_ref.shape[1]
    bk = blk_ref.shape[2]

    @pl.when(jnp.logical_and(p == 0, g == 0))
    def _init():
        # fresh column stripe: clear the Y scratch and the output stripe
        y_ref[...] = jnp.zeros_like(y_ref)
        out_ref[...] = jnp.zeros_like(out_ref)

    tile = _tile(blk_ref, rs_ref, cs_ref, g)

    @pl.when(p == 0)
    def _forward():
        # phase 0: Y[row] += B @ X[col] — the whole Y stripe lives in VMEM
        y_ref[pl.ds(rows_ref[g] * bm, bm), :] += jax.lax.dot(
            tile, x_ref[...], preferred_element_type=jnp.float32)

    @pl.when(p == 1)
    def _backward():
        # phase 1: out[col] += B.T @ Y[row] against the resident scratch
        out_ref[pl.ds(cols_ref[g] * bk, bk), :] += jax.lax.dot_general(
            tile, y_ref[pl.ds(rows_ref[g] * bm, bm), :],
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if with_gram:
        @pl.when(jnp.logical_and(p == 1, g == pl.num_programs(2) - 1))
        def _gram():
            # last payload applied: the (k_pad, bn) output stripe is final
            # and still resident — emit its (bn, bn) Gram without another
            # HBM pass (the CholeskyQR operand of the fused subspace step)
            gram_ref[...] = jax.lax.dot_general(
                out_ref[...], out_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("m_pad", "bn", "interpret", "with_gram"))
def spmm_ata_pallas(
    block_rows: jax.Array,   # (G,) i32, sorted by tile-row
    block_cols: jax.Array,   # (G,) i32
    blocks: jax.Array,       # (G, bm, bk) f32
    x: jax.Array,            # (K_padded, N_padded) dense sketch
    m_pad: int,              # padded intermediate rows (n_tile_rows * bm)
    bn: int = 128,
    interpret: bool = False,
    row_scale: jax.Array | None = None,   # (n_tr, bm) f32
    col_scale: jax.Array | None = None,   # (n_tc, bk) f32
    with_gram: bool = False,
):
    """Raw fused normal-equations pass: ``out = A.T @ (A @ x)``.

    One launch; the ``(m_pad, bn)`` intermediate ``Y = A @ x`` stripe is a
    VMEM scratch that never reaches HBM. Both the ``Y`` stripe and the
    ``(k_pad, bn)`` output stripe must fit VMEM — the ops wrapper falls
    back to two kernel launches for operands past that budget.

    ``with_gram=True`` (single column stripe only: ``x.shape[1] == bn``)
    additionally returns the ``(bn, bn)`` Gram ``out.T @ out`` computed
    from the still-resident output stripe — the fused subspace-iteration
    step. Returns ``out`` or ``(out, gram)``.
    """
    g_total, bm, bk = blocks.shape
    k_pad, n = x.shape
    if with_gram and n != bn:
        raise ValueError(
            f"fused Gram needs a single column stripe (n == bn), got "
            f"n={n}, bn={bn}")
    grid = (n // bn, 2, g_total)
    scaled = row_scale is not None
    in_specs = [pl.BlockSpec((1, bm, bk),
                             lambda j, p, g, rows, cols: (g, 0, 0))]
    operands = [blocks]
    if scaled:
        in_specs += [
            pl.BlockSpec((1, 1, bm),
                         lambda j, p, g, rows, cols: (rows[g], 0, 0)),
            pl.BlockSpec((1, 1, bk),
                         lambda j, p, g, rows, cols: (cols[g], 0, 0)),
        ]
        operands += _scale_views(row_scale, col_scale)
    in_specs.append(
        pl.BlockSpec((bk, bn), lambda j, p, g, rows, cols: (cols[g], j)))
    operands.append(x)
    # one whole-stripe output block: resident for the full (p, g) sweep,
    # so phase-1 accumulation never depends on out-block revisit order
    out_specs = pl.BlockSpec((k_pad, bn), lambda j, p, g, rows, cols: (0, j))
    out_shape = jax.ShapeDtypeStruct((k_pad, n), jnp.float32)
    if with_gram:
        out_specs = [out_specs,
                     pl.BlockSpec((bn, bn), lambda j, p, g, rows, cols: (0, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct((bn, bn), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((m_pad, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel_ata, scaled=scaled, with_gram=with_gram),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="spmm_ata",
    )(block_rows, block_cols, *operands)
