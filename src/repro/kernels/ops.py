"""Public jit'd wrappers around the Pallas kernels.

Each op:
  * pads inputs to hardware-aligned tile multiples (MXU wants multiples of
    128 in the contracted/lane dims; sublane multiples of 8 for f32),
  * handles semantic edge cases the raw kernels don't (centroid-count
    sentinels, GQA head expansion, unpadding),
  * dispatches: real Pallas lowering on TPU, ``interpret=True`` elsewhere
    (the kernel body executes on CPU — used by the test suite), or the
    pure-jnp reference for very small inputs where padding overhead
    dominates.

Set ``REPRO_FORCE_INTERPRET=1`` to force interpret mode on any backend —
a switch for the kernel tests, never for a run that measures the chip.

Every wrapper records which tier it dispatched to via
``obs.kernel_dispatch`` (a labeled counter + optional trace event). The
hook runs at *trace time* with static values only — under jit it counts
compiled dispatch decisions, not executions — so it adds nothing to the
lowered program (the obs-enabled jaxpr-audit entries pin this).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro import obs as _obs
from repro.analysis import vmem

from . import ref
from .bipartite_normalize import scale_apply_pallas
from .flash_attention import flash_attention_pallas
from .kmeans_assign import cosine_assign_pallas, cosine_topk_pallas, kmeans_assign_pallas
from .kmeans_update import kmeans_update_pallas
from .spmm import (
    BlockSparseMatrix,
    bcoo_to_block_sparse,
    spmm_ata_pallas,
    spmm_pallas,
    spmm_t_pallas,
)

__all__ = ["kmeans_assign", "kmeans_update", "cosine_assign", "cosine_topk",
           "bipartite_normalize", "flash_attention", "spmm", "sddmm",
           "spmm_tiled", "spmm_ata", "BlockSparseMatrix",
           "bcoo_to_block_sparse", "tiled_scale_fusion"]


def _interpret() -> bool:
    if os.environ.get("REPRO_FORCE_INTERPRET"):
        return True
    return jax.default_backend() != "tpu"


def _tiled_backend() -> str:
    """Dispatch tier for the tile-level SpMM family.

    ``interpret`` when forced (kernel correctness CI — like
    ``_interpret``, the env switch wins on any backend), ``pallas`` on
    TPU (real lowering), ``jnp`` otherwise: off-TPU the interpret-mode
    grid loop is a correctness tool, not an execution path, so
    production CPU calls use the batched-einsum tile reference
    (``ref.spmm_block_ref``) — same semantics, BLAS-speed.
    """
    if os.environ.get("REPRO_FORCE_INTERPRET"):
        return "interpret"
    if jax.default_backend() == "tpu":
        return "pallas"
    return "jnp"


def tiled_scale_fusion() -> bool:
    """True when the current tiled backend applies pending diagonal
    scales inside the kernels (pallas / interpret tiers).

    ``core.sparse.tiled_scale_rows_cols`` consults this to decide between
    attaching lazy scales (kernel-fused, zero extra HBM) and eagerly
    materializing the scaled block stack (the jnp tier, where the tile
    reference has no fused variant and re-scaling per product inside a
    ``fori_loop`` body would repeat the work every iteration).
    """
    return _tiled_backend() != "jnp"


def _pad_to(x: jax.Array, axis: int, multiple: int, value=0.0) -> jax.Array:
    size = x.shape[axis]
    target = ((size + multiple - 1) // multiple) * multiple
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def kmeans_assign(x: jax.Array, centroids: jax.Array,
                  tile_p: int = 512) -> tuple[jax.Array, jax.Array]:
    """Tiled nearest-centroid assignment. x: (P, D); centroids: (K, D).

    Padded centroids are +1e6 sentinels — farther than any real centroid,
    so argmin never selects them; padded points are sliced off the output.
    """
    p, d = x.shape
    _obs.kernel_dispatch(
        "kmeans_assign", "interpret" if _interpret() else "pallas")
    xp = _pad_to(_pad_to(x, 1, 128), 0, tile_p)
    cp = _pad_to(_pad_to(centroids, 1, 128), 0, 8, value=1e6)
    labels, d2 = kmeans_assign_pallas(xp, cp, tile_p=tile_p, interpret=_interpret())
    return labels[0, :p], d2[0, :p]


def cosine_assign(x: jax.Array, signatures: jax.Array,
                  tile_p: int = 512) -> tuple[jax.Array, jax.Array]:
    """Batched signature scoring: argmax of ``x @ signatures.T``.

    The online-serving hot path (``streaming.assign_rows`` /
    ``assign_cols``): score incoming vectors against the fitted model's
    unit-normalized cluster signatures. x: (P, D); signatures: (K, D).
    Padded signature rows are zeros and masked to -inf inside the kernel
    (static ``k_valid``), so they can never be selected; padded points
    are sliced off the output. Returns ``(labels (P,), score (P,))``.
    """
    p, d = x.shape
    k = signatures.shape[0]
    _obs.kernel_dispatch(
        "cosine_assign", "interpret" if _interpret() else "pallas")
    xp = _pad_to(_pad_to(x, 1, 128), 0, tile_p)
    sp = _pad_to(_pad_to(signatures, 1, 128), 0, 8)
    labels, score = cosine_assign_pallas(
        xp, sp, k_valid=k, tile_p=tile_p, interpret=_interpret())
    return labels[0, :p], score[0, :p]


def cosine_topk(x: jax.Array, signatures: jax.Array, k: int,
                tile_p: int = 512) -> tuple[jax.Array, jax.Array]:
    """Top-``k`` signature scoring: the multi-assignment serving variant
    of :func:`cosine_assign` (DESIGN.md §11).

    Returns ``(labels (P, k), scores (P, k))`` ordered by descending
    score, ties toward the lower cluster id (matching ``jax.lax.top_k``
    and the k=1 ``cosine_assign`` argmax exactly). ``k`` must not exceed
    the number of real signature rows — padded rows are masked to -inf
    and must never surface in a top-k slot.
    """
    p, d = x.shape
    n_sigs = signatures.shape[0]
    if not 1 <= k <= n_sigs:
        raise ValueError(
            f"top-k width must be in [1, {n_sigs}] (the signature count), "
            f"got k={k}")
    _obs.kernel_dispatch(
        "cosine_topk", "interpret" if _interpret() else "pallas")
    xp = _pad_to(_pad_to(x, 1, 128), 0, tile_p)
    sp = _pad_to(_pad_to(signatures, 1, 128), 0, 8)
    labels, scores = cosine_topk_pallas(
        xp, sp, k_valid=n_sigs, k_top=k, tile_p=tile_p,
        interpret=_interpret())
    return labels[:p], scores[:p]


def kmeans_update(x: jax.Array, centroids: jax.Array,
                  weights: jax.Array | None = None,
                  tile_p: int = 512) -> tuple[jax.Array, jax.Array,
                                              jax.Array, jax.Array]:
    """Fused one-pass Lloyd iteration. x: (P, D); centroids: (K, D).

    Returns ``(labels (P,), d2 (P,), sums (K, D) f32, counts (K,) f32)``
    matching ``ref.kmeans_update_ref``. Padded centroids are +1e6
    sentinels (never argmin-selected, so their sums/counts rows stay
    zero and are sliced off); padded points enter with weight 0, so they
    contribute nothing to the accumulators.
    """
    p, d = x.shape
    k = centroids.shape[0]
    _obs.kernel_dispatch(
        "kmeans_update", "interpret" if _interpret() else "pallas")
    w = jnp.ones((p,), jnp.float32) if weights is None else weights.astype(jnp.float32)
    xp = _pad_to(_pad_to(x, 1, 128), 0, tile_p)
    cp = _pad_to(_pad_to(centroids, 1, 128), 0, 8, value=1e6)
    wp = _pad_to(w, 0, tile_p).reshape(1, -1)
    labels, d2, sums, counts = kmeans_update_pallas(
        xp, cp, wp, tile_p=tile_p, interpret=_interpret())
    return labels[0, :p], d2[0, :p], sums[:k, :d], counts[0, :k]


def spmm(a, b: jax.Array, *, transpose: bool = False) -> jax.Array:
    """SpMM against a BCOO matrix: ``A @ b`` (or ``A.T @ b``).

    Jittable everywhere (``nse`` is static): element-level gather +
    segment-sum, the formulation ``randomized_svd`` uses inside the
    jitted sparse atom phase. On TPU, callers that own the matrix for
    many products (the full-matrix sparse SCC baseline) should pre-tile
    once with ``bcoo_to_block_sparse`` and use ``spmm_tiled`` — the
    tile-level kernel keeps the contraction on the MXU instead of the
    scatter unit.
    """
    _obs.kernel_dispatch("spmm", "ref")
    rows, cols = a.indices[:, 0], a.indices[:, 1]
    if transpose:
        rows, cols = cols, rows
    n_out = a.shape[1] if transpose else a.shape[0]
    return ref.spmm_ref(a.data, rows, cols, n_out, b)


def sddmm(x: jax.Array, y: jax.Array, indices: jax.Array) -> jax.Array:
    """Sampled dense-dense matmul: values of ``x @ y.T`` at ``indices``.

    ``indices``: (nnz, 2) row/col pairs (a BCOO's ``.indices``). Pure
    gather-dot — no Pallas twin yet: it is not on the atom hot path
    (needed for future sparse-residual / graph-regularized workloads),
    and per-element dynamic gathers don't map onto TPU DMA without the
    tile-level format ``spmm_tiled`` uses.
    """
    _obs.kernel_dispatch("sddmm", "ref")
    return ref.sddmm_ref(x, y, indices[:, 0], indices[:, 1])


def spmm_tiled(a: BlockSparseMatrix, b: jax.Array, *,
               transpose: bool = False, bn: int = 128) -> jax.Array:
    """Tile-level SpMM: ``A @ b`` (or ``A.T @ b``) with ``A`` pre-tiled.

    ``a`` comes from ``bcoo_to_block_sparse`` (one-time host prep,
    amortized across every product that consumes the operator). ``b`` may
    carry any number of RHS columns — the kernel grids over ``bn``-wide
    column stripes. ``b`` is padded on its contracted axis to the tile
    grid (padded rows multiply zero payload cells only) and, on the
    Pallas tiers, on its column axis to ``bn``; padded output is sliced
    off. Dispatch: TPU -> Pallas kernel; ``REPRO_FORCE_INTERPRET`` ->
    interpret-mode kernel; otherwise the batched-einsum tile reference.
    """
    m, k = a.shape
    bm, bk = a.tile_shape
    n_tr, n_tc = a.n_tiles
    backend = _tiled_backend()
    _obs.kernel_dispatch("spmm_tiled", backend, transpose=transpose,
                         scaled=a.has_scales)
    out_rows = k if transpose else m
    if backend == "jnp":
        # the tile reference has no fused-scale variant: fold pending
        # scales into the payload stack once, outside any product loop
        a = a.materialize_scales()
        bp = _pad_to(b.astype(jnp.float32), 0, bm if transpose else bk)
        out = ref.spmm_block_ref(a.blocks, a.block_rows, a.block_cols,
                                 n_tr, n_tc, bp, transpose=transpose)
        return out[:out_rows, : b.shape[1]]
    interp = backend == "interpret"
    bp = _pad_to(_pad_to(b.astype(jnp.float32), 0, bm if transpose else bk),
                 1, bn)
    if transpose:
        out = spmm_t_pallas(a.block_rows, a.block_cols, a.t_order, a.blocks,
                            bp, k_out=n_tc * bk, bn=bn, interpret=interp,
                            row_scale=a.row_scale, col_scale=a.col_scale)
    else:
        out = spmm_pallas(a.block_rows, a.block_cols, a.blocks, bp,
                          m_out=n_tr * bm, bn=bn, interpret=interp,
                          row_scale=a.row_scale, col_scale=a.col_scale)
    return out[:out_rows, : b.shape[1]]


def spmm_ata(a: BlockSparseMatrix, x: jax.Array, *, bn: int = 128,
             with_gram: bool = False):
    """Fused normal-equations pass: ``A.T @ (A @ x)`` in one sweep.

    The subspace iteration's hot step (DESIGN.md §9): both products of
    one power-iteration application run in a single kernel launch, with
    the ``(M, q)`` intermediate held in VMEM scratch instead of
    round-tripping through HBM. Falls back to two ``spmm_tiled`` calls
    when the resident stripes would not fit the VMEM budget (or on the
    jnp tier, where the composition is already fused by XLA).

    ``with_gram=True`` returns ``(z, gram)`` with ``gram = z.T @ z``
    ``(q, q)`` — the fused subspace-iteration step: on the kernel path
    the Gram comes off the still-VMEM-resident output stripe inside the
    same launch (requires ``x`` to fit one ``bn`` column stripe), so the
    CholeskyQR orthonormalization that follows never re-reads ``z`` from
    HBM. Tiers without the fused kernel compute the same Gram outside.
    """
    m, k = a.shape
    bm, bk = a.tile_shape
    n_tr, n_tc = a.n_tiles
    n = x.shape[1]
    backend = _tiled_backend()
    # the fused in-kernel Gram covers exactly one output column stripe
    gram_in_kernel = with_gram and n <= bn
    if backend == "jnp":
        _obs.kernel_dispatch("spmm_ata", "jnp", fused=False,
                             scaled=a.has_scales, with_gram=with_gram)
        am = a.materialize_scales()
        xp = _pad_to(x.astype(jnp.float32), 0, bk)
        y = ref.spmm_block_ref(am.blocks, am.block_rows, am.block_cols,
                               n_tr, n_tc, xp)
        out = ref.spmm_block_ref(am.blocks, am.block_rows, am.block_cols,
                                 n_tr, n_tc, y, transpose=True)
        out = out[:k, :n]
        if with_gram:
            return out, out.T @ out
        return out
    # fused-kernel residency (Y stripe + output stripe + scales + Gram)
    # priced by the same estimator the A4 static audit uses — one budget,
    # runtime and lint
    stripes = vmem.ata_resident_bytes(n_tr, n_tc, bm, bk, bn,
                                      with_gram=gram_in_kernel,
                                      scaled=a.has_scales)
    budget = vmem.vmem_budget_bytes("tpu")
    if stripes > budget:
        _obs.kernel_dispatch("spmm_ata", backend, fused=False,
                             scaled=a.has_scales, with_gram=with_gram,
                             vmem_bytes=stripes, vmem_budget=budget)
        _obs.get_registry().counter(
            "spmm_ata_vmem_fallback",
            help="fused A.T@(A@x) declined by the VMEM estimator").inc()
        y = spmm_tiled(a, x, bn=bn)
        out = spmm_tiled(a, y, transpose=True, bn=bn)
        if with_gram:
            return out, out.T @ out
        return out
    _obs.kernel_dispatch("spmm_ata", backend, fused=True,
                         scaled=a.has_scales, with_gram=with_gram,
                         vmem_bytes=stripes, vmem_budget=budget)
    interp = backend == "interpret"
    xp = _pad_to(_pad_to(x.astype(jnp.float32), 0, bk), 1, bn)
    res = spmm_ata_pallas(a.block_rows, a.block_cols, a.blocks, xp,
                          m_pad=n_tr * bm, bn=bn, interpret=interp,
                          row_scale=a.row_scale, col_scale=a.col_scale,
                          with_gram=gram_in_kernel)
    if gram_in_kernel:
        out, gram = res
        return out[:k, :n], gram[:n, :n]
    out = res[:k, :n]
    if with_gram:
        return out, out.T @ out
    return out


def bipartite_normalize(a: jax.Array, eps: float = 1e-8,
                        tile_m: int = 256, tile_n: int = 256):
    """Fused ``A_n = D1^{-1/2} A D2^{-1/2}`` (degrees on |A|).

    Returns ``(a_n, d1_isqrt, d2_isqrt)`` with the same contract as
    ``core.spectral.normalize_bipartite``.
    """
    m, n = a.shape
    _obs.kernel_dispatch(
        "bipartite_normalize", "interpret" if _interpret() else "pallas")
    aa = jnp.abs(a)
    d1 = jnp.sum(aa, axis=1)
    d2 = jnp.sum(aa, axis=0)
    ap = _pad_to(_pad_to(a, 0, tile_m), 1, tile_n)
    d1p = _pad_to(d1, 0, tile_m, value=1.0).reshape(1, -1)
    d2p = _pad_to(d2, 0, tile_n, value=1.0).reshape(1, -1)
    out = scale_apply_pallas(ap, d1p, d2p, tile_m=tile_m, tile_n=tile_n,
                             eps=eps, interpret=_interpret())
    d1_isqrt = jax.lax.rsqrt(jnp.maximum(d1, eps))
    d2_isqrt = jax.lax.rsqrt(jnp.maximum(d2, eps))
    return out[:m, :n], d1_isqrt, d2_isqrt


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, tile_q: int = 512,
                    tile_k: int = 512) -> jax.Array:
    """Blockwise attention. q: (B, Hq, Sq, D); k,v: (B, Hkv, Skv, D).

    GQA: ``Hq`` must be a multiple of ``Hkv``; KV heads are expanded here
    (the kernel sees folded (B*H, S, D)). Sequences are padded to tile
    multiples; the kernel masks padded KV columns via ``kv_len`` and padded
    query rows are sliced off.
    """
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, f"GQA heads mismatch: {hq} % {hkv}"
    _obs.kernel_dispatch(
        "flash_attention", "interpret" if _interpret() else "pallas")
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    tq = min(tile_q, max(8, sq))
    tk = min(tile_k, max(128, skv))
    qf = _pad_to(q.reshape(b * hq, sq, dh), 1, tq)
    kf = _pad_to(k.reshape(b * hq, skv, dh), 1, tk)
    vf = _pad_to(v.reshape(b * hq, skv, dh), 1, tk)
    out = flash_attention_pallas(
        qf, kf, vf, kv_len=skv, causal=causal,
        tile_q=tq, tile_k=tk, interpret=_interpret(),
    )
    return out[:, :sq].reshape(b, hq, sq, dh)
