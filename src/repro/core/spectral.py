"""Spectral co-clustering (Dhillon 2001) — the paper's atom co-clusterer (§IV-C).

Pipeline (Eqs. 5-8 of the paper):
  1. ``A_n = D1^{-1/2} A D2^{-1/2}`` — bipartite graph normalization.
  2. Singular vectors ``u_2..u_{l+1}``, ``v_2..v_{l+1}`` of ``A_n``.
  3. ``Z = [D1^{-1/2} U_hat ; D2^{-1/2} V_hat]`` stacked embedding.
  4. k-means on rows of ``Z``; rows of A get ``labels[:M]``, cols ``labels[M:]``.

TPU adaptation (DESIGN.md §2): exact LAPACK SVD is replaced by fixed-iteration
randomized subspace iteration — pure matmul/QR, MXU-aligned, identical trip
count on every device. ``l = n_singular_vectors`` defaults to
``ceil(log2(k)) + 1`` per Dhillon's analysis but is configurable.

Sparse inputs (DESIGN.md §9): ``normalize_bipartite``, ``randomized_svd``
and ``scc`` accept a BCOO matrix, a dual-ELL operator
(``sparse.EllOperator``, gather-only products) or a tiled block-sparse
operator (``kernels.spmm.BlockSparseMatrix``, MXU tile products with the
fused ``Aᵀ(A·X)`` normal-equations pass). Normalization stays in the
operand's format (degree sums + a data rescale, same sparsity pattern);
the subspace iteration's heavy ops become SpMM — cost O(nnz * rank) (or
O(occupied tiles) for tiled) per pass instead of O(M * N * rank). Only
the (M, l)/(N, l) embeddings densify. ``probability.spmm_route`` picks
the format per matrix from its density.

The normalization has a fused Pallas twin (``repro.kernels.bipartite_normalize``)
used on TPU; this file is also its reference oracle.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as _kops

from . import kmeans as _kmeans
from . import sparse as _sparse

__all__ = ["normalize_bipartite", "randomized_svd", "scc", "SCCResult"]


class SCCResult(NamedTuple):
    row_labels: jax.Array   # (M,) int32 in [0, k)
    col_labels: jax.Array   # (N,) int32 in [0, k)
    row_embed: jax.Array    # (M, l) spectral embedding (for merge signatures)
    col_embed: jax.Array    # (N, l)
    inertia: jax.Array


def normalize_bipartite(a: jax.Array, eps: float = 1e-8):
    """``A_n = D1^{-1/2} A D2^{-1/2}`` with degree clamping.

    Degrees are taken on |A| so the construction tolerates signed data
    (the bipartite-graph weights of Eq. 5 assume non-negative affinities).
    Returns ``(a_n, d1_isqrt, d2_isqrt)``; a BCOO input yields a BCOO
    ``a_n`` with the same sparsity pattern (zeros contribute nothing to
    degrees, and the rescale is elementwise on the stored data).
    """
    if _sparse.is_bcoo(a) or _sparse.is_ell(a) or _sparse.is_tiled(a):
        if _sparse.is_ell(a):
            d1, d2 = _sparse.ell_abs_degree_sums(a)
            scale = _sparse.ell_scale_rows_cols
        elif _sparse.is_tiled(a):
            d1, d2 = _sparse.tiled_abs_degree_sums(a)
            scale = _sparse.tiled_scale_rows_cols
        else:
            d1, d2 = _sparse.abs_degree_sums(a)
            scale = _sparse.scale_rows_cols
        d1_isqrt = jax.lax.rsqrt(jnp.maximum(d1, eps))
        d2_isqrt = jax.lax.rsqrt(jnp.maximum(d2, eps))
        return scale(a, d1_isqrt, d2_isqrt), d1_isqrt, d2_isqrt
    aa = jnp.abs(a)
    d1 = jnp.sum(aa, axis=1)
    d2 = jnp.sum(aa, axis=0)
    d1_isqrt = jax.lax.rsqrt(jnp.maximum(d1, eps))
    d2_isqrt = jax.lax.rsqrt(jnp.maximum(d2, eps))
    return a * d1_isqrt[:, None] * d2_isqrt[None, :], d1_isqrt, d2_isqrt


def _orth_from_gram(yf: jax.Array, g: jax.Array,
                    eps: float = 1e-7) -> jax.Array:
    """CholeskyQR from a precomputed Gram: ``Q = Y L^{-T}``, ``G = LLᵀ``.

    Split out of :func:`_cholesky_orth` so the tiled subspace iteration
    can feed it the Gram emitted by the fused ``spmm_ata`` launch
    (``with_gram=True``) — the ``(M, r)`` factor is then never re-read to
    form ``YᵀY``. A trace-scaled ridge keeps the Cholesky finite when
    ``Y`` is (numerically) rank-deficient.
    """
    r = g.shape[0]
    ridge = eps * (jnp.trace(g) / r + 1.0)
    l = jnp.linalg.cholesky(g + ridge * jnp.eye(r, dtype=g.dtype))
    # Solve Q @ Lᵀ = Y  =>  Q = Y L^{-T}.
    return jax.lax.linalg.triangular_solve(
        l, yf, left_side=False, lower=True, transpose_a=True)


def _cholesky_orth(y: jax.Array, eps: float = 1e-7) -> jax.Array:
    """Gram-based orthonormalization: ``Q = Y (YᵀY)^{-1/2}`` (CholeskyQR).

    The Gram matrix is a tiny ``(r, r)`` — the only non-matmul work is its
    Cholesky and a triangular solve, both on an ``(r, r)`` operand, so the
    tall-skinny factor never goes through LAPACK QR. A trace-scaled ridge
    keeps the Cholesky finite when ``Y`` is (numerically) rank-deficient;
    see DESIGN.md §5 for the conditioning argument (squares ``cond(Y)``,
    fine for the normalized-affinity matrices of the SCC atom).
    """
    yf = y.astype(jnp.float32)
    g = yf.T @ yf                                   # (r, r) Gram — MXU
    return _orth_from_gram(yf, g, eps).astype(y.dtype)


def randomized_svd(key: jax.Array, a: jax.Array, rank: int, n_iter: int = 4,
                   qr_method: str = "qr"):
    """Randomized subspace iteration for the top-``rank`` singular triplets.

    ``n_iter`` stabilized power iterations; all heavy ops are matmuls (MXU)
    and a final tiny ``(rank, rank)`` exact SVD. Deterministic in ``key``.
    Returns ``(U (M,r), S (r,), Vt (r,N))``.

    ``qr_method`` selects the per-iteration orthonormalization:
      * ``"qr"`` — Householder ``jnp.linalg.qr`` (LAPACK-exact, but lowers
        to a sequential panel algorithm per block when vmapped on TPU);
      * ``"cholesky"`` — Gram-based CholeskyQR (``_cholesky_orth``):
        matmul + ``(r, r)`` Cholesky only, batch-friendly, MXU-resident.

    A BCOO ``a`` routes every product through SpMM (``kernels.ops.spmm``):
    the power iteration touches only the stored nonzeros, O(nnz * r) per
    pass; the sketch/projection operands stay dense tall-skinny. A
    dual-ELL operand keeps the same two-sided iteration with gather-only
    products. A tiled ``BlockSparseMatrix`` operand runs the *fused
    normal-equations* form instead: each power step is one
    ``A.T @ (A @ X)`` pass (``kernels.ops.spmm_ata`` — a single kernel
    launch whose intermediate never leaves VMEM on TPU), iterating the
    ``(N, r)`` sketch and mapping through ``A`` once at the end. Both
    forms apply the same polynomial of ``A``, so they converge to the
    same subspace: ``span(A (AᵀA)^t Ω) = span((AAᵀ)^t A Ω)``.
    """
    m, n = a.shape
    r = min(rank, m, n)
    orth = _cholesky_orth if qr_method == "cholesky" else (
        lambda y: jnp.linalg.qr(y)[0])
    sparse_in = _sparse.is_bcoo(a) or _sparse.is_ell(a) or _sparse.is_tiled(a)
    if _sparse.is_ell(a):
        # gather-only dual-ELL products — the amortized repeated-product
        # path (converted once per matrix, see sparse.EllOperator)
        matvec = lambda x: _sparse.ell_matvec(a, x)
        rmatvec = lambda x: _sparse.ell_rmatvec(a, x)
        ata = ata_step = None
    elif _sparse.is_tiled(a):
        matvec = lambda x: _kops.spmm_tiled(a, x)
        rmatvec = lambda x: _kops.spmm_tiled(a, x, transpose=True)
        ata = lambda x: _kops.spmm_ata(a, x)
        if qr_method == "cholesky":
            # fused subspace-iteration step: one spmm_ata launch returns
            # both Z = A.T(A X) and its (r, r) Gram (computed from the
            # still-VMEM-resident stripe on TPU), feeding CholeskyQR
            # directly — Z is never re-read to form ZᵀZ
            ata_step = lambda x: _orth_from_gram(
                *_kops.spmm_ata(a, x, with_gram=True))
        else:
            ata_step = lambda x: orth(ata(x))
    elif _sparse.is_bcoo(a):
        matvec = lambda x: _kops.spmm(a, x)                  # A @ x
        rmatvec = lambda x: _kops.spmm(a, x, transpose=True)  # A.T @ x
        ata = ata_step = None
    else:
        matvec = lambda x: a @ x
        rmatvec = lambda x: a.T @ x
        ata = ata_step = None
    omega = jax.random.normal(key, (n, r), dtype=jnp.float32 if sparse_in
                              else a.dtype)
    if sparse_in:
        # Orthonormalize the sketch before the first product. Same span, and
        # the QR custom call forces the RNG output to materialize: without
        # it XLA fuses the threefry generator into the SpMM gather and
        # recomputes it per gathered element (measured ~7x slower on CPU).
        omega = orth(omega)
    if ata is not None:
        # fused normal-equations power iteration on the (N, r) sketch
        x = jax.lax.fori_loop(0, n_iter, lambda _, x: ata_step(x), omega)
        q = orth(matvec(x))                         # (M, r)
    else:
        q = orth(matvec(omega))                     # (M, r)

        def body(_, q):
            z = orth(rmatvec(q))                    # (N, r)
            return orth(matvec(z))                  # (M, r)

        q = jax.lax.fori_loop(0, n_iter, body, q)
    b = rmatvec(q).T if sparse_in else q.T @ a      # (r, N)
    # exact SVD of the small projected matrix
    ub, s, vt = jnp.linalg.svd(b, full_matrices=False)
    u = q @ ub
    return u, s, vt


def exact_svd(a: jax.Array, rank: int):
    """LAPACK-style full SVD truncated to ``rank`` — the paper's original
    atom cost profile (O(M N min(M,N)), superlinear). Baseline mode for the
    Table II speedup reproduction; ``randomized_svd`` is the TPU-adapted
    default (DESIGN.md §2)."""
    u, s, vt = jnp.linalg.svd(a, full_matrices=False)
    return u[:, :rank], s[:rank], vt[:rank, :]


@functools.partial(
    jax.jit,
    static_argnames=("n_row_clusters", "n_col_clusters", "n_singular_vectors",
                     "svd_iters", "kmeans_iters", "assign_impl", "svd_method",
                     "qr_method"),
)
def scc(
    key: jax.Array,
    a: jax.Array,
    n_row_clusters: int,
    n_col_clusters: int | None = None,
    n_singular_vectors: int | None = None,
    svd_iters: int = 4,
    kmeans_iters: int = 16,
    assign_impl: str = "jnp",
    svd_method: str = "randomized",
    qr_method: str = "qr",
) -> SCCResult:
    """Spectral co-clustering of one (sub)matrix.

    When ``n_col_clusters == n_row_clusters`` (the bipartite-partition case
    of the paper) rows and columns are clustered *jointly* in the stacked
    ``Z`` space — exactly Dhillon's algorithm. Otherwise rows and columns
    get separate k-means in the same spectral space.
    """
    k = n_row_clusters
    d = n_col_clusters if n_col_clusters is not None else k
    # Dhillon: l = ceil(log2 k) singular vectors carry the k-modal structure;
    # bit_length() gives ceil(log2 x)+1 — one extra vector for robustness —
    # and is a static python int so jit sees a fixed SVD rank.
    l = n_singular_vectors if n_singular_vectors is not None else max(k, d).bit_length()

    if ((_sparse.is_bcoo(a) or _sparse.is_ell(a) or _sparse.is_tiled(a))
            and svd_method == "exact"):
        raise ValueError(
            "svd_method='exact' (LAPACK) requires a dense matrix; the sparse "
            "path supports svd_method='randomized' (SpMM subspace iteration)")
    # the atom's phases as named scopes (DESIGN.md §14): the device trace
    # names each instruction's phase, in every program that runs an atom
    with jax.named_scope("atom/normalize"):
        a_n, d1_isqrt, d2_isqrt = normalize_bipartite(a)
    with jax.named_scope("atom/svd"):
        ksvd, kkm1, kkm2 = jax.random.split(key, 3)
        if svd_method == "exact":
            u, s, vt = exact_svd(a_n, rank=l + 1)
        else:
            u, s, vt = randomized_svd(ksvd, a_n, rank=l + 1, n_iter=svd_iters,
                                      qr_method=qr_method)
        # Drop the leading (trivial) singular pair: u_2..u_{l+1}, v_2..v_{l+1}.
        u_hat = u[:, 1 : l + 1]
        v_hat = vt[1 : l + 1, :].T
        row_embed = d1_isqrt[:, None] * u_hat           # (M, l)
        col_embed = d2_isqrt[:, None] * v_hat           # (N, l)

    with jax.named_scope("atom/kmeans"):
        if k == d:
            z = jnp.concatenate([row_embed, col_embed], axis=0)
            res = _kmeans.kmeans(kkm1, z, k, n_iter=kmeans_iters, assign_impl=assign_impl)
            row_labels = res.labels[: a.shape[0]]
            col_labels = res.labels[a.shape[0] :]
            inertia = res.inertia
        else:
            res_r = _kmeans.kmeans(kkm1, row_embed, k, n_iter=kmeans_iters, assign_impl=assign_impl)
            res_c = _kmeans.kmeans(kkm2, col_embed, d, n_iter=kmeans_iters, assign_impl=assign_impl)
            row_labels, col_labels = res_r.labels, res_c.labels
            inertia = res_r.inertia + res_c.inertia

    return SCCResult(row_labels, col_labels, row_embed, col_embed, inertia)
