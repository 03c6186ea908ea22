"""Pallas TPU kernels: tiled k-means assignment (distance + argmin) and
cosine scoring (dot + argmax) against a signature table.

The paper's hottest inner loop: every k-means iteration on every block
assigns ``P`` points to ``K`` centroids. The kernel tiles points into VMEM
blocks of ``tile_p`` rows, keeps the (small) centroid table resident in
VMEM, and computes

    d2 = |x|^2 - 2 x @ c^T + |c|^2

with the ``x @ c^T`` contraction on the MXU (``preferred_element_type``
pinned to f32 so bf16 inputs accumulate in f32). Outputs are per-point
argmin labels and min distances.

``cosine_assign_pallas`` is the serving twin (online assignment of new
rows/cols to a fitted co-clustering, DESIGN.md §10): same tiling, but the
score is the raw dot ``x @ s^T`` against *unit-normalized* cluster
signatures and the reduction is an argmax. For unit signatures the dot
ordering equals the Euclidean ordering (``|x - s|^2 = |x|^2 - 2 x.s + 1``),
so no norms are needed; padded signature rows are masked to -inf via the
static ``k_valid`` so they can never win.

VMEM budget per grid step: ``tile_p*D + K*D + tile_p*K`` floats — e.g.
(512 x 256) + (64 x 256) + (512 x 64) ~ 0.7 MB, comfortably under the
~16 MB/core VMEM of a v5e, leaving room for double-buffering.

Grid: ``(ceil(P / tile_p),)`` — 1-D over point tiles; centroids are
broadcast to every step (index_map returns block 0).

Per-point outputs are lane-dense ``(1, P)`` rows written in
``(1, tile_p)`` blocks: the TPU compiler tiles a 1-D ``(P,)`` array by
1024 while a ``(tile_p,)`` block asks for ``tile_p``, and refuses the
mismatch. ``ops.py`` takes row 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["kmeans_assign_pallas", "cosine_assign_pallas",
           "cosine_topk_pallas"]


def _kernel(x_ref, c_ref, labels_ref, d2_ref):
    x = x_ref[...].astype(jnp.float32)               # (TP, D)
    c = c_ref[...].astype(jnp.float32)               # (K, D)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)      # (TP, 1)
    c2 = jnp.sum(c * c, axis=-1)                     # (K,)
    xc = jax.lax.dot_general(
        x, c,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                # (TP, K) on the MXU
    d2 = x2 - 2.0 * xc + c2[None, :]
    tp = d2.shape[0]
    labels_ref[...] = jnp.argmin(d2, axis=-1).astype(jnp.int32).reshape(1, tp)
    d2_ref[...] = jnp.maximum(jnp.min(d2, axis=-1), 0.0).reshape(1, tp)


@functools.partial(jax.jit, static_argnames=("tile_p", "interpret"))
def kmeans_assign_pallas(
    x: jax.Array,          # (P, D) — P and D already padded by ops.py
    centroids: jax.Array,  # (K, D) — K padded with +inf-distance sentinels
    tile_p: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw kernel invocation; returns ``(labels, d2)`` shaped ``(1, P)``.
    Use ``repro.kernels.ops.kmeans_assign`` for the shape-safe public
    wrapper (padding, sentinel handling, CPU fallback)."""
    p, d = x.shape
    k, _ = centroids.shape
    grid = (pl.cdiv(p, tile_p),)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_p, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_p), lambda i: (0, i)),
            pl.BlockSpec((1, tile_p), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, p), jnp.int32),
            jax.ShapeDtypeStruct((1, p), jnp.float32),
        ],
        interpret=interpret,
        name="kmeans_assign",
    )(x, centroids)


def _cosine_kernel(k_valid, x_ref, s_ref, labels_ref, score_ref):
    x = x_ref[...].astype(jnp.float32)               # (TP, D)
    s = s_ref[...].astype(jnp.float32)               # (K, D)
    xs = jax.lax.dot_general(
        x, s,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                # (TP, K) on the MXU
    # mask padded signature rows: zero-padded rows score 0, which would
    # beat any all-negative real row — force them unselectable instead
    valid = jax.lax.broadcasted_iota(jnp.int32, xs.shape, 1) < k_valid
    xs = jnp.where(valid, xs, -jnp.inf)
    tp = xs.shape[0]
    labels_ref[...] = jnp.argmax(xs, axis=-1).astype(jnp.int32).reshape(1, tp)
    score_ref[...] = jnp.max(xs, axis=-1).reshape(1, tp)


def _cosine_topk_kernel(k_valid, k_top, x_ref, s_ref, labels_ref, score_ref):
    x = x_ref[...].astype(jnp.float32)               # (TP, D)
    s = s_ref[...].astype(jnp.float32)               # (K, D)
    xs = jax.lax.dot_general(
        x, s,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                # (TP, K) on the MXU
    valid = jax.lax.broadcasted_iota(jnp.int32, xs.shape, 1) < k_valid
    xs = jnp.where(valid, xs, -jnp.inf)
    # iterative select-and-mask: k_top is static and small, so this
    # unrolls to k_top argmax/VPU passes over the VMEM-resident (TP, K)
    # score tile — no sort network, no HBM traffic. Ties go to the lower
    # cluster id each round, matching jax.lax.top_k (the ref oracle).
    labs, scores = [], []
    for _ in range(k_top):
        lab = jnp.argmax(xs, axis=-1).astype(jnp.int32)   # (TP,)
        scores.append(jnp.max(xs, axis=-1))
        labs.append(lab)
        taken = jax.lax.broadcasted_iota(jnp.int32, xs.shape, 1) == lab[:, None]
        xs = jnp.where(taken, -jnp.inf, xs)
    labels_ref[...] = jnp.stack(labs, axis=1)
    score_ref[...] = jnp.stack(scores, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("k_valid", "k_top", "tile_p", "interpret"))
def cosine_topk_pallas(
    x: jax.Array,           # (P, D) — P and D already padded by ops.py
    signatures: jax.Array,  # (K, D) — K padded with zero rows
    k_valid: int,
    k_top: int,
    tile_p: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Top-``k_top`` signature scoring: the multi-assignment serving twin
    of :func:`cosine_assign_pallas` (DESIGN.md §11). Returns
    ``(labels (P, k_top) int32, scores (P, k_top) f32)`` ordered by
    descending score. Use ``repro.kernels.ops.cosine_topk`` for the
    shape-safe public wrapper (padding, k validation, CPU fallback)."""
    p, d = x.shape
    k, _ = signatures.shape
    grid = (pl.cdiv(p, tile_p),)
    return pl.pallas_call(
        functools.partial(_cosine_topk_kernel, k_valid, k_top),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_p, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_p, k_top), lambda i: (i, 0)),
            pl.BlockSpec((tile_p, k_top), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, k_top), jnp.int32),
            jax.ShapeDtypeStruct((p, k_top), jnp.float32),
        ],
        interpret=interpret,
        name="cosine_topk",
    )(x, signatures)


@functools.partial(jax.jit, static_argnames=("k_valid", "tile_p", "interpret"))
def cosine_assign_pallas(
    x: jax.Array,           # (P, D) — P and D already padded by ops.py
    signatures: jax.Array,  # (K, D) — K padded with zero rows
    k_valid: int,
    tile_p: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw kernel invocation; returns ``(labels, score)`` shaped
    ``(1, P)``. Use ``repro.kernels.ops.cosine_assign`` for the
    shape-safe public wrapper (padding, CPU fallback)."""
    p, d = x.shape
    k, _ = signatures.shape
    grid = (pl.cdiv(p, tile_p),)
    return pl.pallas_call(
        functools.partial(_cosine_kernel, k_valid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_p, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_p), lambda i: (0, i)),
            pl.BlockSpec((1, tile_p), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, p), jnp.int32),
            jax.ShapeDtypeStruct((1, p), jnp.float32),
        ],
        interpret=interpret,
        name="cosine_assign",
    )(x, signatures)
