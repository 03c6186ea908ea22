"""Pallas TPU kernel: fused one-pass Lloyd iteration (assign + accumulate).

``kmeans_assign`` answers "which centroid?"; a full Lloyd iteration also
needs the *update* statistics — per-centroid coordinate sums and member
counts. The jnp reference does that with three passes over ``x`` (assign,
``one_hot.T @ x``, count reduction) and materializes a ``(P, K)`` one-hot
in HBM. This kernel fuses all of it: per point-tile it

  1. computes ``d2 = |x|^2 - 2 x @ c^T + |c|^2`` on the MXU,
  2. takes argmin labels / min distances,
  3. builds the *tile-local* one-hot in VMEM (never written to HBM) and
     accumulates ``sums += one_hot^T @ x`` (a second MXU contraction) and
     ``counts += sum(one_hot)`` into carried output blocks,

so one Lloyd iteration reads ``x`` from HBM exactly once and writes only
``(K, D) + (1, K)`` accumulators plus the labels.

Weighted k-means folds weights into the one-hot (``one_hot * w``), which
also makes padded points (weight 0) contribute nothing — the wrapper in
``ops.py`` exploits this for point padding.

VMEM budget per grid step (DESIGN.md §4): ``tile_p*D`` (x tile) + ``K*D``
(centroids) + ``tile_p*K`` (d2 + one-hot) + ``K*D + K`` (accumulators)
floats — e.g. tile_p=512, D=256, K=64: ~1.1 MB, far under the ~16 MB/core
of a v5e, leaving headroom for double-buffering.

Grid: ``(ceil(P / tile_p),)`` — sequential on TPU, so the accumulator
blocks (index_map pinned to block 0) carry across steps; step 0 zeroes
them via ``pl.when``.

Weights, labels and distances travel as lane-dense ``(1, P)`` rows in
``(1, tile_p)`` blocks, for the same reason as in ``kmeans_assign``: the
TPU compiler refuses a ``(tile_p,)`` block of a 1-D array.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["kmeans_update_pallas"]


def _kernel(x_ref, c_ref, w_ref, labels_ref, d2_ref, sums_ref, counts_ref):
    @pl.when(pl.program_id(0) == 0)
    def _zero_accumulators():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    x = x_ref[...].astype(jnp.float32)               # (TP, D)
    c = c_ref[...].astype(jnp.float32)               # (K, D)
    tp = x.shape[0]
    w = w_ref[...].astype(jnp.float32).reshape(tp, 1)  # (1, TP) -> (TP, 1)
    k = c.shape[0]

    x2 = jnp.sum(x * x, axis=-1, keepdims=True)      # (TP, 1)
    c2 = jnp.sum(c * c, axis=-1)                     # (K,)
    xc = jax.lax.dot_general(
        x, c,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                # (TP, K) on the MXU
    d2 = x2 - 2.0 * xc + c2[None, :]
    labels = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    labels_ref[...] = labels.reshape(1, tp)
    d2_ref[...] = jnp.maximum(jnp.min(d2, axis=-1), 0.0).reshape(1, tp)

    # Tile-local weighted one-hot — lives only in VMEM.
    ids = jax.lax.broadcasted_iota(jnp.int32, (tp, k), 1)
    onehot = jnp.where(ids == labels[:, None], w, 0.0)   # (TP, K)
    sums_ref[...] += jax.lax.dot_general(
        onehot, x,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                # (K, D) on the MXU
    counts_ref[...] += jnp.sum(onehot, axis=0)[None, :]           # (1, K)


@functools.partial(jax.jit, static_argnames=("tile_p", "interpret"))
def kmeans_update_pallas(
    x: jax.Array,          # (P, D) — P and D already padded by ops.py
    centroids: jax.Array,  # (K, D) — K padded with +1e6-distance sentinels
    weights: jax.Array,    # (1, P) — padded points carry weight 0
    tile_p: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Raw kernel invocation; returns ``(labels, d2, sums, counts)`` with
    ``labels``/``d2`` shaped ``(1, P)`` and ``counts`` ``(1, K)``. Use ``repro.kernels.ops.kmeans_update``
    for the shape-safe public wrapper (padding, sentinels, CPU fallback)."""
    p, d = x.shape
    k, _ = centroids.shape
    grid = (pl.cdiv(p, tile_p),)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_p, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, tile_p), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_p), lambda i: (0, i)),
            pl.BlockSpec((1, tile_p), lambda i: (0, i)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, p), jnp.int32),
            jax.ShapeDtypeStruct((1, p), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
        ],
        interpret=interpret,
        name="kmeans_update",
    )(x, centroids, weights)
