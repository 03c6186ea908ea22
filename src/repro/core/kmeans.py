"""Fixed-iteration k-means in pure JAX.

SPMD design notes (DESIGN.md §2): iteration count is *static* — every device
runs the identical program regardless of data, so block co-clustering never
creates shape- or trip-count-stragglers. Convergence is monitored (inertia is
returned) but never branched on.

The Lloyd iteration is the hot spot (the paper's inner loop). The jnp path
implements it via the MXU-friendly expansion ``|x-c|^2 = |x|^2 - 2 x.c +
|c|^2`` plus a materialized one-hot update; ``assign_impl='pallas'`` routes
the whole iteration through the fused one-pass kernel
``repro.kernels.kmeans_update`` (assignment + per-centroid sum/count
accumulation in VMEM — one HBM read of ``x`` per iteration instead of
three, DESIGN.md §4), validated against this reference in tests.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as _kops

__all__ = ["KMeansResult", "assign", "kmeans", "kmeanspp_init"]


class KMeansResult(NamedTuple):
    labels: jax.Array      # (P,) int32
    centroids: jax.Array   # (K, D)
    inertia: jax.Array     # () float32 — sum of squared distances


def assign(x: jax.Array, centroids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Nearest-centroid assignment. Returns (labels, min_sq_dist)."""
    # |x-c|^2 = |x|^2 - 2 x.c + |c|^2 ; |x|^2 constant wrt argmin but needed
    # for inertia.
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)            # (P,1)
    c2 = jnp.sum(centroids * centroids, axis=-1)           # (K,)
    d2 = x2 - 2.0 * (x @ centroids.T) + c2[None, :]        # (P,K)
    labels = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    return labels, jnp.maximum(jnp.min(d2, axis=-1), 0.0)


def _pallas_assign(x, centroids):
    return _kops.kmeans_assign(x, centroids)


def _pallas_update(x, centroids, weights):
    return _kops.kmeans_update(x, centroids, weights=weights)


def kmeanspp_init(key: jax.Array, x: jax.Array, k: int,
                  weights: jax.Array | None = None) -> jax.Array:
    """k-means++ seeding with a static-trip-count ``fori_loop``.

    With ``weights``, seeds are sampled proportional to ``w * d^2`` (zero-
    weight points are never selected).
    """
    p = x.shape[0]
    w = jnp.ones((p,), x.dtype) if weights is None else weights.astype(x.dtype)
    kfirst, krest = jax.random.split(key)
    first = jax.random.choice(kfirst, p, p=w / jnp.sum(w))
    cents = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])

    x2 = jnp.sum(x * x, axis=-1, keepdims=True)            # loop-invariant

    def body(i, carry):
        cents, key = carry
        key, sub = jax.random.split(key)
        # distance to nearest of the first i centroids; mask out unset rows
        c2 = jnp.sum(cents * cents, axis=-1)
        d2 = x2 - 2.0 * (x @ cents.T) + c2[None, :]        # (P,K)
        valid = jnp.arange(k) < i
        d2 = jnp.where(valid[None, :], d2, jnp.inf)
        dmin = jnp.maximum(jnp.min(d2, axis=-1), 1e-12) * w
        probs = dmin / jnp.sum(dmin)
        nxt = jax.random.choice(sub, p, p=probs)
        return cents.at[i].set(x[nxt]), key

    cents, _ = jax.lax.fori_loop(1, k, body, (cents, key))
    return cents


@functools.partial(jax.jit, static_argnames=("k", "n_iter", "assign_impl"))
def kmeans(
    key: jax.Array,
    x: jax.Array,
    k: int,
    n_iter: int = 16,
    assign_impl: str = "jnp",
    weights: jax.Array | None = None,
) -> KMeansResult:
    """Lloyd's algorithm, ``n_iter`` static iterations, k-means++ init.

    Empty clusters keep their previous centroid (standard fix that preserves
    SPMD static shapes). ``weights`` makes both seeding and centroid updates
    weighted (zero-weight points contribute nothing). ``assign_impl='pallas'``
    routes each full Lloyd iteration through the fused Pallas kernel
    (``kernels.kmeans_update``): assignment *and* sum/count accumulation in
    one pass over ``x``, with no materialized ``(P, K)`` one-hot.
    """
    assign_fn = _pallas_assign if assign_impl == "pallas" else assign
    w = None if weights is None else weights.astype(x.dtype)
    cents0 = kmeanspp_init(key, x, k, weights=w)

    if assign_impl == "pallas":
        def step(cents, _):
            _labels, _d, sums, counts = _pallas_update(x, cents, w)
            new = jnp.where(
                counts[:, None] > 0,
                (sums / jnp.maximum(counts, 1e-9)[:, None]).astype(x.dtype),
                cents,
            )
            return new, None

        cents, _ = jax.lax.scan(step, cents0, None, length=n_iter)
    else:
        def step(cents, _):
            labels, _d = assign_fn(x, cents)
            onehot = jax.nn.one_hot(labels, k, dtype=x.dtype)   # (P,K)
            if w is not None:
                onehot = onehot * w[:, None]
            counts = jnp.sum(onehot, axis=0)                    # (K,)
            sums = onehot.T @ x                                 # (K,D)
            new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1e-9)[:, None], cents)
            return new, None

        cents, _ = jax.lax.scan(step, cents0, None, length=n_iter)
    labels, d2 = assign_fn(x, cents)
    if w is not None:
        d2 = d2 * w
    return KMeansResult(labels=labels, centroids=cents, inertia=jnp.sum(d2))
