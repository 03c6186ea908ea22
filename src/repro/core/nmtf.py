"""Non-negative Matrix Tri-Factorization atom co-clusterer.

Implements orthogonal NMTF (Ding et al. 2006; the serial core of the
"PNMTF [11]" baseline in the paper's Table II): ``A ~= F S G^T`` with
``F (M,k) >= 0``, ``G (N,d) >= 0``, multiplicative updates, fixed iteration
count (SPMD-uniform, see DESIGN.md §2). Row labels = argmax_k F, col labels
= argmax_d G.

Used two ways:
  * as a drop-in atom method for LAMC (``LAMC-PNMTF`` row of Table II), and
  * unpartitioned, as the ``PNMTF`` baseline itself (``core.baselines``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import kmeans as _kmeans

__all__ = ["NMTFResult", "nmtf"]

_EPS = 1e-9


class NMTFResult(NamedTuple):
    row_labels: jax.Array   # (M,)
    col_labels: jax.Array   # (N,)
    f: jax.Array            # (M,k)
    s: jax.Array            # (k,d)
    g: jax.Array            # (N,d)
    loss: jax.Array         # ||A - F S G^T||_F^2


@functools.partial(jax.jit, static_argnames=("k", "d", "n_iter"))
def nmtf(key: jax.Array, a: jax.Array, k: int, d: int | None = None,
         n_iter: int = 64) -> NMTFResult:
    """Orthogonal tri-factorization with multiplicative updates.

    ``a`` is shifted to be non-negative (co-clustering affinities are
    magnitudes; the shift is removed from the reported loss baseline).
    """
    if d is None:
        d = k
    # the atom's phases as named scopes (DESIGN.md §14); the multiplicative
    # updates take the place SCC's subspace iteration has
    with jax.named_scope("atom/normalize"):
        a = a - jnp.minimum(jnp.min(a), 0.0)  # enforce non-negativity
    m, n = a.shape
    with jax.named_scope("atom/kmeans"):
        kf, kg = jax.random.split(key)
        # k-means init (Ding et al. recommend it): F = onehot(rows) + 0.2,
        # G = onehot(cols) + 0.2 — orders of magnitude faster convergence
        # than random init for the multiplicative updates.
        row_km = _kmeans.kmeans(kf, a, k, n_iter=8)
        col_km = _kmeans.kmeans(kg, a.T, d, n_iter=8)
        f = jax.nn.one_hot(row_km.labels, k, dtype=a.dtype) + 0.2
        g = jax.nn.one_hot(col_km.labels, d, dtype=a.dtype) + 0.2

    def step(carry, _):
        f, s, g = carry
        # G <- G * sqrt( (A^T F S) / (G G^T A^T F S) )
        num_g = a.T @ (f @ s)                               # (N,d)
        den_g = g @ (g.T @ num_g)
        g = g * jnp.sqrt(num_g / jnp.maximum(den_g, _EPS))
        # F <- F * sqrt( (A G S^T) / (F F^T A G S^T) )
        num_f = a @ (g @ s.T)                               # (M,k)
        den_f = f @ (f.T @ num_f)
        f = f * jnp.sqrt(num_f / jnp.maximum(den_f, _EPS))
        # S <- S * sqrt( (F^T A G) / (F^T F S G^T G) )
        num_s = f.T @ a @ g                                 # (k,d)
        den_s = (f.T @ f) @ s @ (g.T @ g)
        s = s * jnp.sqrt(num_s / jnp.maximum(den_s, _EPS))
        return (f, s, g), None

    with jax.named_scope("atom/factorize"):
        s = f.T @ a @ g / jnp.maximum(jnp.sum(f, 0)[:, None] * jnp.sum(g, 0)[None, :], _EPS)
        (f, s, g), _ = jax.lax.scan(step, (f, s, g), None, length=n_iter)
        recon = f @ s @ g.T
        loss = jnp.sum((a - recon) ** 2)
        row_labels = jnp.argmax(f, axis=1).astype(jnp.int32)
        col_labels = jnp.argmax(g, axis=1).astype(jnp.int32)
    return NMTFResult(row_labels=row_labels, col_labels=col_labels,
                      f=f, s=s, g=g, loss=loss)
