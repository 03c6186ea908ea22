"""Pallas TPU kernel: fused bipartite-graph normalization scale-apply.

Computes ``out = A * rsqrt(max(d1,eps))[:,None] * rsqrt(max(d2,eps))[None,:]``
(Eq. 7's ``A_n = D1^{-1/2} A D2^{-1/2}``) in a single pass: the naive jnp
formulation materializes two broadcast intermediates (HBM traffic ~4|A|);
the fused kernel reads A once and writes A_n once (~2|A|), with the rsqrt
folded into the tile compute. Degree sums themselves are row/col reductions
XLA already fuses well; they stay in jnp (see ops.bipartite_normalize).

Grid: 2-D over (row tiles, col tiles). VMEM per step:
``tile_m*tile_n + tile_m + tile_n`` floats — 256 KB at 256 x 256 f32.
Degrees ride in as lane-dense ``(1, M)`` / ``(1, N)`` rows: the TPU
compiler refuses a ``(tile_m,)`` block of a 1-D array.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["scale_apply_pallas"]


def _kernel(a_ref, d1_ref, d2_ref, out_ref, *, eps: float):
    a = a_ref[...].astype(jnp.float32)                 # (TM, TN)
    d1 = d1_ref[...].astype(jnp.float32)               # (1, TM)
    d2 = d2_ref[...].astype(jnp.float32)               # (1, TN)
    s1 = jax.lax.rsqrt(jnp.maximum(d1, eps)).reshape(a.shape[0], 1)
    s2 = jax.lax.rsqrt(jnp.maximum(d2, eps))
    out_ref[...] = (a * s1 * s2).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n", "eps", "interpret"))
def scale_apply_pallas(
    a: jax.Array,    # (M, N)
    d1: jax.Array,   # (1, M) raw row degrees
    d2: jax.Array,   # (1, N) raw col degrees
    tile_m: int = 256,
    tile_n: int = 256,
    eps: float = 1e-8,
    interpret: bool = False,
) -> jax.Array:
    m, n = a.shape
    grid = (pl.cdiv(m, tile_m), pl.cdiv(n, tile_n))
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m, tile_n), lambda i, j: (i, j)),
            pl.BlockSpec((1, tile_m), lambda i, j: (0, i)),
            pl.BlockSpec((1, tile_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        interpret=interpret,
        name="bipartite_normalize",
    )(a, d1, d2)
