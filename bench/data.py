"""Data of a cell, made on the device from ``--seed``.

Both makers follow the planted co-cluster model of
``repro.data.planted_cocluster_matrix``: balanced row and column labels
in a shuffled order, one mean per (row cluster, column cluster) cell
drawn uniformly from ``[0, signal]``, Gaussian noise of scale ``noise``,
and for a sparse matrix a Bernoulli support of the given density. The
matrix itself is drawn by ``jax.random`` in one jitted call, so it never
crosses from the host.

The seed is any whole number: it seeds a NumPy generator, which draws the
labels, the means and the 31-bit keys that ``jax.random`` is given.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Planted:
    a: object                 # (M, N) jax.Array, or a BCOO for a sparse cell
    row_labels: np.ndarray    # (M,) int32 planted truth
    col_labels: np.ndarray    # (N,) int32
    nnz: int | None = None    # stored entries of a sparse matrix


def _labels(rng: np.random.Generator, n: int, k: int) -> np.ndarray:
    lab = np.arange(n) % k
    rng.shuffle(lab)
    return lab.astype(np.int32)


def _draw(seed: int, n_rows: int, n_cols: int, k: int, d: int,
          signal: float):
    rng = np.random.default_rng(seed)
    rows, cols = _labels(rng, n_rows, k), _labels(rng, n_cols, d)
    mu = rng.uniform(0.0, signal, (k, d)).astype(np.float32)
    key = jax.random.key(int(rng.integers(2**31)))
    return rows, cols, mu, key


@functools.partial(jax.jit, static_argnames=("noise", "out_sharding"))
def _dense(key, mu, r, c, *, noise, out_sharding=None):
    shape = (r.shape[0], c.shape[0])
    z = jax.random.normal(key, shape, jnp.float32)
    a = mu[r][:, c] + noise * z
    if out_sharding is not None:
        a = jax.lax.with_sharding_constraint(a, out_sharding)
    return a


def plant_dense(seed: int, n_rows: int, n_cols: int, k: int, d: int, *,
                signal: float, noise: float, sharding=None) -> Planted:
    """Dense planted matrix, float32, placed by ``sharding`` if given."""
    rows, cols, mu, key = _draw(seed, n_rows, n_cols, k, d, signal)
    if sharding is None:
        a = _dense(key, jnp.asarray(mu), jnp.asarray(rows), jnp.asarray(cols),
                   noise=float(noise))
    else:
        fn = jax.jit(functools.partial(_dense.__wrapped__, noise=float(noise),
                                       out_sharding=sharding),
                     out_shardings=sharding)
        a = fn(key, jnp.asarray(mu), jnp.asarray(rows), jnp.asarray(cols))
    return Planted(a.block_until_ready(), rows, cols)


@functools.partial(jax.jit, static_argnames=("shape", "density"))
def _support_count(key, *, shape, density):
    return jnp.sum(jax.random.bernoulli(key, density, shape), dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("shape", "density", "nnz"))
def _support(key, *, shape, density, nnz):
    mask = jax.random.bernoulli(key, density, shape)
    r, c = jnp.nonzero(mask, size=nnz)
    return r.astype(jnp.int32), c.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("noise",))
def _values(key, mu, row_lab, col_lab, r, c, *, noise):
    z = jax.random.normal(key, r.shape, jnp.float32)
    return mu[row_lab[r], col_lab[c]] + noise * z


def plant_sparse(seed: int, n_rows: int, n_cols: int, k: int, d: int, *,
                 signal: float, noise: float, density: float,
                 support_seed: int) -> Planted:
    """Sparse planted matrix as a canonical BCOO (row-major, unique).

    The support is drawn from ``support_seed``, which the configuration
    fixes, and the labels and values from ``seed``. So every seed stores
    the same number of entries with the same row and column counts: the
    shapes the program compiles (the dual-ELL widths, ``nse``) are the
    same for every seed, while the planted clusters, and so which entries
    belong to which cluster, are drawn anew.
    """
    from jax.experimental import sparse as jsparse

    rows, cols, mu, key = _draw(seed, n_rows, n_cols, k, d, signal)
    skey = jax.random.key(support_seed)
    shape = (n_rows, n_cols)
    nnz = int(_support_count(skey, shape=shape, density=density))
    r, c = _support(skey, shape=shape, density=density, nnz=nnz)
    vals = _values(key, jnp.asarray(mu), jnp.asarray(rows), jnp.asarray(cols),
                   r, c, noise=float(noise))
    a = jsparse.BCOO((vals, jnp.stack([r, c], axis=1)), shape=shape,
                     indices_sorted=True, unique_indices=True)
    jax.block_until_ready(a.data)
    return Planted(a, rows, cols, nnz=nnz)


def make(config: dict, seed: int, sharding=None) -> Planted:
    """The planted matrix that a configuration file describes."""
    spec = config
    common = dict(signal=spec["signal"], noise=spec["noise"])
    if spec["format"] == "dense":
        return plant_dense(seed, spec["rows"], spec["cols"], spec["k"],
                           spec["d"], sharding=sharding, **common)
    if spec["format"] == "bcoo":
        return plant_sparse(seed, spec["rows"], spec["cols"], spec["k"],
                            spec["d"], density=spec["density"],
                            support_seed=spec["support_seed"], **common)
    raise ValueError(f"unknown data format {spec['format']!r}")
