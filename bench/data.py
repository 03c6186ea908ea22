"""Data of a cell, made on the device from ``--seed``.

Every maker follows the planted co-cluster model of
``repro.data.planted_cocluster_matrix``: balanced row and column labels
in a shuffled order, one mean per (row cluster, column cluster) cell
drawn uniformly from ``[0, signal]`` and Gaussian noise of scale
``noise``. A sparse matrix stores those values on a support of the given
density, drawn by one of two laws that a configuration's ``"support"``
group selects by its ``"law"``:

- ``"bernoulli"`` (the default): every cell is stored with probability
  ``density``, independently;
- ``"doc_term"``: a document-term support. Row i (a document) has a
  log-normal length of shape ``"sigma"`` whose mean is ``density * cols``,
  and its terms are drawn from a Zipf law of ``"exponent"`` over the
  columns (see :func:`plant_doc_term`).

The matrix itself is drawn by ``jax.random`` in jitted calls, so it never
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


# -- the document-term support ---------------------------------------------

#: the most term draws a document may take, as a multiple of the columns
DOC_TERM_MAX_DRAWS = 16
#: points of the table of distinct terms against draws (geometric in draws)
_DISTINCT_GRID = 512


def _zipf(n_cols: int, exponent: float):
    """Zipf probabilities of the term ranks 1..n_cols, float32."""
    w = jnp.arange(1, n_cols + 1, dtype=jnp.float32) ** -exponent
    return w / jnp.sum(w)


@functools.partial(jax.jit,
                   static_argnames=("n_rows", "n_cols", "mean", "sigma",
                                    "exponent"))
def _doc_draws(key, *, n_rows, n_cols, mean, sigma, exponent):
    """How many term ids each document draws, ``(n_rows,)`` int32.

    Lengths are log-normal with shape ``sigma``, scaled so that their mean
    over the documents is ``mean`` exactly, and clipped to
    ``[1, n_cols]``. A document that draws ``t`` ids with replacement
    keeps ``D(t) = sum_r 1 - (1 - p_r)^t`` distinct terms on average, so
    it draws ``D^-1(length)`` ids, read from a table of ``D`` at
    ``_DISTINCT_GRID`` points (interpolated in ``log t``): the stored row
    lengths then follow the log-normal, not a law bent by duplicates.
    Lengths beyond ``D(DOC_TERM_MAX_DRAWS * n_cols)`` take that many draws.
    """
    length = jnp.exp(sigma * jax.random.normal(key, (n_rows,), jnp.float32))
    length = jnp.clip(length * (mean * n_rows / jnp.sum(length)), 1.0,
                      float(n_cols))
    log1m_p = jnp.log1p(-_zipf(n_cols, exponent))
    t = jnp.geomspace(1.0, float(DOC_TERM_MAX_DRAWS * n_cols), _DISTINCT_GRID)
    distinct = jnp.sum(-jnp.expm1(t[:, None] * log1m_p[None, :]), axis=1)
    draws = jnp.exp(jnp.interp(length, distinct, jnp.log(t)))
    return jnp.maximum(jnp.round(draws), 1.0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_cols", "exponent", "total"))
def _doc_term_support(key, draws, *, n_cols, exponent, total):
    """The canonical support of ``draws``: ``(rows, cols, nnz)``, where the
    first ``nnz`` of the ``(total,)`` arrays are the stored entries in
    row-major order, each once, and the rest is zero.

    Each of the ``total = sum(draws)`` draws names its document and a
    term rank by inverse CDF of the Zipf law, searched on the tail sums
    (which float32 holds to its relative precision down to the rarest
    term) with a uniform of 32 bits; the ranks are mapped to columns by
    one permutation, so frequent terms are not the low column ids. Every
    array has ``total`` or ``n_cols`` elements.
    """
    k_term, k_perm = jax.random.split(key)
    starts = jnp.cumsum(draws) - draws
    rows = jnp.cumsum(jnp.zeros(total, jnp.int32).at[starts].set(1)) - 1
    tail = jnp.cumsum(_zipf(n_cols, exponent)[::-1])   # the j + 1 rarest
    bits = jax.random.bits(k_term, (total,), jnp.uint32)
    u = (bits.astype(jnp.float32) + 0.5) * jnp.float32(2.0 ** -32)
    rank = n_cols - 1 - jnp.searchsorted(tail, u * tail[-1])
    perm = jax.random.permutation(k_perm, n_cols).astype(jnp.int32)
    rows, cols = jax.lax.sort((rows, perm[rank]), num_keys=2)
    new = jnp.concatenate([jnp.ones((1,), bool),
                           (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])])
    at = jnp.where(new, jnp.cumsum(new, dtype=jnp.int32) - 1, total)
    zero = jnp.zeros(total, jnp.int32)
    return (zero.at[at].set(rows, mode="drop"),
            zero.at[at].set(cols, mode="drop"),
            jnp.sum(new, dtype=jnp.int32))


def plant_doc_term(seed: int, n_rows: int, n_cols: int, k: int, d: int, *,
                   signal: float, noise: float, density: float,
                   support_seed: int, sigma: float,
                   exponent: float) -> Planted:
    """Sparse planted matrix on a document-term support, as a canonical
    BCOO (row-major, unique).

    Rows are documents and columns terms. Document lengths (distinct
    terms stored) are log-normal with shape ``sigma`` and mean ``density
    * n_cols``, so about ``density * n_rows * n_cols`` entries are stored;
    the terms follow a Zipf law of ``exponent`` over the columns. As in
    :func:`plant_sparse`, the support depends on ``support_seed`` alone
    and the labels and values on ``seed``, so every seed compiles the
    same shapes.
    """
    from jax.experimental import sparse as jsparse

    rows, cols, mu, key = _draw(seed, n_rows, n_cols, k, d, signal)
    k_len, k_sup = jax.random.split(jax.random.key(support_seed))
    draws = _doc_draws(k_len, n_rows=n_rows, n_cols=n_cols,
                       mean=float(density * n_cols), sigma=float(sigma),
                       exponent=float(exponent))
    total = int(np.asarray(draws).sum(dtype=np.int64))
    if total >= 2**31:
        raise ValueError(f"{total} term draws do not fit int32 indexing")
    r, c, nnz = _doc_term_support(k_sup, draws, n_cols=n_cols,
                                  exponent=float(exponent), total=total)
    r, c = r[:int(nnz)], c[:int(nnz)]
    vals = _values(key, jnp.asarray(mu), jnp.asarray(rows), jnp.asarray(cols),
                   r, c, noise=float(noise))
    a = jsparse.BCOO((vals, jnp.stack([r, c], axis=1)),
                     shape=(n_rows, n_cols), indices_sorted=True,
                     unique_indices=True)
    del r, c
    jax.block_until_ready(a.data)
    return Planted(a, rows, cols, nnz=int(vals.shape[0]))


@functools.partial(jax.jit, static_argnames=("shape",))
def _counts(idx, *, shape):
    m, n = shape
    r, c = idx[:, 0], idx[:, 1]
    gn = -(-n // 128)
    tiles = jnp.zeros(-(-m // 128) * gn, bool)
    tiles = tiles.at[(r // 128) * gn + c // 128].set(True)
    return (jnp.zeros(m, jnp.int32).at[r].add(1),
            jnp.zeros(n, jnp.int32).at[c].add(1),
            jnp.sum(tiles, dtype=jnp.int32))


def support_counts(a) -> tuple[np.ndarray, np.ndarray, int]:
    """Stored entries of each row and each column of the BCOO ``a``, and
    its occupied 128 x 128 tiles."""
    rows, cols, tiles = _counts(a.indices, shape=tuple(a.shape))
    return np.asarray(rows), np.asarray(cols), int(tiles)


def support_summary(a) -> dict:
    """What sizes the program's sparse operands: ``nnz``, ``row_width``
    (the longest row), ``col_width`` (the fullest column) and
    ``tiles_128`` (occupied 128 x 128 tiles)."""
    rows, cols, tiles = support_counts(a)
    return {"nnz": int(a.nse), "row_width": int(rows.max()),
            "col_width": int(cols.max()), "tiles_128": tiles}


def make(config: dict, seed: int, sharding=None) -> Planted:
    """The planted matrix that a configuration file describes."""
    spec = config
    common = dict(signal=spec["signal"], noise=spec["noise"])
    if spec["format"] == "dense":
        return plant_dense(seed, spec["rows"], spec["cols"], spec["k"],
                           spec["d"], sharding=sharding, **common)
    if spec["format"] != "bcoo":
        raise ValueError(f"unknown data format {spec['format']!r}")
    sparse = dict(density=spec["density"], support_seed=spec["support_seed"],
                  **common)
    support = spec.get("support", {})
    law = support.get("law", "bernoulli")
    if law == "bernoulli":
        return plant_sparse(seed, spec["rows"], spec["cols"], spec["k"],
                            spec["d"], **sparse)
    if law == "doc_term":
        return plant_doc_term(seed, spec["rows"], spec["cols"], spec["k"],
                              spec["d"], sigma=support["sigma"],
                              exponent=support["exponent"], **sparse)
    raise ValueError(f"unknown support law {law!r}")
