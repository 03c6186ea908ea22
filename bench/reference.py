"""Plain reference for what the timed path answers, and its control.

Nothing here imports the program. The reference takes the data the
benchmark made, the planted truth, and the answers under check (labels,
the anchor ids a fitted model carries, served labels and scores), and
recomputes every continuous output from them in float64 on the host:

- a fit's serving signatures: for each cluster, the mean of its members'
  anchor features, centred by the mean over all points, scaled to unit
  length (DESIGN.md §10), for rows over ``A[:, anchor_cols]`` and for
  columns over ``A[anchor_rows, :].T``; and the centring means;
- a served request's scores: ``(x[anchor] - mean) . sig`` against every
  cluster signature, and their top ``k``;
- the atom a fit runs (:func:`scc`): Dhillon's spectral co-clustering of
  the data itself, with nothing taken from the answer: the bipartite
  normalisation, the leading singular subspace by subspace iteration,
  the stacked embedding and k-means. Its labels are what a fit's labels
  are compared with.

The control is the same computation in bfloat16 (data, arithmetic and
result), the step below the float32 that the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def nmi(a: np.ndarray, b: np.ndarray) -> float:
    """Normalized mutual information, arithmetic normalization, in [0, 1]."""
    a = np.asarray(a, np.int64)
    b = np.asarray(b, np.int64)
    if a.shape != b.shape or a.size == 0 or a.min() < 0 or b.min() < 0:
        return 0.0
    na, nb = int(a.max()) + 1, int(b.max()) + 1
    t = np.bincount(a * nb + b, minlength=na * nb).reshape(na, nb)
    t = t.astype(np.float64) / a.size
    pa, pb = t.sum(1), t.sum(0)
    nz = t > 0
    mi = float(np.sum(t[nz] * np.log(t[nz] / np.outer(pa, pb)[nz])))
    ha = -float(np.sum(pa[pa > 0] * np.log(pa[pa > 0])))
    hb = -float(np.sum(pb[pb > 0] * np.log(pb[pb > 0])))
    return 0.0 if ha + hb == 0 else 2.0 * mi / (ha + hb)


def anchor_slivers(a, anchor_rows, anchor_cols) -> tuple[np.ndarray, np.ndarray]:
    """``(A[:, anchor_cols] (M, q), A[anchor_rows, :].T (N, q))`` on the host.

    ``a`` is a dense device array (sharded or not) or a BCOO matrix; the
    sparse one is scattered from its stored entries.
    """
    ar = np.asarray(anchor_rows, np.int64)
    ac = np.asarray(anchor_cols, np.int64)
    if hasattr(a, "indices") and hasattr(a, "data"):
        m, n = a.shape
        idx = np.asarray(a.indices)
        val = np.asarray(a.data, np.float64)
        r, c = idx[:, 0], idx[:, 1]
        col_pos = np.full(n, -1, np.int64)
        col_pos[ac] = np.arange(ac.size)
        row_pos = np.full(m, -1, np.int64)
        row_pos[ar] = np.arange(ar.size)
        rf = np.zeros((m, ac.size))
        sel = col_pos[c] >= 0
        rf[r[sel], col_pos[c[sel]]] = val[sel]
        cf = np.zeros((n, ar.size))
        sel = row_pos[r] >= 0
        cf[c[sel], row_pos[r[sel]]] = val[sel]
        return rf, cf
    rf = np.asarray(jax.device_get(a[:, jnp.asarray(ac)]), np.float64)
    cf = np.asarray(jax.device_get(a[jnp.asarray(ar), :]), np.float64).T
    return rf, cf


def signatures(feats: np.ndarray, labels: np.ndarray, k: int):
    """Unit cluster signatures ``(k, q)`` and the centring mean ``(q,)``."""
    f = np.asarray(feats, np.float64)
    mean = f.mean(axis=0)
    onehot = np.zeros((f.shape[0], k))
    onehot[np.arange(f.shape[0]), labels] = 1.0
    sums = onehot.T @ (f - mean)
    counts = onehot.sum(axis=0)
    sig = sums / np.maximum(counts, 1.0)[:, None]
    norm = np.linalg.norm(sig, axis=1, keepdims=True)
    return sig / np.maximum(norm, 1e-12), mean


@jax.jit
def _signatures_bf16(feats, onehot):
    f = feats.astype(jnp.bfloat16)
    mean = jnp.mean(f, axis=0)
    sums = onehot.astype(jnp.bfloat16).T @ (f - mean)
    counts = jnp.sum(onehot, axis=0).astype(jnp.bfloat16)
    sig = sums / jnp.maximum(counts, 1)[:, None]
    norm = jnp.sqrt(jnp.sum(sig * sig, axis=1, keepdims=True))
    return sig / jnp.maximum(norm, jnp.bfloat16(1e-12)), mean


def signatures_control(feats: np.ndarray, labels: np.ndarray, k: int):
    """:func:`signatures` computed in bfloat16 on the default device."""
    onehot = np.zeros((feats.shape[0], k), np.float32)
    onehot[np.arange(feats.shape[0]), labels] = 1.0
    sig, mean = _signatures_bf16(jnp.asarray(feats, jnp.float32),
                                 jnp.asarray(onehot))
    return (np.asarray(sig.astype(jnp.float32), np.float64),
            np.asarray(mean.astype(jnp.float32), np.float64))


def scores(x_anchor: np.ndarray, mean: np.ndarray, sigs: np.ndarray) -> np.ndarray:
    """Every cluster's score ``(B, K)`` of requests restricted to the anchors."""
    f = np.asarray(x_anchor, np.float64) - np.asarray(mean, np.float64)
    return f @ np.asarray(sigs, np.float64).T


@jax.jit
def _scores_bf16(x_anchor, mean, sigs):
    f = x_anchor.astype(jnp.bfloat16) - mean.astype(jnp.bfloat16)
    return f @ sigs.astype(jnp.bfloat16).T


def scores_control(x_anchor, mean, sigs) -> np.ndarray:
    """:func:`scores` computed in bfloat16 on the default device."""
    s = _scores_bf16(jnp.asarray(x_anchor, jnp.float32),
                     jnp.asarray(mean, jnp.float32),
                     jnp.asarray(sigs, jnp.float32))
    return np.asarray(s.astype(jnp.float32), np.float64)


# -- the atom: spectral co-clustering of the data --------------------------

def _nearest(z: np.ndarray, c: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    d2 = (np.sum(z * z, 1)[:, None] - 2.0 * z @ c.T
          + np.sum(c * c, 1)[None, :])
    lab = np.argmin(d2, axis=1)
    return lab, np.maximum(d2[np.arange(z.shape[0]), lab], 0.0)


def kmeans(z: np.ndarray, k: int, rng: np.random.Generator, *,
           restarts: int = 4, max_iter: int = 100) -> np.ndarray:
    """Lloyd's k-means in float64, k-means++ seeded, to convergence; the
    labels of the restart with the least inertia."""
    best, best_inertia = None, np.inf
    for _ in range(restarts):
        c = np.empty((k, z.shape[1]))
        c[0] = z[rng.integers(z.shape[0])]
        d2 = np.sum((z - c[0]) ** 2, axis=1)
        for j in range(1, k):
            c[j] = z[rng.choice(z.shape[0], p=d2 / d2.sum())]
            d2 = np.minimum(d2, np.sum((z - c[j]) ** 2, axis=1))
        lab = None
        for _ in range(max_iter):
            new, dist = _nearest(z, c)
            if lab is not None and np.array_equal(new, lab):
                break
            lab = new
            counts = np.bincount(lab, minlength=k)
            for dim in range(z.shape[1]):
                sums = np.bincount(lab, weights=z[:, dim], minlength=k)
                c[counts > 0, dim] = sums[counts > 0] / counts[counts > 0]
        inertia = float(np.sum(dist))
        if inertia < best_inertia:
            best, best_inertia = lab, inertia
    return best.astype(np.int32)


def _is_sparse(a) -> bool:
    return hasattr(a, "indices") and hasattr(a, "data")


@functools.partial(jax.jit, static_argnames=("dtype",))
def _abs_degrees(a, keep, *, dtype):
    aa = jnp.abs(a.astype(dtype)) * keep.astype(dtype)[None, :]
    return jnp.sum(aa, axis=1, dtype=dtype), jnp.sum(aa, axis=0, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _abs_degrees_sparse(vals, idx, keep, *, shape, dtype):
    v = jnp.abs(vals.astype(dtype)) * keep.astype(dtype)[idx[:, 1]]
    return (jax.ops.segment_sum(v, idx[:, 0], shape[0]),
            jax.ops.segment_sum(v, idx[:, 1], shape[1]))


@functools.partial(jax.jit, static_argnames=("transpose", "dtype"))
def _product(a, x, *, transpose, dtype):
    """``A @ x`` or ``A.T @ x``, at full precision for float32 and in one
    bfloat16 pass for bfloat16."""
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    am, x = a.astype(dtype), x.astype(dtype)
    return jnp.matmul(am.T if transpose else am, x, precision=prec)


#: bytes of the ``(entries, p)`` float32 terms that a sparse product forms
#: at once. The TPU pads ``p`` to 128 lanes, so a chunk takes ``128 / p``
#: times this: at RCV1-v2's 60.8 M entries all at once would be 31 GB.
PRODUCT_CHUNK_BYTES = 1 << 28


@functools.partial(jax.jit,
                   static_argnames=("shape", "transpose", "dtype", "chunk"))
def _product_sparse(vals, idx, x, *, shape, transpose, dtype, chunk):
    """:func:`_product` over the stored entries of a sparse matrix, summed
    ``chunk`` entries at a time, so that its terms never take more than
    ``chunk * p`` elements."""
    src, dst = (idx[:, 0], idx[:, 1]) if transpose else (idx[:, 1], idx[:, 0])
    n_out = shape[1] if transpose else shape[0]
    xd = x.astype(dtype)

    def part(v, s, t):
        return jax.ops.segment_sum(v.astype(dtype)[:, None] * xd[s], t, n_out)

    nnz = vals.shape[0]
    chunk = min(chunk, nnz)
    full = nnz // chunk * chunk

    def step(i, acc):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk)
        return acc + part(cut(vals), cut(src), cut(dst))

    acc = jax.lax.fori_loop(0, nnz // chunk, step,
                            jnp.zeros((n_out, x.shape[1]), dtype))
    if full < nnz:
        acc = acc + part(vals[full:], src[full:], dst[full:])
    return acc


def _orth(y: np.ndarray) -> np.ndarray:
    return np.linalg.qr(y)[0]


def scc(a, k: int, d: int, seed, *, dtype=jnp.float32, iters: int = 8,
        oversample: int = 10, keep_cols: float = 1.0) -> dict:
    """Spectral co-clustering of the device matrix ``a`` (M, N), dense or
    a BCOO of stored entries.

    ``A_n = D1^-1/2 A D2^-1/2`` with the degrees taken on ``|A|``; the top
    ``l + 1`` singular triplets of ``A_n`` (``l = max(k, d).bit_length()``)
    by subspace iteration with ``oversample`` extra columns; the leading
    (trivial) pair dropped; ``Z = [D1^-1/2 U ; D2^-1/2 V]``; k-means on the
    stacked ``Z`` when ``k == d``, else on each side apart.

    The products over ``a`` run on the device (at ``Precision.HIGHEST`` in
    float32, the precision the configurations state); the degrees come
    back as float64, and the orthogonalisation, the small SVD, the
    embedding and k-means are float64 on the host. ``dtype=bfloat16`` is
    the control: the data and the products in bfloat16. ``iters`` and
    ``keep_cols < 1`` (the last columns left out of the products) are there
    to read faults of the atom: too few steps, half of the data skipped.

    Returns the row and column labels and the singular values.
    """
    m, n = a.shape
    l = max(k, d).bit_length()
    p = min(l + 1 + oversample, m, n)
    keep = jnp.asarray(np.arange(n) < int(round(keep_cols * n)))
    if _is_sparse(a):
        degrees = _abs_degrees_sparse(a.data, a.indices, keep, shape=(m, n),
                                      dtype=dtype)
        chunk = PRODUCT_CHUNK_BYTES // (jnp.dtype(dtype).itemsize * p)
        product = functools.partial(_product_sparse, a.data, a.indices,
                                    shape=(m, n), chunk=max(chunk, 1))
    else:
        degrees = _abs_degrees(a, keep, dtype=dtype)
        product = functools.partial(_product, a)
    r1, r2 = jax.device_get(degrees)
    s1 = 1.0 / np.sqrt(np.maximum(np.asarray(r1, np.float64), 1e-8))
    s2 = 1.0 / np.sqrt(np.maximum(np.asarray(r2, np.float64), 1e-8))

    kept = np.asarray(keep, np.float64)[:, None]

    def prod(x, transpose):
        """``A_n @ x`` or ``A_n.T @ x``, the left-out columns zero."""
        scale_in, scale_out = (s1, s2 * kept[:, 0]) if transpose else (s2, s1)
        x_in = scale_in[:, None] * x * (1.0 if transpose else kept)
        y = product(jnp.asarray(x_in, jnp.float32), transpose=transpose,
                    dtype=dtype)
        return scale_out[:, None] * np.asarray(jax.device_get(y), np.float64)

    rng = np.random.default_rng(seed)
    x = _orth(rng.standard_normal((n, p)))
    for _ in range(iters):
        x = _orth(prod(_orth(prod(x, False)), True))
    q = _orth(prod(x, False))                               # (M, p)
    ub, s, vt = np.linalg.svd(prod(q, True).T, full_matrices=False)
    u = q @ ub
    z_rows = s1[:, None] * u[:, 1:l + 1]
    z_cols = s2[:, None] * vt[1:l + 1].T
    if k == d:
        lab = kmeans(np.concatenate([z_rows, z_cols]), k, rng)
        rows, cols = lab[:m], lab[m:]
    else:
        rows, cols = kmeans(z_rows, k, rng), kmeans(z_cols, d, rng)
    return {"row_labels": rows, "col_labels": cols, "singular": s[:l + 1]}
