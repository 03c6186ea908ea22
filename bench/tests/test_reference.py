"""The reference's sparse product summed in chunks of entries
(``reference._product_sparse``) against the product of all the entries at
once, in float64 on the host."""

import jax.numpy as jnp
import numpy as np
import pytest

import reference

SHAPE = (300, 200)


def _bcoo_parts(seed: int, nnz: int = 5_000):
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.integers(0, SHAPE[0], nnz),
                    rng.integers(0, SHAPE[1], nnz)], axis=1)
    vals = rng.standard_normal(nnz)
    return jnp.asarray(vals, jnp.float32), jnp.asarray(idx, jnp.int32)


@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("chunk", [512, 1000, 4096, 5000, 1 << 20])
def test_chunked_product_matches_unchunked(transpose, chunk):
    """A chunk that divides the entries, one that leaves a remainder, one
    as large as all of them and one larger give the same sums."""
    vals, idx = _bcoo_parts(chunk)
    rows = SHAPE[0] if transpose else SHAPE[1]
    x = np.random.default_rng(1).standard_normal((rows, 7)).astype(np.float32)
    a = np.zeros(SHAPE)
    np.add.at(a, tuple(np.asarray(idx).T), np.asarray(vals, np.float64))
    whole = (a.T if transpose else a) @ x.astype(np.float64)
    parts = np.asarray(reference._product_sparse(
        vals, idx, jnp.asarray(x), shape=SHAPE, transpose=transpose,
        dtype=jnp.float32, chunk=chunk))
    assert parts.shape == whole.shape
    assert np.max(np.abs(parts - whole)) <= 1e-5 * np.max(np.abs(whole))


def test_scc_in_chunks_finds_the_same_subspace(monkeypatch):
    from jax.experimental import sparse as jsparse

    vals, idx = _bcoo_parts(3, nnz=20_000)
    a = jsparse.BCOO((jnp.abs(vals) + 0.1, idx), shape=SHAPE)
    a = a.sum_duplicates()
    whole = reference.scc(a, 4, 4, 5)
    monkeypatch.setattr(reference, "PRODUCT_CHUNK_BYTES", 4 * 14 * 1000)
    parts = reference.scc(a, 4, 4, 5)
    np.testing.assert_allclose(parts["singular"], whole["singular"],
                               rtol=1e-5)
