"""The planted data's support laws (``bench/data.py``), on the CPU.

The document-term law is read at 20,000 x 4,096 with RCV1-v2's mean
document length (75.6 terms), and lowered, with no data made, at
RCV1-v2's 804,414 x 47,236. The dense and Bernoulli makers are pinned to
the hashes they gave before the document-term law was added.
"""

import hashlib
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import data

M, N = 20_000, 4_096
DENSITY = 75.6 / N
SIGMA, EXPONENT = 0.8, 1.0
DOC_TERM = {"format": "bcoo", "rows": M, "cols": N, "k": 4, "d": 4,
            "signal": 4.0, "noise": 0.6, "density": DENSITY,
            "support_seed": 0,
            "support": {"law": "doc_term", "sigma": SIGMA,
                        "exponent": EXPONENT}}


def _sha(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def planted():
    return data.make(DOC_TERM, 2718281901)


@pytest.fixture(scope="module")
def counts(planted):
    return data.support_counts(planted.a)


def test_doc_term_support_is_canonical_and_unique(planted):
    idx = np.asarray(planted.a.indices).astype(np.int64)
    key = idx[:, 0] * N + idx[:, 1]
    assert planted.nnz == idx.shape[0] == planted.a.data.shape[0]
    assert np.all(np.diff(key) > 0)
    assert idx.min() >= 0 and idx[:, 0].max() < M and idx[:, 1].max() < N


def test_doc_term_support_follows_support_seed_alone(planted):
    other_seed = data.make(DOC_TERM, 7)
    np.testing.assert_array_equal(np.asarray(other_seed.a.indices),
                                  np.asarray(planted.a.indices))
    assert not np.array_equal(np.asarray(other_seed.a.data),
                              np.asarray(planted.a.data))
    other_support = data.make(dict(DOC_TERM, support_seed=1), 2718281901)
    assert _sha(other_support.a.indices) != _sha(planted.a.indices)


def test_doc_term_stores_the_density(planted):
    assert abs(planted.nnz / (DENSITY * M * N) - 1.0) < 0.01


def test_doc_term_columns_follow_zipf(counts):
    """Column counts against their rank fall with the law's exponent where
    they are neither saturated (in over 5% of documents) nor a handful."""
    cols = np.sort(counts[1])[::-1]
    rank = np.arange(1, N + 1)
    sel = (cols <= 0.05 * M) & (cols >= 100)
    assert rank[sel].max() > 5 * rank[sel].min()
    slope = np.polyfit(np.log(rank[sel]), np.log(cols[sel]), 1)[0]
    assert abs(slope + EXPONENT) < 0.1, slope
    assert cols[-1] > 0


def test_doc_term_frequent_terms_are_spread_over_the_columns(counts):
    fullest = np.argsort(counts[1])[::-1][:20]
    assert fullest.max() > N // 2 and fullest.min() < N // 2


@pytest.mark.parametrize("q", [0.5, 0.9, 0.99])
def test_doc_term_rows_follow_lognormal(counts, q):
    """Stored row lengths (distinct terms) have the log-normal's quantiles:
    the draws are calibrated against duplicates, not by one factor."""
    mu = np.log(DENSITY * N) - SIGMA ** 2 / 2
    want = np.exp(mu + SIGMA * NormalDist().inv_cdf(q))
    assert abs(np.quantile(counts[0], q) / want - 1.0) < 0.05


def test_support_summary_reads_widths_and_tiles():
    from jax.experimental import sparse as jsparse

    idx = jnp.asarray([[0, 0], [0, 5], [0, 200], [3, 5], [130, 5]], jnp.int32)
    a = jsparse.BCOO((jnp.ones(5), idx), shape=(300, 260))
    assert data.support_summary(a) == {"nnz": 5, "row_width": 3,
                                       "col_width": 3, "tiles_128": 3}


def _avals(jaxpr):
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                if hasattr(sub, "consts") and hasattr(sub, "jaxpr"):
                    yield from _avals(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    yield from _avals(sub)


def test_doc_term_draw_at_rcv1_shape_stays_in_nnz_memory():
    """Lowered and compiled from shapes alone at 804,414 x 47,236 (0.16%):
    no array has more elements than the draws (or the table of distinct
    counts), and the temporaries stay under 4 GB."""
    m, n, density = 804_414, 47_236, 0.0016
    total = int(1.2 * density * m * n)
    key = jax.eval_shape(lambda: jax.random.key(0))
    draws = jax.ShapeDtypeStruct((m,), jnp.int32)
    calls = [
        (lambda k: data._doc_draws(k, n_rows=m, n_cols=n, mean=density * n,
                                   sigma=SIGMA, exponent=EXPONENT), (key,)),
        (lambda k, d: data._doc_term_support(k, d, n_cols=n,
                                             exponent=EXPONENT, total=total),
         (key, draws))]
    bound = max(total, data._DISTINCT_GRID * n)
    assert bound < m * n // 100
    for fn, args in calls:
        jaxpr = jax.make_jaxpr(fn)(*args)
        largest = max(int(np.prod(a.shape)) for a in _avals(jaxpr.jaxpr)
                      if hasattr(a, "shape"))
        assert largest <= bound, largest
        mem = jax.jit(fn).lower(*args).compile().memory_analysis()
        assert mem.temp_size_in_bytes < 4e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("config,want", [
    ({"format": "dense", "rows": 300, "cols": 200, "k": 4, "d": 3,
      "signal": 4.0, "noise": 0.6},
     "326ab076d950bbd963b8eb76f60ca1cacec956528e5d1a895de19461a938941d"),
    ({"format": "bcoo", "rows": 600, "cols": 250, "k": 4, "d": 3,
      "signal": 5.0, "noise": 0.2, "density": 0.1, "support_seed": 3},
     "69797bd5acb255198a890e18fdbfba8b4d6a6ac7e66c0d3ccadfc259598fe98b"),
], ids=["dense", "bernoulli"])
def test_dense_and_bernoulli_makers_are_unchanged(config, want):
    p = data.make(config, 2718281901)
    arrays = ((p.a,) if config["format"] == "dense"
              else (p.a.data, p.a.indices))
    assert _sha(*arrays, p.row_labels, p.col_labels) == want


def test_an_unknown_support_law_is_refused():
    with pytest.raises(ValueError, match="support law"):
        data.make(dict(DOC_TERM, support={"law": "uniform"}), 1)
