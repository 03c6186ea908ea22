"""What decides ``correct`` fails when it should: the control (the
reference in bfloat16 in the program's place) and each fault that a cell
can have, with the timed path broken underneath a whole run. At a test
run's size on the CPU, against the committed limits."""

import pytest

import tiny

ALTER_LABELS = '''
import repro.core.lamc as L
_orig = L._lamc_jit
def _broken(a, cfg, plan, operator=None, block_mask=None):
    merged, ar, ac = _orig(a, cfg, plan, operator, block_mask)
    rl = merged.row_labels
    rl = rl.at[:8].set((rl[:8] + 1) % cfg.n_row_clusters)
    return merged._replace(row_labels=rl), ar, ac
L._lamc_jit = _broken
'''

# the atom skips half of the columns: their labels are noise
ATOM_HALF_LEFT_OUT = '''
import jax.numpy as jnp
import repro.core.spectral as S
_orig = S.scc
def _broken(key, a, *args, **kw):
    n = a.shape[1]
    return _orig(key, a * (jnp.arange(n) < n // 2)[None, :], *args, **kw)
S.scc = _broken
'''

HALF_LEFT_OUT = '''
import repro.core.merging as M
_orig = M.cluster_signatures
def _broken(feats, labels, k):
    half = feats.shape[0] // 2
    return _orig(feats[:half], labels[:half], k)
M.cluster_signatures = _broken
'''

NO_EXCHANGE = '''
import jax
jax.lax.psum = lambda x, axis_name, **kw: x
'''

ALTER_SCORES = '''
import repro.kernels.ops as O
_orig = O.cosine_assign
def _broken(x, sigs, tile_p=512):
    labels, scores = _orig(x, sigs, tile_p)
    return (labels + 1) % sigs.shape[0], scores
O.cosine_assign = _broken
'''


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("workload,patch,devices", [
    ("tiny_dense_fit", ALTER_LABELS, 1),
    ("tiny_sparse_fit", ALTER_LABELS, 1),
    ("tiny_dense_fit", HALF_LEFT_OUT, 1),
    ("tiny_dense_fit", ATOM_HALF_LEFT_OUT, 1),
    ("tiny_doc_term_fit", ALTER_LABELS, 1),
    ("tiny_doc_term_fit", ATOM_HALF_LEFT_OUT, 1),
    ("tiny_mesh_fit", HALF_LEFT_OUT, 4),
    ("tiny_mesh_fit", NO_EXCHANGE, 4),
    ("tiny_serve", ALTER_SCORES, 1),
], ids=["fit-answer-altered", "sparse-answer-altered", "fit-half-left-out",
        "fit-atom-half-left-out", "doc-term-answer-altered",
        "doc-term-atom-half-left-out",
        "mesh-half-left-out", "mesh-exchange-left-out", "serve-answer-altered"])
def test_a_broken_timed_path_is_not_correct(tree, workload, patch, devices):
    rc, sound, err = tiny.run(tree, workload, devices=devices)
    assert rc == 0 and sound["correct"] is True, err[-2000:]
    rc, broken, err = tiny.run(tree, workload, devices=devices, patch=patch)
    assert rc == 0, err[-3000:]
    assert broken["correct"] is False, broken["checks"]
    assert broken["failed"] > 0
    off = [n for n, c in broken["checks"].items() if not c["value"] <= c["limit"]]
    assert off, broken["checks"]


@pytest.mark.parametrize("workload", ["tiny_dense_fit", "tiny_sparse_fit",
                                      "tiny_doc_term_fit", "tiny_serve"])
def test_the_control_fails_and_the_program_passes(tree, workload):
    import json

    config = tiny.CONFIGS[tiny.CELLS[workload][0]]
    limits = config["limits"]
    held = lambda nums: {n: v for n, v in nums.items() if n in limits}
    for r in tiny.control(tree, workload, [3, 4, 5]):
        assert all(v <= limits[n] for n, v in held(r["program"]).items()), r
        assert any(not v <= limits[n] for n, v in held(r["control"]).items()), \
            json.dumps(r)
        if "half_columns" in r:           # a fit: the atom's fault fails
            assert any(not v <= limits[n] for n, v in
                       held(r["half_columns"]).items()), json.dumps(r)
