"""``bench/probe.py`` and the support-sized operands of ``bench/described.py``
on a small document-term configuration, on the CPU."""

import json

import numpy as np
import pytest

import data
import described
import probe

CONFIG = {"format": "bcoo", "rows": 2048, "cols": 512, "k": 4, "d": 4,
          "signal": 5.0, "noise": 0.2, "density": 0.05, "support_seed": 0,
          "support": {"law": "doc_term", "sigma": 0.8, "exponent": 1.0}}


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    out = tmp_path_factory.mktemp("probe") / "probe.jsonl"
    assert probe.main(["--config", json.dumps(CONFIG), "--seeds", "5", "6",
                       "--k", "4", "--q", "16", "--out", str(out)]) == 0
    return [json.loads(ln) for ln in out.read_text().splitlines()]


def test_probe_reads_the_support_each_seed_shares(lines):
    draws = [ln for ln in lines if ln["probe"] == "draw"]
    assert [d["seed"] for d in draws] == [5, 6]
    a, b = draws
    assert a["support_sha256"] == b["support_sha256"]
    assert a["values_sha256"] != b["values_sha256"]
    want = data.support_summary(data.make(CONFIG, 5).a)
    assert {k: a[k] for k in want} == want
    assert a["row_quantiles"]["1.0"] == want["row_width"]
    assert a["top_cols"][0] == want["col_width"]
    assert abs(a["nnz_share"] - 1.0) < 0.02


def test_probe_runs_the_reference_and_the_check_on_each_seed(lines):
    refs = [ln for ln in lines if ln["probe"] == "reference"]
    assert [(r["seed"], r["k"]) for r in refs] == [(5, 4), (6, 4)]
    for r in refs:
        assert sum(r["row_sizes"]) == CONFIG["rows"]
        assert sum(r["col_sizes"]) == CONFIG["cols"]
        assert 0.0 <= r["nmi_rows"] <= 1.0 and 0.0 <= r["nmi_cols"] <= 1.0
        assert r["scc_s"] > 0 and r["check_s"] > 0


def test_operand_bytes_follow_the_support():
    """Dual-ELL: a float32 value and an int32 index a padded slot, rows
    padded to the longest row and columns to the fullest column; tiled:
    a float32 128 x 128 tile and three int32 ids an occupied tile."""
    summary = {"nnz": 10, "row_width": 3, "col_width": 7, "tiles_128": 5}
    shape = (1000, 300)
    assert described.operand_bytes("dual_ell", shape, summary) == \
        1000 * 3 * 8 + 300 * 7 * 8
    assert described.operand_bytes("tiled", shape, summary) == \
        5 * (128 * 128 * 4 + 12)


def test_described_sizes_a_sparse_cell_by_the_support_it_plants(lines):
    draw = next(ln for ln in lines if ln["probe"] == "draw")
    got = described.support_summary(CONFIG)
    assert got == {k: draw[k] for k in got}
    bytes_ = draw["operand_bytes"]
    assert bytes_ == {r: described.operand_bytes(r, (2048, 512), got)
                      for r in probe.ROUTES}
    assert np.all(np.asarray(list(bytes_.values())) > 0)
