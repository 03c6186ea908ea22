"""The whole harness on the CPU at a test run's size: every traffic mix
end to end, picked up by name from files added beside the committed ones."""

import filecmp
import json

import pytest

import tiny

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("checkout"))


def test_new_files_are_found_without_editing_existing_ones(tree):
    cmp = filecmp.dircmp(tiny.BENCH, tree / "bench",
                         ignore=["__pycache__", "testdata"])

    def changed(d):
        return d.diff_files + [f for sub in d.subdirs.values()
                               for f in changed(sub)]

    assert changed(cmp) == []
    added = set(cmp.subdirs["configs"].right_only)
    assert added == {f"{n}.json" for n in tiny.CONFIGS}
    assert cmp.subdirs["metrics"].right_only == ["window_fits.py"]


@pytest.mark.parametrize("workload,devices", [
    ("tiny_dense_fit", 1), ("tiny_sparse_fit", 1), ("tiny_doc_term_fit", 1),
    ("tiny_serve", 1), ("tiny_mesh_fit", 4)])
def test_every_mix_runs_end_to_end(tree, workload, devices):
    rc, last, err = tiny.run(tree, workload, devices=devices)
    assert rc == 0, err[-3000:]
    assert list(last) == CONTRACT_KEYS + ["checks"]
    assert last["correct"] is True, last["checks"]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "setup_s" in last["metrics"]
    moved = "serve_p99_ms" if workload == "tiny_serve" else "fit_s"
    assert moved in last["metrics"]
    assert all(v["unit"] for v in last["metrics"].values())
    assert last["device"]["count"] == devices
    tail = [ln for ln in err.strip().splitlines()][-len(last["checks"]):]
    assert all(ln.startswith("check ") and " limit=" in ln for ln in tail)


def test_a_traced_run_reports_per_layer_metrics(tree):
    rc, last, err = tiny.run(tree, "tiny_dense_fit", seconds=3.0, trace=1)
    assert rc == 0, err[-3000:]
    # the CPU trace holds no TPU plane: only what spans and counters give
    assert last["metrics"]["window_fits"]["value"] > 0
    assert "plan_ms" in last["metrics"]
    assert "fit_s" not in last["metrics"]


def test_the_measuring_path_refuses_a_cpu_device(tree):
    rc, last, err = tiny.run(tree, "tiny_dense_fit", skip_chip=False)
    assert rc != 0 and last is None
    assert "no TPU" in err


def test_a_tree_without_the_program_gives_no_result(tmp_path):
    root = tmp_path / "bare"
    root.mkdir()
    import shutil
    shutil.copytree(tiny.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(tiny.REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    rc, last, err = tiny.run(root, "dense_fit")
    assert rc != 0 and last is None


def test_benchmark_json_names_every_file_it_needs():
    bench = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (tiny.REPO / c["file"]).is_file()
        assert json.loads((tiny.REPO / c["file"]).read_text())["name"] == c["name"]
    for w in bench["workloads"]:
        assert (tiny.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["per_layer"]:
        assert (tiny.BENCH / "metrics" / f"{m['name']}.py").is_file()
