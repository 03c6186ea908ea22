"""The host metrics read the program's spans: a fit's ``dispatch``, its root
less its ``wait``, and its ``compile`` spans; a program whose span tree lacks
them (one fenced ``pipeline`` span per fit) gives nothing, and nothing raises."""

import pytest

import spec

#: span durations (s) of three fits of each span tree
NEW_TREE = {"lamc": [0.154, 0.155, 0.156], "plan": [3e-4] * 3,
            "dispatch": [7e-4] * 3, "wait": [0.153, 0.154, 0.155],
            "finalize": [2e-5] * 3}
OLD_TREE = {"lamc": [0.154, 0.155, 0.156], "plan": [3e-4] * 3,
            "pipeline": [0.1535] * 3, "finalize": [2e-5] * 3}


def ctx(spans, kind="fit", fits=3):
    return {"kind": kind, "trace": None, "device_kind": "TPU v5 lite",
            "spans": spans, "stats": {"fits": fits}, "work": None,
            "registry": None}


@pytest.mark.parametrize("name,want", [
    ("dispatch_ms", 0.7), ("fit_host_ms", 1.0), ("fit_compile_ms", 0.0)])
def test_reads_the_new_span_tree(name, want):
    assert spec.metric_reader(name)(ctx(NEW_TREE)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["dispatch_ms", "fit_host_ms",
                                  "fit_compile_ms"])
def test_nothing_from_the_old_span_tree_or_a_serve_cell(name):
    read = spec.metric_reader(name)
    assert read(ctx(OLD_TREE)) is None
    assert read(ctx(NEW_TREE, kind="serve")) is None
    assert read(ctx({})) is None


def test_compile_spans_are_summed_per_fit():
    spans = dict(NEW_TREE, compile=[0.5, 0.25, 0.75])
    assert spec.metric_reader("fit_compile_ms")(ctx(spans)) == \
        pytest.approx(500.0)


def test_mesh_fits_read_their_own_root():
    spans = {"distributed_lamc": [1.2, 1.0], "build_step": [1e-3] * 2,
             "dispatch": [1.0, 0.8], "wait": [0.19, 0.19],
             "finalize": [1e-5] * 2}
    assert spec.metric_reader("fit_host_ms")(ctx(spans, fits=2)) == \
        pytest.approx(910.0)
