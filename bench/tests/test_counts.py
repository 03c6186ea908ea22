"""``counts`` and ``peaks``: the work count of a fit is a floor under what
XLA compiles for the chip, and does not depend on how the work is done."""

import os

import pytest

import counts
import peaks
import spec

#: the fit configuration of each cell, with its chips, and the four-chip
#: form of it that PERF.md keeps for a later cell: the published 56,200
#: rows sharded over a 2 x 2 mesh
FIT_CONFIGS = (("gtex_v8_tissues", 1, {}),
               ("gtex_v8_tissues", 4, {"rows": 56200,
                                       "mesh": {"shape": [2, 2],
                                                "axes": ["data", "model"]},
                                       "min_cocluster_rows": 1040}))


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_fail():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
    with pytest.raises(KeyError):
        peaks.least_seconds(1.0, 1.0, "cpu")


def test_least_time_is_the_larger_bound():
    kind = "TPU v5 lite"
    assert peaks.least_seconds(197e12, 0.0, kind) == pytest.approx(1.0)
    assert peaks.least_seconds(0.0, 819e9 * 2, kind) == pytest.approx(2.0)


def test_dense_fit_count_matches_the_formula():
    flops, nbytes = counts.lamc_fit(phi=51840, psi=17382, k=54, d=54,
                                    svd_iters=4)
    e = 51840 * 17382
    assert counts.sketch_rank(54, 54) == 7
    assert nbytes == 7 * e * 4
    assert flops == e * (2 + 4 * 7 * 4 + 4 * 7)


def test_sparse_count_is_per_stored_entry():
    flops, nbytes = counts.lamc_fit(phi=100000, psi=5000, k=10, d=10,
                                    svd_iters=4, nnz=1000)
    assert nbytes == 7 * 1000 * 8
    assert flops == 1000 * (2 + 4 * 5 * 4 + 4 * 5)


@pytest.mark.parametrize("knob", [("assign_impl", "pallas"),
                                  ("qr_method", "cholesky"),
                                  ("spmm_impl", "tiled")])
def test_count_ignores_how_the_work_is_done(knob):
    import cells

    _, w, config, traffic = spec.cell("dense_fit")
    cell = cells.make(config, traffic, 0, w["chips"])
    changed = dict(config, lamc=dict(config["lamc"], **dict([knob])))
    other = cells.make(changed, traffic, 0, w["chips"])
    from repro.core.partition import PartitionPlan

    plan = PartitionPlan(65536, 16384, m=1, n=1, phi=65536, psi=16384, t_p=1)
    for c in (cell, other):
        c.plan = plan
        c.planted = type("P", (), {"nnz": None})()
    assert cell.work() == other.work()


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("name,chips,change", FIT_CONFIGS,
                         ids=[f"{n}-{c}chip" for n, c, _ in FIT_CONFIGS])
def test_count_is_at_most_what_xla_compiles(topo, name, chips, change):
    """At the cell's sizes, compiled for a described v5e. XLA's cost
    analysis counts a loop body once, so both sides are taken at
    ``svd_iters=1``, where the body runs once."""
    import described

    config = spec.load_json(spec.BENCH / "configs" / f"{name}.json")
    change = dict(change)
    lamc = dict(config["lamc"], svd_iters=1)
    if "min_cocluster_rows" in change:
        lamc["min_cocluster_rows"] = change.pop("min_cocluster_rows")
    config = dict(config, lamc=lamc, **change)
    compiled, plan = described.fit_program(config, topo, chips)
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    c = config["lamc"]
    k, d = c["n_row_clusters"], c["n_col_clusters"]
    flops, nbytes = counts.lamc_fit(
        phi=plan.phi, psi=plan.psi, k=k, d=d, svd_iters=1, t_p=plan.t_p,
        blocks_per_device=max(plan.blocks_per_resample // chips, 1),
        nnz=config.get("support", {}).get("nnz"))
    assert flops <= cost["flops"]
    assert nbytes <= cost["bytes accessed"]
