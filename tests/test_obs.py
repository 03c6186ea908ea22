"""Telemetry layer (DESIGN.md §14): spans, metrics, export, instrumentation.

The load-bearing invariants pinned here:

  * spans nest correctly, time monotonically, and are exact no-ops when
    obs is disabled — the obs-enabled jaxpr-audit entries must stage to
    **identical** jaxprs as their plain twins (the telemetry layer adds
    zero primitives and zero host syncs to traced code);
  * histogram percentiles track ``np.percentile`` within one geometric
    bucket (≤ 25% relative), with bounded memory and NaN-when-empty —
    the contract the ``serve_lamc`` percentile path rides on;
  * a streaming fit's trace carries exactly one ``chunk`` span per
    non-empty chunk, with resume-skipped and recovery-refolded chunks
    marked ``replayed=True``;
  * ``run_with_recovery`` emits structured recovery events (the
    stale-checkpoint warning names the ignored step id);
  * ``benchio.merge_rows`` leaves a provenance sidecar next to every
    trajectory file.
"""

import importlib
import json
import math

import numpy as np
import pytest

from repro import benchio, obs
from repro import checkpoint as ckpt
from repro.runtime.fault_tolerance import FailureInjector, run_with_recovery

sfit = importlib.import_module("repro.streaming.fit")


@pytest.fixture
def obs_on():
    """Enable spans for one test, with a fresh trace; restore after."""
    was = obs.enabled()
    obs.configure(enabled=True)
    tr = obs.reset_trace()
    yield tr
    obs.configure(enabled=was)
    obs.reset_trace()


@pytest.fixture
def obs_off():
    was = obs.enabled()
    obs.configure(enabled=False)
    obs.reset_trace()
    yield
    obs.configure(enabled=was)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class TestSpans:
    def test_nesting_paths_and_attrs(self, obs_on):
        tr = obs_on
        with obs.span("root", a=1):
            with obs.span("child1"):
                with obs.span("leaf"):
                    pass
            with obs.span("child2") as c2:
                c2.set(k="v")
        walked = [(sp.name, depth, path) for sp, depth, path in tr.walk()]
        assert walked == [("root", 0, "root"), ("child1", 1, "root/child1"),
                          ("leaf", 2, "root/child1/leaf"),
                          ("child2", 1, "root/child2")]
        assert tr.find("root")[0].attrs == {"a": 1}
        assert tr.find("child2")[0].attrs == {"k": "v"}

    def test_timing_monotonic_and_contained(self, obs_on):
        tr = obs_on
        with obs.span("outer"):
            with obs.span("inner"):
                x = sum(range(1000))  # noqa: F841 — some real work
        outer, inner = tr.find("outer")[0], tr.find("inner")[0]
        assert outer.t_end >= outer.t_start
        assert inner.duration_s >= 0
        # child starts after parent and ends before the parent's exit
        assert inner.t_start >= outer.t_start
        assert inner.t_end <= outer.t_end
        assert inner.duration_s <= outer.duration_s

    def test_fence_returns_value_and_blocks(self, obs_on):
        import jax.numpy as jnp
        with obs.span("fenced") as sp:
            y = sp.fence(jnp.ones((8, 8)) * 3.0)
        assert float(y[0, 0]) == 3.0

    def test_exception_recorded_and_stack_popped(self, obs_on):
        tr = obs_on
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("x")
        sp = tr.find("boom")[0]
        assert sp.attrs["error"] == "ValueError"
        assert sp.t_end >= sp.t_start
        # the stack unwound: a new span is a root, not a child of "boom"
        with obs.span("after"):
            pass
        assert [r.name for r in tr.roots] == ["boom", "after"]

    def test_event_attaches_to_open_span_else_trace(self, obs_on):
        tr = obs_on
        obs.event("free", x=1)
        with obs.span("s"):
            obs.event("inside", y=2)
        assert [e["name"] for e in tr.events] == ["free"]
        assert [e["name"] for e in tr.find("s")[0].events] == ["inside"]

    def test_disabled_is_shared_noop_singleton(self, obs_off):
        s1, s2 = obs.span("a"), obs.span("b", k=1)
        assert s1 is s2  # one shared object: zero allocation per span
        with s1 as sp:
            assert sp.fence(42) == 42
            sp.set(ignored=True)
        obs.event("dropped")  # must not touch (or create) a trace
        tr = obs.current_trace()
        assert tr.roots == [] and tr.events == []


# ---------------------------------------------------------------------------
# obs adds nothing to traced programs
# ---------------------------------------------------------------------------


class TestJaxprNeutrality:
    @pytest.mark.parametrize("plain", ["lamc_dense", "streaming_chunk",
                                       "cosine_assign", "spmm_ata"])
    def test_obs_twin_traces_identically(self, plain):
        from repro.analysis import entry_points as ep
        a = ep.trace_entry(plain)
        b = ep.trace_entry(f"{plain}_obs")
        assert str(a) == str(b), (
            f"{plain}: telemetry changed the lowered program")

    def test_block_until_ready_is_traceable_noop(self):
        import jax
        import jax.numpy as jnp

        def f(x):
            with obs.span("s") as sp:
                return sp.fence(x + 1)

        was = obs.enabled()
        obs.configure(enabled=True)
        try:
            jaxpr = str(jax.make_jaxpr(f)(jnp.ones((4,))))
        finally:
            obs.configure(enabled=was)
        assert "add" in jaxpr and "callback" not in jaxpr


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


class TestHistogram:
    def test_percentiles_track_numpy_within_one_bucket(self):
        rng = np.random.default_rng(7)
        samples = rng.lognormal(mean=np.log(800.0), sigma=1.2, size=5000)
        h = obs.Histogram("lat")
        for v in samples:
            h.observe(float(v))
        for p in (10, 50, 90, 99):
            oracle = float(np.percentile(samples, p))
            est = h.percentile(p)
            # geometric buckets at ratio 1.25: within one bucket of exact
            assert oracle / 1.26 <= est <= oracle * 1.26, (p, est, oracle)

    def test_empty_is_nan(self):
        h = obs.Histogram("lat")
        assert math.isnan(h.percentile(50))
        snap = h.snapshot()
        assert snap["count"] == 0 and snap["min"] is None

    def test_single_sample_is_exact(self):
        h = obs.Histogram("lat").observe(123.4)
        assert h.percentile(0) == h.percentile(50) == h.percentile(100) \
            == pytest.approx(123.4)

    def test_bounded_memory(self):
        h = obs.Histogram("lat")
        n_cells = len(h.snapshot()["counts"])
        for v in np.random.default_rng(0).uniform(0.5, 1e9, size=10_000):
            h.observe(float(v))
        assert len(h.snapshot()["counts"]) == n_cells  # never grows
        assert h.count == 10_000

    def test_bad_buckets_rejected(self):
        with pytest.raises(ValueError, match="ascending"):
            obs.Histogram("h", buckets=(1.0, 1.0, 2.0))
        with pytest.raises(ValueError, match="at least one"):
            obs.Histogram("h", buckets=())


class TestRegistry:
    def test_counter_labels_and_negative_inc(self):
        reg = obs.Registry()
        c = reg.counter("dispatch")
        c.labels(op="spmm", tier="ref").inc()
        c.labels(op="spmm", tier="ref").inc()
        c.labels(tier="jnp", op="ata").inc()  # kwarg order is normalized
        snap = c.snapshot()
        assert snap["series"] == {"op=spmm,tier=ref": 2.0,
                                  "op=ata,tier=jnp": 1.0}
        with pytest.raises(ValueError, match=">= 0"):
            c.inc(-1)

    def test_type_conflict_is_loud(self):
        reg = obs.Registry()
        reg.counter("x")
        with pytest.raises(TypeError, match="already registered as counter"):
            reg.gauge("x")
        reg.histogram("h", buckets=(1.0, 2.0))
        with pytest.raises(ValueError, match="different"):
            reg.histogram("h", buckets=(1.0, 3.0))

    def test_snapshot_json_roundtrip_and_diff(self):
        reg = obs.Registry()
        reg.counter("c").inc(3)
        reg.gauge("g").set(1.5)
        reg.histogram("h", buckets=(10.0, 100.0)).observe(5).observe(50)
        snap0 = reg.snapshot()
        assert json.loads(json.dumps(snap0)) == snap0  # JSON-able, exactly
        reg.counter("c").inc(2)
        reg.gauge("g").set(9.0)
        reg.histogram("h").observe(500)
        d = obs.Registry.diff(reg.snapshot(), snap0)
        assert d["c"]["value"] == 2.0
        assert d["g"]["value"] == 9.0              # gauges: newer value
        assert d["h"]["count"] == 1
        assert sum(d["h"]["counts"]) == 1

    def test_to_rows_flattens_histograms(self):
        reg = obs.Registry()
        reg.histogram("lat_us").observe(100.0)
        reg.counter("n").inc(4)
        rows = reg.to_rows(prefix="serve_")
        assert rows["serve_n"] == 4.0
        assert rows["serve_lat_us_count"] == 1
        assert rows["serve_lat_us_p50"] == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# export: JSONL round-trip, validation, CLI
# ---------------------------------------------------------------------------


class TestExport:
    def _small_trace(self):
        tr = obs.reset_trace()
        with obs.span("root", n=2):
            with obs.span("child"):
                obs.event("tick", i=0)
        return tr

    def test_jsonl_roundtrip_validates(self, obs_on, tmp_path):
        self._small_trace()
        path = str(tmp_path / "t.jsonl")
        obs.write_trace_jsonl(path)
        assert obs.validate_trace_jsonl(path) == []
        rows = obs.read_trace_jsonl(path)
        assert rows[0] == {"type": "trace",
                           "version": obs.TRACE_SCHEMA_VERSION}
        spans = [r for r in rows if r["type"] == "span"]
        assert [s["path"] for s in spans] == ["root", "root/child"]
        events = [r for r in rows if r["type"] == "event"]
        assert events[0]["name"] == "tick" and events[0]["path"] == "root/child"

    def test_corruption_is_detected(self, obs_on, tmp_path):
        self._small_trace()
        path = str(tmp_path / "t.jsonl")
        obs.write_trace_jsonl(path)
        lines = open(path).read().splitlines()
        lines[1] = lines[1][:-5]  # truncate one row mid-JSON
        open(path, "w").write("\n".join(lines) + "\n")
        errors = obs.validate_trace_jsonl(path)
        assert errors and "not valid JSON" in errors[0]

    def test_missing_header_is_an_error(self):
        errs = obs.validate_rows([{"type": "span", "name": "x", "path": "x",
                                   "depth": 0, "t_start_s": 0.0, "dur_s": 0.0,
                                   "attrs": {}}])
        assert any("first row" in e for e in errs)

    def test_render_smoke(self, obs_on):
        tr = self._small_trace()
        text = obs.render_trace(tr)
        assert "root" in text and "child" in text and "schema v1" in text

    def test_cli_validate_and_render(self, obs_on, tmp_path, capsys):
        from repro.obs.__main__ import main
        self._small_trace()
        path = str(tmp_path / "t.jsonl")
        obs.write_trace_jsonl(path)
        assert main([path, "--validate"]) == 0
        assert "OK" in capsys.readouterr().out
        assert main([path]) == 0
        assert "root" in capsys.readouterr().out
        bad = str(tmp_path / "bad.jsonl")
        open(bad, "w").write('{"type": "span"}\n')
        assert main([bad, "--validate"]) == 1


# ---------------------------------------------------------------------------
# instrumentation: lamc, kernels, recovery, streaming, serving, benchio
# ---------------------------------------------------------------------------


def _stream_cfg(**over):
    base = dict(n_row_clusters=2, n_col_clusters=2, col_blocks=2,
                signature_dim=8, anchor_rows=8, svd_iters=2, kmeans_iters=2,
                merge_kmeans_iters=2, merge_restarts=1, seed=0)
    base.update(over)
    return sfit.StreamConfig(**base)


def _chunks(n_chunks=4, rows=32, cols=64, empty_at=()):
    rng = np.random.default_rng(3)
    out = []
    for i in range(n_chunks):
        if i in empty_at:
            out.append(np.zeros((0, cols), np.float32))
        out.append(rng.standard_normal((rows, cols)).astype(np.float32))
    return out


def _tiny_fit_args():
    import jax.numpy as jnp
    from repro.core.lamc import LAMCConfig
    a = jnp.asarray(np.random.default_rng(0).standard_normal((32, 32)),
                    jnp.float32)
    cfg = LAMCConfig(n_row_clusters=2, n_col_clusters=2, svd_iters=2,
                     kmeans_iters=2, merge_kmeans_iters=2,
                     merge_restarts=1, signature_dim=8)
    return a, cfg


#: the device phases every instruction of the fit program falls under
FIT_PHASES = ("extract", "atom/normalize", "atom/svd", "atom/kmeans",
              "signatures", "merge")


class TestLamcTrace:
    def test_span_tree_and_plan_attrs(self, obs_on):
        from repro.core.lamc import lamc_cocluster
        tr = obs_on
        a, cfg = _tiny_fit_args()
        lamc_cocluster(a, cfg)
        root = tr.find("lamc")[0]
        names = [c.name for c in root.children]
        assert names == ["plan", "dispatch", "wait", "finalize"]
        for key in ("m", "n", "phi", "psi", "t_p", "spmm_route", "extract",
                    "density"):
            assert key in root.attrs, f"missing plan attr {key}"
        assert root.attrs["rows"] == 32
        assert not tr.find("pipeline")
        # the host's phases tile the fit in order, inside the root
        kids = root.children
        assert all(x.t_end <= y.t_start for x, y in zip(kids, kids[1:]))
        assert kids[0].t_start >= root.t_start and kids[-1].t_end <= root.t_end

    def test_spans_are_on_the_profiler_clock(self, obs_on, tmp_path):
        import jax
        from jax.profiler import ProfileData
        from repro.core.lamc import lamc_cocluster
        a, cfg = _tiny_fit_args()
        lamc_cocluster(a, cfg)          # compile outside the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            lamc_cocluster(a, cfg)
        finally:
            jax.profiler.stop_trace()
        found = {}
        for path in tmp_path.rglob("*.xplane.pb"):
            for plane in ProfileData.from_file(str(path)).planes:
                if not plane.name.startswith("/host:"):
                    continue
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("obs."):
                            found.setdefault(e.name, (
                                e.start_ns, e.start_ns + e.duration_ns))
        assert {"obs.lamc", "obs.plan", "obs.dispatch", "obs.wait",
                "obs.finalize"} <= set(found)
        root = found["obs.lamc"]
        for name in ("obs.plan", "obs.dispatch", "obs.wait", "obs.finalize"):
            s, e = found[name]
            assert root[0] <= s <= e <= root[1], name
        assert found["obs.dispatch"][1] <= found["obs.wait"][0]

    def test_device_phases_are_named_in_the_program(self):
        import re

        import jax
        from jax.experimental.compilation_cache import compilation_cache
        from repro.core import partition
        from repro.core.lamc import _lamc_jit
        a, cfg = _tiny_fit_args()
        plan = partition.PartitionPlan(
            n_rows=32, n_cols=32, m=2, n=2, phi=16, psi=16, t_p=2, seed=0,
            detection_p=0.9)
        # the persistent cache's key leaves op_name out: an entry written
        # by an older program would bring that program's names with it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            text = _lamc_jit.lower(a, cfg, plan).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()
        # instructions of the program's own computations carry its name
        # stack; the scalar bodies a reduce or sort applies carry only the
        # primitive's name, and belong to the instruction that applies them
        names = re.findall(r'op_name="(jit\(_lamc_jit\)/[^"]*)"', text)
        assert len(names) > 100
        hits = {p: 0 for p in FIT_PHASES}
        under_one = 0
        for n in names:
            phases = [p for p in FIT_PHASES if f"/{p}/" in n + "/"]
            for p in phases:
                hits[p] += 1
            under_one += len(phases) == 1
        assert all(hits.values()), hits
        assert under_one >= 0.95 * len(names), (under_one, len(names))

    def test_compile_span_on_retrace_not_on_cache_hit(self, obs_on):
        import jax
        import jax.numpy as jnp
        tr = obs_on

        @jax.jit
        def twice(x):
            return 2.0 * x

        def compiles(n):
            with obs.span("call") as sp:
                sp.fence(twice(jnp.ones((n,), jnp.float32)))
            return [c for c in tr.roots[-1].children if c.name == "compile"]

        first = compiles(5)
        assert first, "the first call traces and compiles"
        assert {c.attrs["fun_name"] for c in first} >= {"twice"}
        assert compiles(5) == []            # cache hit: nothing recorded
        again = compiles(7)                 # a new shape retraces
        assert any(c.attrs["event"] == "jaxpr_trace_duration"
                   for c in again)
        # compile spans never overlap, and each lies inside its parent
        parent = tr.roots[-1]
        for x, y in zip(again, again[1:]):
            assert x.t_end <= y.t_start + 1e-4
        assert all(parent.t_start - 1e-4 <= c.t_start <= c.t_end
                   <= parent.t_end for c in again)

    def test_compile_outside_spans_is_an_event(self, obs_on):
        import jax
        import jax.numpy as jnp
        tr = obs_on
        jax.jit(lambda x: x + 3.0)(jnp.ones((3,)))
        assert tr.roots == []
        assert any(e["name"] == "compile" for e in tr.events)

    def test_distributed_span_tree(self, obs_on):
        import jax
        from repro.core import partition
        from repro.core.distributed import distributed_lamc
        tr = obs_on
        a, cfg = _tiny_fit_args()
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        plan = partition.PartitionPlan(
            n_rows=32, n_cols=32, m=1, n=1, phi=32, psi=32, t_p=1, seed=0,
            detection_p=0.9)
        distributed_lamc(mesh, a, cfg, plan)
        root = tr.find("distributed_lamc")[0]
        assert [c.name for c in root.children] == [
            "build_step", "dispatch", "wait", "finalize"]
        assert root.attrs["extract"] == "whole"


class TestKernelDispatch:
    def test_counts_by_op_and_tier(self):
        import jax.numpy as jnp
        from jax.experimental import sparse as jsparse
        from repro.kernels import ops
        obs.reset_metrics()
        dense = np.zeros((16, 16), np.float32)
        dense[0, 0] = 1.0
        a = jsparse.BCOO.fromdense(jnp.asarray(dense))
        ops.spmm(a, jnp.ones((16, 4)))
        ops.spmm(a, jnp.ones((16, 4)))
        series = obs.get_registry().counter("kernel_dispatch").snapshot()["series"]
        assert series["op=spmm,tier=ref"] == 2.0

    def test_spmm_ata_records_vmem_verdict(self, obs_on):
        import jax.numpy as jnp
        from jax.experimental import sparse as jsparse
        from repro.kernels import ops
        tr = obs_on
        obs.reset_metrics()
        rng = np.random.default_rng(11)
        dense = np.where(rng.random((256, 256)) < 0.1,
                         rng.standard_normal((256, 256)), 0.0)
        a = ops.bcoo_to_block_sparse(
            jsparse.BCOO.fromdense(jnp.asarray(dense, jnp.float32)),
            bm=128, bk=128)
        with obs.span("host"):
            ops.spmm_ata(a, jnp.ones((256, 8), jnp.float32))
        evs = [e for e in tr.find("host")[0].events
               if e["name"] == "kernel_dispatch"]
        assert evs and evs[0]["attrs"]["op"] == "spmm_ata"
        assert "fused" in evs[0]["attrs"]
        series = obs.get_registry().counter("kernel_dispatch").snapshot()["series"]
        assert sum(v for k, v in series.items() if "op=spmm_ata" in k) >= 1


class TestRecoveryEvents:
    def _loop(self, d, *, fail_at, total=3, save_every=5):
        inj = FailureInjector(fail_at_steps=tuple(fail_at))

        def step_fn(t, s):
            out = {"v": np.asarray(s["v"] + 1, np.int64)}
            inj.maybe_fail(t)
            return out

        def restore_state(step):
            if step < 0:
                return {"v": np.asarray(0, np.int64)}
            tree, _ = ckpt.restore(d, step, {"v": np.asarray(0, np.int64)})
            return tree

        return run_with_recovery(
            total_steps=total, step_fn=step_fn,
            state={"v": np.asarray(0, np.int64)}, ckpt_dir=d,
            save_every=save_every, restore_state=restore_state)

    def test_stale_checkpoint_event_names_ignored_step(self, obs_on, tmp_path):
        tr = obs_on
        obs.reset_metrics()
        d = str(tmp_path)
        # a previous run left step 50 here; THIS run never saved it
        ckpt.save(d, 50, {"v": np.asarray(99, np.int64)},
                  extra_meta={"step": 50})
        state, stats = self._loop(d, fail_at=(1,))
        assert int(state["v"]) == 3 and stats["failures"] == 1
        stale = [e for e in tr.events
                 if e["name"] == "recovery.stale_checkpoint"]
        assert len(stale) == 1
        assert stale[0]["attrs"]["ignored_step"] == 50
        assert stale[0]["attrs"]["last_saved"] is None
        rest = [e for e in tr.events if e["name"] == "recovery.restore"]
        assert rest[0]["attrs"]["failed_step"] == 1
        assert rest[0]["attrs"]["target"] == -1  # from scratch, not step 50
        reg = obs.get_registry()
        assert reg.counter("recovery_stale_checkpoints").value == 1.0
        assert reg.counter("recovery_restores").value == 1.0

    def test_checkpoint_saved_events(self, obs_on, tmp_path):
        tr = obs_on
        self._loop(str(tmp_path), fail_at=(), total=4, save_every=2)
        saved = [e["attrs"]["step"] for e in tr.events
                 if e["name"] == "recovery.checkpoint_saved"]
        assert saved == [2, 4]


class TestStreamingTrace:
    def test_one_chunk_span_per_nonempty_chunk(self, obs_on):
        tr = obs_on
        chunks = _chunks(n_chunks=3, empty_at=(1,))  # 3 real + 1 empty
        sfit.fit(iter(chunks), _stream_cfg())
        spans = tr.find("chunk")
        assert len(spans) == 3  # the empty chunk left no span
        assert [s.attrs["t"] for s in spans] == [0, 1, 2]
        assert all(s.attrs["replayed"] is False for s in spans)
        assert [c.name for c in spans[0].children] == \
            ["blocks", "atoms", "reservoir"]
        root = tr.find("stream_fit")[0]
        assert root.attrs["chunks"] == 3
        fin = tr.find("finalize")[0]
        assert [c.name for c in fin.children] == ["align", "votes", "columns"]

    def test_resume_marks_skipped_chunks_replayed(self, obs_on, tmp_path):
        cfg = _stream_cfg()
        chunks = _chunks(n_chunks=4)
        d = str(tmp_path)
        fitter = sfit.StreamingCocluster(cfg)
        for c in chunks[:2]:
            fitter.partial_fit(c)
        sfit.save_fit_state(d, fitter)

        # "new process": fresh trace, resume the fit over the same stream
        tr = obs.reset_trace()
        model, stats = sfit.fit(iter(chunks), cfg, ckpt_dir=d, save_every=2,
                                resume_from=d)
        assert stats.chunks == 4
        spans = tr.find("chunk")
        assert len(spans) == 4  # exactly one span per non-empty chunk
        flags = [(s.attrs["replayed"], s.attrs.get("skipped", False))
                 for s in spans]
        assert flags == [(True, True), (True, True),
                         (False, False), (False, False)]
        # and the trace round-trips through the JSONL schema
        path = str(tmp_path / "fit_trace.jsonl")
        obs.write_trace_jsonl(path, tr)
        assert obs.validate_trace_jsonl(path) == []

    def test_injected_failure_refold_marked_replayed(self, obs_on, tmp_path):
        tr = obs_on
        chunks = _chunks(n_chunks=4)
        sfit.fit(iter(chunks), _stream_cfg(), ckpt_dir=str(tmp_path),
                 save_every=2,
                 failure_injector=FailureInjector(fail_at_steps=(2,)))
        spans = tr.find("chunk")
        # chunk 2 folded, failed post-fold, restored to ckpt step 2, refolded
        refolds = [s for s in spans if s.attrs["replayed"]]
        assert len(refolds) == 1 and refolds[0].attrs["t"] == 2
        restores = [e for e in tr.find("stream_fit")[0].events
                    if e["name"] == "recovery.restore"]
        assert len(restores) == 1 and restores[0]["attrs"]["failed_step"] == 2


class TestServeMetrics:
    def _save_model(self, tmp_path):
        from repro import streaming
        rng = np.random.default_rng(5)
        k, q, n = 2, 8, 32
        sigs = rng.standard_normal((k, q)).astype(np.float32)
        sigs /= np.linalg.norm(sigs, axis=1, keepdims=True)
        model = streaming.CoclusterModel(
            row_labels=np.zeros(n, np.int32),
            col_labels=np.zeros(n, np.int32),
            row_votes=np.zeros((n, k), np.float32),
            col_votes=np.zeros((n, k), np.float32),
            row_sigs=sigs, col_sigs=sigs.copy(),
            row_mean=np.zeros(q, np.float32),
            col_mean=np.zeros(q, np.float32),
            anchor_rows=np.arange(q, dtype=np.int32),
            anchor_cols=np.arange(q, dtype=np.int32),
        )
        streaming.save_model(str(tmp_path), model)
        return str(tmp_path)

    def test_histogram_percentiles_and_error_counter(self, tmp_path):
        from repro.launch import serve_lamc
        d = self._save_model(tmp_path)
        reg = obs.Registry()
        out = serve_lamc.serve(d, batch=4, requests=6, warmup=1,
                               adversarial=3, registry=reg)
        h = reg.get("serve_assign_rows_latency_us")
        assert h.count == 6  # adversarial batches are never timed
        assert out["serve_assign_rows_errors"] == 3
        assert out["serve_assign_rows_p50_us"] == pytest.approx(
            h.percentile(50))
        assert out["serve_assign_rows_qps"] > 0
        # bounded memory: bucket vector, not a sample list
        assert len(h.snapshot()["counts"]) == len(h.buckets) + 1

    def test_all_rejected_reports_nan_percentiles(self, tmp_path):
        from repro.launch import serve_lamc
        d = self._save_model(tmp_path)
        out = serve_lamc.serve(d, batch=4, requests=0, warmup=1,
                               adversarial=3)
        assert math.isnan(out["serve_assign_rows_p50_us"])
        assert math.isnan(out["serve_assign_rows_p99_us"])
        assert out["serve_assign_rows_errors"] == 3
        assert out["serve_assign_rows_qps"] == 0.0

    def test_serve_emits_span_trace(self, obs_on, tmp_path):
        from repro.launch import serve_lamc
        tr = obs_on
        d = self._save_model(tmp_path)
        serve_lamc.serve(d, batch=4, requests=2, warmup=1, adversarial=1)
        root = tr.find("serve")[0]
        assert [c.name for c in root.children] == ["warmup", "request_loop"]
        assert root.attrs["served"] == 2 and root.attrs["errors"] == 1
        rejected = [e for e in tr.find("request_loop")[0].events
                    if e["name"] == "request_rejected"]
        assert len(rejected) == 1


class TestBenchMeta:
    def test_merge_rows_writes_provenance_sidecar(self, tmp_path):
        path = str(tmp_path / "BENCH_x.json")
        benchio.merge_rows(path, {"x_a": 1.0}, own_prefixes=("x_",))
        meta = json.load(open(str(tmp_path / benchio.META_BASENAME)))
        entry = meta["BENCH_x.json"]
        for key in ("git_sha", "jax_version", "backend", "device_kind",
                    "timestamp"):
            assert key in entry, f"missing provenance field {key}"
        assert entry["rows"] == 1
        assert entry["git_sha"] != ""  # repo checkout: a real sha
        # a second trajectory file merges into the same sidecar
        benchio.merge_rows(str(tmp_path / "BENCH_y.json"), {"y_b": 2.0})
        meta = json.load(open(str(tmp_path / benchio.META_BASENAME)))
        assert set(meta) == {"BENCH_x.json", "BENCH_y.json"}

    def test_provenance_never_raises(self):
        info = benchio.provenance()
        assert set(info) >= {"git_sha", "jax_version", "backend",
                             "device_kind", "timestamp"}
