"""Assignment service + model registry (DESIGN.md §15).

Pins the serving-layer contracts:
  * coalesced service responses are bit-equal to the direct jitted
    assign step, for every request size the coalescer can see (1-row,
    odd, full-batch, zero-row) and for top-k and column traffic;
  * admission rejects carry machine-readable reason codes and never
    raise into the caller (bad shape/dtype/payload, bad k, oversize,
    queue_full load shedding, post-close shutdown);
  * hot swap is atomic: under continuous multi-thread traffic every
    response is attributable to exactly one model version and its
    labels match that version's model exactly — no torn batches, no
    dropped or errored requests (the zero-drop guarantee);
  * the registry's publish/load round-trip, monotonic version ids, and
    crash-consistency (a claimed-but-uncommitted version is invisible);
  * the serving sharding policy (``serve_model_specs``) shards exactly
    the 2-D tables whose leading dim divides the mesh, and the sharded
    service returns the same labels as the single-device one (slow,
    8-device subprocess).
"""

import os
import subprocess
import sys
import textwrap
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, streaming
from repro.data import planted_cocluster_matrix


@pytest.fixture(scope="module")
def fitted():
    rng = np.random.default_rng(0)
    data = planted_cocluster_matrix(rng, 256, 128, k=4, d=4,
                                    signal=4.0, noise=0.6)
    cfg = streaming.StreamConfig(n_row_clusters=4, n_col_clusters=4, seed=0)
    model, _ = streaming.fit(streaming.iter_row_chunks(data.matrix, 128), cfg)
    return model, cfg


def _service(model, **over):
    kw = dict(batch=16, replicas=2)
    kw.update(over)
    return streaming.AssignService(
        model, version="v1", config=streaming.ServeConfig(**kw),
        metrics=obs.Registry())


class TestServiceParity:
    def test_coalesced_matches_direct(self, fitted):
        model, _ = fitted
        rng = np.random.default_rng(1)
        sizes = [1, 3, 16, 0, 7, 5]
        reqs = [rng.normal(size=(s, model.n_cols)).astype(np.float32)
                for s in sizes]
        direct = [streaming.assign_rows(model, jnp.asarray(x)) for x in reqs]
        with _service(model) as svc:
            tickets = [svc.submit(x) for x in reqs]
            for x, t, want in zip(reqs, tickets, direct):
                res = t.result(timeout=60.0)
                assert res.ok, (res.reason, res.detail)
                assert res.version == "v1"
                assert res.labels.shape == (x.shape[0],)
                np.testing.assert_array_equal(res.labels,
                                              np.asarray(want.labels))
                np.testing.assert_allclose(res.scores,
                                           np.asarray(want.score),
                                           rtol=1e-5, atol=1e-6)

    def test_topk_and_cols_traffic(self, fitted):
        model, _ = fitted
        rng = np.random.default_rng(2)
        xr = rng.normal(size=(6, model.n_cols)).astype(np.float32)
        xc = rng.normal(size=(6, model.n_rows)).astype(np.float32)
        want_k = streaming.assign_rows_topk(model, jnp.asarray(xr), k=3)
        want_c = streaming.assign_cols(model, jnp.asarray(xc))
        with _service(model) as svc:
            rk = svc.submit(xr, axis="rows", k=3).result(timeout=60.0)
            rc = svc.submit(xc, axis="cols").result(timeout=60.0)
        assert rk.ok and rk.labels.shape == (6, 3)
        np.testing.assert_array_equal(rk.labels, np.asarray(want_k.labels))
        assert rc.ok
        np.testing.assert_array_equal(rc.labels, np.asarray(want_c.labels))

    def test_zero_row_submit_completes_immediately(self, fitted):
        model, _ = fitted
        with _service(model) as svc:
            res = svc.submit(
                np.zeros((0, model.n_cols), np.float32)).result(timeout=5.0)
            assert res.ok and res.labels.shape == (0,)
            res_k = svc.submit(np.zeros((0, model.n_cols), np.float32),
                               k=2).result(timeout=5.0)
            assert res_k.ok and res_k.labels.shape == (0, 2)


class TestAdmission:
    def test_malformed_requests_reject_with_codes(self, fitted):
        model, _ = fitted
        dim = model.n_cols
        bad = np.zeros((2, dim), np.float32)
        bad[0, 0] = np.inf
        cases = [
            (np.zeros((dim,), np.float32), {}, "bad_rank"),
            (np.zeros((2, dim + 1), np.float32), {}, "bad_width"),
            (np.zeros((2, dim), np.int32), {}, "bad_dtype"),
            (bad, {}, "non_finite"),
            (np.zeros((2, dim), np.float32), {"k": 0}, "bad_k"),
            (np.zeros((2, dim), np.float32), {"k": 99}, "bad_k"),
            (np.zeros((17, dim), np.float32), {}, "oversize"),
        ]
        with _service(model, batch=16) as svc:
            for x, kw, code in cases:
                res = svc.submit(x, **kw).result(timeout=5.0)
                assert not res.ok and res.reason == code, (res.reason, code)
                assert res.version is None and res.labels is None
            with pytest.raises(ValueError, match="axis"):
                svc.submit(np.zeros((2, dim), np.float32), axis="diag")

    def test_queue_full_sheds_load(self, fitted):
        model, _ = fitted
        gate = threading.Event()
        with _service(model, batch=4, replicas=1, max_queue_rows=8) as svc:
            orig = svc._score_batch

            def stalled(key, reqs):
                gate.wait(30.0)
                orig(key, reqs)

            svc._score_batch = stalled
            x4 = np.zeros((4, model.n_cols), np.float32)
            first = svc.submit(x4)           # taken by the (stalled) worker
            deadline = time.time() + 10.0
            while svc.stats()["queued_rows"] and time.time() < deadline:
                time.sleep(0.005)
            held = [svc.submit(x4), svc.submit(x4)]   # fills the 8-row budget
            shed = svc.submit(x4).result(timeout=5.0)
            assert not shed.ok and shed.reason == "queue_full"
            gate.set()
            for t in [first] + held:
                assert t.result(timeout=60.0).ok

    def test_internal_error_rejects_batch_not_worker(self, fitted):
        model, _ = fitted
        with _service(model, replicas=1) as svc:

            def boom(x):
                raise RuntimeError("injected scorer failure")

            with svc._engine._lock:
                svc._engine._scorers[("rows", 1)] = boom
            x = np.zeros((2, model.n_cols), np.float32)
            res = svc.submit(x).result(timeout=30.0)
            assert not res.ok and res.reason == "internal_error"
            assert "injected" in res.detail
            # the worker survived: fix the scorer, traffic flows again
            with svc._engine._lock:
                del svc._engine._scorers[("rows", 1)]
            res2 = svc.submit(x).result(timeout=60.0)
            assert res2.ok

    @pytest.mark.parametrize("driver", ["serve_lamc", "bench_serve"])
    def test_drivers_fail_on_internal_error(self, fitted, tmp_path,
                                            monkeypatch, driver):
        """A scorer that raises must fail the serving drivers, not let
        them exit 0 over a service that scored nothing."""
        from repro.streaming import serve as serve_mod

        def boom(x):
            raise RuntimeError("injected scorer failure")

        monkeypatch.setattr(serve_mod._Engine, "warm",
                            lambda self, axis, k, batch: None)
        monkeypatch.setattr(serve_mod._Engine, "scorer",
                            lambda self, axis, k: boom)
        model, _ = fitted
        if driver == "serve_lamc":
            from repro.launch import serve_lamc

            ckpt = str(tmp_path / "model")
            streaming.save_model(ckpt, model)
            with pytest.raises(RuntimeError, match="internal_error"):
                serve_lamc.serve_service(ckpt, batch=16, requests=4,
                                         warmup=1, replicas=1)
        else:
            from benchmarks import bench_serve

            x = np.zeros((4, model.n_cols), np.float32)
            d = bench_serve._drive(model, [x, x, x], axis="rows", k=1,
                                   replicas=1, batch=16)
            assert d["errors"] == 4      # the warm-up and all three

    def test_shutdown_rejects_after_close(self, fitted):
        model, _ = fitted
        svc = _service(model)
        x = np.zeros((2, model.n_cols), np.float32)
        assert svc.submit(x).result(timeout=60.0).ok
        svc.close()
        res = svc.submit(x).result(timeout=5.0)
        assert not res.ok and res.reason == "shutdown"

    def test_rejects_are_counted_per_reason(self, fitted):
        model, _ = fitted
        reg = obs.Registry()
        svc = streaming.AssignService(
            model, version="v1",
            config=streaming.ServeConfig(batch=8, replicas=1), metrics=reg)
        svc.submit(np.zeros((3,), np.float32)).result(timeout=5.0)
        svc.submit(np.zeros((9, model.n_cols), np.float32)).result(timeout=5.0)
        svc.close()
        rejected = svc.stats()["rejected"]
        assert rejected["reason=bad_rank"] == 1
        assert rejected["reason=oversize"] == 1


class TestHotSwap:
    """Swap atomicity: the successor model's signature table is a
    cyclic roll of the original's, so labels map deterministically —
    every response must match exactly one version's mapping."""

    def test_every_response_attributable_to_one_version(self, fitted):
        model, _ = fitted
        k = model.n_row_clusters
        model2 = model._replace(
            row_sigs=jnp.roll(model.row_sigs, 1, axis=0))
        rng = np.random.default_rng(3)
        x = rng.normal(size=(4, model.n_cols)).astype(np.float32)
        want_v1 = np.asarray(streaming.assign_rows(model, jnp.asarray(x)).labels)
        want_v2 = (want_v1 + 1) % k   # rolled sigs shift every argmax by 1
        np.testing.assert_array_equal(
            np.asarray(streaming.assign_rows(model2, jnp.asarray(x)).labels),
            want_v2)

        results: list = []
        lock = threading.Lock()
        stop = threading.Event()
        with _service(model, batch=8, replicas=2) as svc:

            def pump():
                while not stop.is_set():
                    res = svc.submit(x).result(timeout=60.0)
                    with lock:
                        results.append(res)

            threads = [threading.Thread(target=pump) for _ in range(3)]
            for t in threads:
                t.start()
            while len(results) < 20:
                time.sleep(0.002)
            displaced = svc.swap(model2, "v2")
            with lock:
                at_swap = len(results)
            while len(results) < at_swap + 20:
                time.sleep(0.002)
            stop.set()
            for t in threads:
                t.join(timeout=60.0)
        assert displaced == "v1"
        versions = {r.version for r in results}
        assert versions == {"v1", "v2"}, versions
        for res in results:
            assert res.ok, (res.reason, res.detail)
            want = want_v1 if res.version == "v1" else want_v2
            np.testing.assert_array_equal(res.labels, want)

    def test_swap_prewarms_previously_compiled_shapes(self, fitted):
        model, _ = fitted
        with _service(model, replicas=1) as svc:
            svc.submit(np.zeros((2, model.n_cols), np.float32),
                       k=2).result(timeout=60.0)
            warmed_before = set(svc._engine.warmed_keys())
            assert ("rows", 2) in warmed_before
            svc.swap(model, "v2")
            assert set(svc._engine.warmed_keys()) >= warmed_before
            assert svc.version == "v2"

    def test_swap_async_resolves_and_serves(self, fitted):
        model, _ = fitted
        with _service(model, replicas=1) as svc:
            done = svc.swap_async(lambda: model, "v2")
            res = done.result(timeout=60.0)
            assert res.ok and res.version == "v2"
            out = svc.submit(
                np.zeros((2, model.n_cols), np.float32)).result(timeout=60.0)
            assert out.version == "v2"
            fail = svc.swap_async(
                lambda: (_ for _ in ()).throw(RuntimeError("fit died")),
                "v3")
            bad = fail.result(timeout=60.0)
            assert not bad.ok and bad.reason == "internal_error"
            assert svc.version == "v2"   # failed swap changes nothing


class TestRegistry:
    def test_publish_load_roundtrip_with_provenance(self, fitted, tmp_path):
        model, cfg = fitted
        reg = streaming.ModelRegistry(str(tmp_path))
        ent = reg.publish("planted", model, cfg=cfg,
                          metrics={"row_nmi": 0.97},
                          data_fingerprint="stream:demo")
        assert ent.version == "v_000001"
        assert ent.config_hash == streaming.config_hash(cfg)
        back, ent2 = reg.load("planted")
        assert ent2 == ent
        assert ent2.metrics == {"row_nmi": 0.97}
        assert ent2.data_fingerprint == "stream:demo"
        np.testing.assert_array_equal(np.asarray(back.row_sigs),
                                      np.asarray(model.row_sigs))

    def test_versions_are_monotonic_and_immutable(self, fitted, tmp_path):
        model, cfg = fitted
        reg = streaming.ModelRegistry(str(tmp_path))
        reg.publish("m", model, cfg=cfg)
        reg.publish("m", model, cfg=cfg)
        assert reg.versions("m") == ["v_000001", "v_000002"]
        assert reg.latest("m") == "v_000002"
        assert reg.names() == ["m"]

    def test_crashed_publish_is_invisible_and_skipped(self, fitted, tmp_path):
        model, cfg = fitted
        reg = streaming.ModelRegistry(str(tmp_path))
        reg.publish("m", model, cfg=cfg)
        # a claim that never committed (publisher crashed after mkdir)
        os.mkdir(tmp_path / "m" / "v_000099")
        assert reg.versions("m") == ["v_000001"]
        with pytest.raises(streaming.ModelLoadError, match="no committed"):
            reg.entry("m", "v_000099")
        # the next publish allocates past the dead claim, never into it
        ent = reg.publish("m", model, cfg=cfg)
        assert ent.version == "v_000100"

    def test_bad_name_is_loud(self, tmp_path):
        reg = streaming.ModelRegistry(str(tmp_path))
        with pytest.raises(ValueError, match="bad model name"):
            reg.versions("../escape")

    def test_fingerprint_tracks_content(self, fitted):
        model, cfg = fitted
        fp = streaming.model_fingerprint(model)
        assert fp == streaming.model_fingerprint(model)
        bumped = model._replace(
            row_votes=model.row_votes.at[0, 0].add(1.0))
        assert streaming.model_fingerprint(bumped) != fp
        assert streaming.config_hash({"b": 1, "a": 2}) == \
            streaming.config_hash({"a": 2, "b": 1})
        assert streaming.config_hash(cfg) != streaming.config_hash(None)

    def test_registry_feeds_swap_async(self, fitted, tmp_path):
        # the intended deploy loop: background fit -> publish -> swap
        model, cfg = fitted
        reg = streaming.ModelRegistry(str(tmp_path))
        ent = reg.publish("live", model, cfg=cfg)
        with _service(model, replicas=1) as svc:
            done = svc.swap_async(lambda: reg.load("live")[0], ent.version)
            assert done.result(timeout=120.0).ok
            assert svc.version == "v_000001"


class TestShardingPolicy:
    def test_specs_shard_divisible_leading_dims_only(self, fitted):
        import jax
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        from repro.runtime import shardings

        model, _ = fitted
        # a 1-device mesh exercises the policy shape (size-1 divides all)
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
        specs = shardings.serve_model_specs(model, mesh)
        assert specs.row_sigs == P("data", None)       # (K, q) 2-D table
        assert specs.row_votes == P("data", None)      # (M, K)
        assert specs.anchor_rows == P(None)            # 1-D replicates
        assert specs.row_mean == P(None)

    def test_indivisible_dims_relax_to_replication(self):
        from jax.sharding import PartitionSpec as P

        from repro.runtime import shardings

        class FakeMesh:
            # stand-in exposing only .shape, to test the divisibility
            # rule against a mesh size no single-device host can build
            shape = {"data": 8}

        tree = {"sigs": np.zeros((24, 7)), "odd": np.zeros((9, 4)),
                "vec": np.zeros((24,))}
        specs = shardings.serve_model_specs(tree, FakeMesh())
        assert specs["sigs"] == P("data", None)   # 24 % 8 == 0
        assert specs["odd"] == P(None, None)      # 9 % 8 != 0 -> replicate
        assert specs["vec"] == P(None)


@pytest.mark.slow
def test_sharded_service_matches_single_device():
    """8-device host mesh (subprocess): the cluster-sharded service
    returns byte-identical labels to an unsharded one."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import numpy as np
        from repro import obs, streaming
        from repro.data import planted_cocluster_matrix

        assert len(jax.devices()) == 8, jax.devices()
        rng = np.random.default_rng(0)
        data = planted_cocluster_matrix(rng, 256, 128, k=4, d=4,
                                        signal=4.0, noise=0.6)
        cfg = streaming.StreamConfig(n_row_clusters=4, n_col_clusters=4,
                                     seed=0)
        model, _ = streaming.fit(
            streaming.iter_row_chunks(data.matrix, 128), cfg)
        x = rng.normal(size=(32, model.n_cols)).astype(np.float32)

        def run(shard):
            svc = streaming.AssignService(
                model, version="v1",
                config=streaming.ServeConfig(batch=16, replicas=2,
                                             shard=shard),
                metrics=obs.Registry())
            with svc:
                if shard:
                    assert svc._engine.mesh is not None
                res = svc.submit(x[:16]).result(timeout=120.0)
                res2 = svc.submit(x[16:], k=2).result(timeout=120.0)
            assert res.ok and res2.ok
            return res.labels, res2.labels

        a1, a2 = run(shard=True)
        b1, b2 = run(shard=False)
        np.testing.assert_array_equal(a1, b1)
        np.testing.assert_array_equal(a2, b2)
        print("SHARDED_PARITY_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED_PARITY_OK" in proc.stdout
