"""Set-up, measured window and answer check of the two kinds of cell.

A fit cell drives ``repro.core.lamc_cocluster`` (or, on a mesh,
``repro.core.distributed.distributed_lamc``) back to back on a planted
matrix; a serve cell drives ``repro.streaming.AssignService.submit`` in
an open loop. Each keeps the answers its window produced, and
:meth:`check` compares them with ``bench/reference.py`` once the window
has closed.
"""

from __future__ import annotations

import hashlib
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import counts
import data as bench_data
import generator
import reference

FIT_NUMBERS = ("anchors_bad", "nmi_loss", "atom_nmi_loss", "recovery_gap",
               "sig_gap", "mean_gap")


def _hash(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _nmi_min(rows, cols, other: tuple) -> float:
    return min(reference.nmi(rows, other[0]), reference.nmi(cols, other[1]))


def fit_numbers(ans: dict, slivers, truth, q: int, atom: dict) -> dict:
    """Every number of one fit's answer (see ``reference``); a
    configuration's ``limits`` name the ones compared.

    ``atom`` is :func:`reference.scc` of the cell's data: the answer's
    labels are read against its labels (``atom_nmi_loss``) and against its
    recovery of the planted truth (``recovery_gap``), besides the truth
    itself (``nmi_loss``).
    """
    rf, cf = slivers
    out = {"anchors_bad": 0.0}
    for ids, n in ((ans["anchor_cols"], truth[1].size),
                   (ans["anchor_rows"], truth[0].size)):
        ok = (ids.size == min(q, n) and np.unique(ids).size == ids.size
              and ids.min() >= 0 and ids.max() < n)
        out["anchors_bad"] += 0.0 if ok else 1.0
    rows, cols = ans["row_labels"], ans["col_labels"]
    recovered = _nmi_min(rows, cols, truth)
    out["nmi_loss"] = 1.0 - recovered
    out["atom_nmi_loss"] = 1.0 - _nmi_min(
        rows, cols, (atom["row_labels"], atom["col_labels"]))
    out["recovery_gap"] = _nmi_min(atom["row_labels"], atom["col_labels"],
                                   truth) - recovered
    sig_gap, mean_gap = 0.0, 0.0
    for feats, lab, sig, mean in ((rf, rows, ans["row_sigs"], ans["row_mean"]),
                                  (cf, cols, ans["col_sigs"], ans["col_mean"])):
        if lab.min() < 0 or lab.max() >= sig.shape[0]:
            return dict(out, sig_gap=math.inf, mean_gap=math.inf)
        ref_sig, ref_mean = reference.signatures(feats, lab, sig.shape[0])
        sig_gap = max(sig_gap, float(np.max(np.abs(sig - ref_sig))))
        mean_gap = max(mean_gap, float(np.max(np.abs(mean - ref_mean))
                                       / np.max(np.abs(ref_mean))))
    out.update(sig_gap=sig_gap, mean_gap=mean_gap)
    return out


def substitute_fit(ans: dict, slivers, atom: dict, *, control: bool) -> dict:
    """An answer with the reference in the program's place: the labels of
    ``atom`` (a :func:`reference.scc`), the answer's anchor ids, and the
    signatures and means of those labels, in bfloat16 for the control and
    in float64 otherwise."""
    rf, cf = slivers
    sigs = reference.signatures_control if control else reference.signatures
    out = dict(ans, row_labels=atom["row_labels"], col_labels=atom["col_labels"])
    out["row_sigs"], out["row_mean"] = sigs(rf, atom["row_labels"],
                                            ans["row_sigs"].shape[0])
    out["col_sigs"], out["col_mean"] = sigs(cf, atom["col_labels"],
                                            ans["col_sigs"].shape[0])
    return out


class FitCell:
    kind = "fit"

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        from repro.core import LAMCConfig

        self.config, self.traffic, self.seed = config, traffic, seed
        self.chips = chips
        self.cfg = LAMCConfig(**config["lamc"])
        self.q = self.cfg.signature_dim
        self.mesh = None
        self.phases = []     # per fit: (seconds to return, seconds waiting)

    # -- set-up ----------------------------------------------------------
    def setup(self) -> None:
        sharding = None
        if self.config.get("mesh"):
            from jax.sharding import AxisType, NamedSharding
            from jax.sharding import PartitionSpec as P

            shape, axes = self.config["mesh"]["shape"], self.config["mesh"]["axes"]
            self.mesh = jax.make_mesh(tuple(shape), tuple(axes),
                                      devices=jax.devices()[:self.chips],
                                      axis_types=(AxisType.Auto,) * len(axes))
            sharding = NamedSharding(self.mesh, P((axes[0],), axes[1]))
        self.planted = bench_data.make(self.config, self.seed, sharding)
        self.fit_once()                      # compiles every program it runs

    def before_fit(self):
        if self.traffic.get("fresh_operator"):
            from repro.core import opcache
            opcache.default_cache().clear()

    def fit_once(self) -> dict:
        from repro.core import lamc_cocluster
        from repro.core.distributed import distributed_lamc
        from repro.core.partition import make_plan

        a = self.planted.a
        t0 = time.perf_counter()
        if self.mesh is None:
            res = lamc_cocluster(a, self.cfg)
        else:
            c = self.cfg
            plan = make_plan(a.shape[0], a.shape[1],
                             min_cocluster_rows=c.min_cocluster_rows,
                             min_cocluster_cols=c.min_cocluster_cols,
                             p_thresh=c.p_thresh, workers=self.chips,
                             seed=c.seed, k=c.atom_k,
                             grid_candidates=c.grid_candidates,
                             svd_method=c.svd_method)
            res = distributed_lamc(self.mesh, a, c, plan)
        t1 = time.perf_counter()
        rows, cols = jax.device_get((res.row_labels, res.col_labels))
        rest = jax.block_until_ready(
            {f: getattr(res, f) for f in ("row_sigs", "col_sigs", "row_mean",
                                          "col_mean", "anchor_rows",
                                          "anchor_cols")})
        self.plan = res.plan
        self.phases.append((t1 - t0, time.perf_counter() - t1))
        return dict(rest, row_labels=rows, col_labels=cols)

    # -- window ----------------------------------------------------------
    def window(self, seconds: float, trace=None) -> dict:
        self.stats = generator.fit_loop(self.fit_once, seconds,
                                        before_fit=self.before_fit,
                                        trace=trace)
        return self.stats

    def end_to_end(self) -> dict:
        return {"fit_s": self.stats["elapsed_s"] / self.stats["fits"]}

    def attempted(self) -> int:
        return self.stats["fits"]

    def work(self) -> tuple[float, float]:
        """``(flops, bytes)`` per device of one fit (``counts.lamc_fit``)."""
        p, c = self.plan, self.cfg
        n_dev = max(self.chips if self.mesh is not None else 1, 1)
        return counts.lamc_fit(phi=p.phi, psi=p.psi, k=c.atom_k, d=c.atom_d,
                               svd_iters=c.svd_iters, t_p=p.t_p,
                               blocks_per_device=max(
                                   p.blocks_per_resample // n_dev, 1),
                               nnz=self.planted.nnz)

    def release(self) -> None:
        """Drop the program's state; the planted matrix stays (it is data)."""
        for ans in self.stats["answers"]:
            for f in ("row_sigs", "col_sigs", "row_mean", "col_mean",
                      "anchor_rows", "anchor_cols"):
                ans[f] = np.asarray(ans[f])

    # -- check -----------------------------------------------------------
    def distinct_answers(self) -> list[tuple[dict, int]]:
        seen: dict[str, list] = {}
        for ans in self.stats["answers"]:
            key = _hash(*(ans[f] for f in sorted(ans)))
            seen.setdefault(key, [ans, 0])[1] += 1
        return [tuple(v) for v in seen.values()]

    def slivers(self, ans: dict):
        return reference.anchor_slivers(self.planted.a, ans["anchor_rows"],
                                        ans["anchor_cols"])

    def atom_reference(self, **fault) -> dict:
        """:func:`reference.scc` of the cell's data, its own seed drawn from
        ``--seed``; ``fault`` passes the control's and the faults' knobs."""
        return reference.scc(self.planted.a, self.config["k"],
                             self.config["d"], [self.seed, 3], **fault)

    def check(self, limits: dict) -> tuple[dict, int]:
        """Worst of each number that has a limit over the window's distinct
        answers, and how many fits gave an answer outside a limit."""
        truth = (self.planted.row_labels, self.planted.col_labels)
        compared = [n for n in FIT_NUMBERS if n in limits]
        worst = {n: -math.inf for n in compared}
        failed = 0
        atom = self.atom_reference()
        for ans, n in self.distinct_answers():
            nums = fit_numbers(ans, self.slivers(ans), truth, self.q, atom)
            for k in compared:
                worst[k] = max(worst[k], nums[k])
            if any(not nums[k] <= limits[k] for k in compared):
                failed += n
        return worst, failed


class ServeCell:
    kind = "serve"

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.chips = chips

    def setup(self) -> None:
        """Plant the matrix, build the served model from the planted truth
        (so the reference takes nothing the program made), copy the
        payload pools to the host, then start and warm the service."""
        from repro import streaming

        spec = self.config
        planted = bench_data.make(self.config, self.seed)
        a = planted.a
        m, n = a.shape
        q = self.config["lamc"].get("signature_dim", 64)
        rng = np.random.default_rng([self.seed, 1])
        anchor_rows = np.sort(rng.choice(m, min(q, m), replace=False))
        anchor_cols = np.sort(rng.choice(n, min(q, n), replace=False))
        rf, cf = reference.anchor_slivers(a, anchor_rows, anchor_cols)
        row_sigs, row_mean = reference.signatures(rf, planted.row_labels,
                                                  spec["k"])
        col_sigs, col_mean = reference.signatures(cf, planted.col_labels,
                                                  spec["d"])
        pool = self.traffic["pool"]
        row_ids = rng.choice(m, pool["rows"], replace=False)
        col_ids = rng.choice(n, pool["cols"], replace=False)
        self.pools = {
            "rows": np.asarray(a[jnp.asarray(row_ids)]),
            "cols": np.ascontiguousarray(
                np.asarray(a[:, jnp.asarray(col_ids)]).T)}
        self.anchored = {"rows": self.pools["rows"][:, anchor_cols],
                         "cols": self.pools["cols"][:, anchor_rows]}
        rows_truth, cols_truth = planted.row_labels, planted.col_labels
        del a, planted
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        # the reference scores against exactly the float32 model served
        as_served = lambda x: np.float64(np.float32(x))
        self.model_np = {"rows": (as_served(row_mean), as_served(row_sigs)),
                         "cols": (as_served(col_mean), as_served(col_sigs))}
        self.model = streaming.CoclusterModel(
            row_labels=jnp.asarray(rows_truth),
            col_labels=jnp.asarray(cols_truth),
            row_votes=f32(np.eye(spec["k"])[rows_truth]),
            col_votes=f32(np.eye(spec["d"])[cols_truth]),
            row_sigs=f32(row_sigs), col_sigs=f32(col_sigs),
            row_mean=f32(row_mean), col_mean=f32(col_mean),
            anchor_rows=jnp.asarray(anchor_rows, jnp.int32),
            anchor_cols=jnp.asarray(anchor_cols, jnp.int32))
        self.start_service()

    def start_service(self) -> None:
        """A fresh ``AssignService`` over the model, every scorer the mix
        uses compiled and run once."""
        from repro import obs, streaming

        self.registry = obs.Registry()
        self.service = streaming.AssignService(
            self.model, config=streaming.ServeConfig(**self.config["serve"]),
            metrics=self.registry)
        batch = self.service.config.batch
        for axis in self.traffic["axis"]:
            for k in self.traffic["k"]:
                t = self.service.submit(self.pools[axis][:batch], axis=axis,
                                        k=int(k))
                res = t.result(600.0)
                if not res.ok:
                    raise RuntimeError(f"warm-up {axis} k={k}: {res.reason}: "
                                       f"{res.detail}")

    def payload(self, req: dict) -> np.ndarray:
        return self.pools[req["axis"]][req["offset"]:req["offset"] + req["rows"]]

    def window(self, seconds: float, trace=None) -> dict:
        self.requests = generator.schedule(self.traffic, seconds, self.seed)
        before = self.registry.snapshot()
        submit = lambda x, axis, k: self.service.submit(x, axis=axis, k=k)
        self.stats = generator.open_loop(submit, self.requests, self.payload,
                                         wait_s=60.0, trace=trace)
        self.registry_diff = self.registry.diff(self.registry.snapshot(), before)
        return self.stats

    def latencies_ms(self) -> np.ndarray:
        s, cap = self.stats, self.stats["deadline"]
        out = []
        for req, done, res in zip(self.requests, s["done_at"], s["results"]):
            due = s["t0"] + req["due"]
            ok = res is not None and res.ok and not math.isnan(done)
            out.append(((done if ok else cap) - due) * 1e3)
        return np.asarray(out)

    def end_to_end(self) -> dict:
        lat = self.latencies_ms()
        s = self.stats
        ok = [i for i, r in enumerate(s["results"]) if r is not None and r.ok]
        rows = sum(self.requests[i]["rows"] for i in ok)
        span = (max(s["done_at"][i] for i in ok) - min(s["sent_at"])
                if ok else math.inf)
        return {"serve_p50_ms": float(np.percentile(lat, 50)),
                "serve_p99_ms": float(np.percentile(lat, 99)),
                "serve_rows_per_s": rows / span if ok else 0.0}

    def attempted(self) -> int:
        return len(self.requests)

    def sender_late_ms(self) -> np.ndarray:
        s = self.stats
        return np.asarray([(t - s["t0"] - r["due"]) * 1e3
                           for t, r in zip(s["sent_at"], self.requests)])

    def release(self) -> None:
        self.service.close()

    def check(self, limits: dict, control: bool = False) -> tuple[dict, int]:
        """``score_gap``: over every answered row and each of its ``k``
        answers, the larger of how far its score lies from the reference's
        score of the cluster it names, and how far that cluster's reference
        score lies below the reference's ``j``-th best."""
        worst, failed = 0.0, 0
        for req, res in zip(self.requests, self.stats["results"]):
            if res is None:
                failed += 1
                worst = math.inf
                continue
            if not res.ok:
                failed += 1
                continue
            axis, k = req["axis"], req["k"]
            feats = self.anchored[axis][req["offset"]:req["offset"] + req["rows"]]
            mean, sigs = self.model_np[axis]
            ref = reference.scores(feats, mean, sigs)
            if control:
                got_s = reference.scores_control(feats, mean, sigs)
                lab = np.argsort(-got_s, axis=1, kind="stable")[:, :k]
                score = np.take_along_axis(got_s, lab, 1)
            else:
                lab = np.asarray(res.labels).reshape(req["rows"], k)
                score = np.asarray(res.scores, np.float64).reshape(req["rows"], k)
            if lab.min() < 0 or lab.max() >= sigs.shape[0]:
                gap = math.inf
            else:
                named = np.take_along_axis(ref, lab, 1)
                best = -np.sort(-ref, axis=1)[:, :k]
                gap = float(max(np.max(np.abs(score - named)),
                                np.max(best - named)))
            worst = max(worst, gap)
            if not gap <= limits.get("score_gap", math.inf):
                failed += 1
        return ({"score_gap": worst} if "score_gap" in limits else {}), failed


CELLS = {"fit": FitCell, "open": ServeCell}


def make(config: dict, traffic: dict, seed: int, chips: int):
    return CELLS[traffic["loop"]](config, traffic, seed, chips)


