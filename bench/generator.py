"""The one traffic generator: a closed loop of fits or an open loop of requests.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters:

- ``"loop": "fit"`` — one caller runs fits back to back; the last fit
  started inside the window is completed and counted.
  ``"fresh_operator": true`` empties the program's sparse-operator cache
  (``core.opcache``) before each fit, so every fit pays the conversion a
  new matrix would.
- ``"loop": "open"`` — requests arrive on a schedule fixed in advance,
  whatever the service does: ``rate_per_s`` requests a second for the
  window, rows per request from ``P(r) ~ r^-power`` on
  ``[min, max]``, and the shares of each ``axis`` and top-``k`` width.
  Every seed gets the same multiset of gaps, sizes, axes and widths (the
  quantiles of each distribution), in an order of its own; so seeds
  change which request comes when, not how much work the window holds.
"""

from __future__ import annotations

import math
import queue
import threading
import time

import numpy as np


# -- closed loop of fits ---------------------------------------------------

def fit_loop(fit_once, seconds: float, *, before_fit=None,
             trace: dict | None = None) -> dict:
    """Run ``fit_once()`` back to back for ``seconds``.

    ``trace``, if given, is ``{"start": callable, "stop": callable,
    "from_s": float, "for_s": float}``: the trace starts at the first fit
    boundary past ``from_s`` and stops at the first past
    ``from_s + for_s``, so it holds whole fits only.
    """
    answers, fit_s = [], []
    traced_fits, tracing, traced = 0, False, False
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if trace is not None and not traced:
            if not tracing and now >= trace["from_s"]:
                trace["start"]()
                tracing, t_trace = True, now
            elif tracing and now - t_trace >= trace["for_s"]:
                trace["stop"]()
                tracing, traced = False, True
        if now >= seconds and not tracing:
            break
        if before_fit is not None:
            before_fit()
        t1 = time.perf_counter()
        answers.append(fit_once())
        fit_s.append(time.perf_counter() - t1)
        traced_fits += tracing
    return {"elapsed_s": time.perf_counter() - t0, "fits": len(answers),
            "answers": answers, "each_fit_s": fit_s,
            "traced_fits": traced_fits}


# -- open loop of requests -------------------------------------------------

def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _shares(n: int, shares: dict, rng) -> list:
    """``n`` draws with exactly the given shares (rounded), shuffled."""
    keys = list(shares)
    counts = [int(round(n * shares[k])) for k in keys[:-1]]
    counts.append(n - sum(counts))
    out = np.concatenate([np.full(c, i) for i, c in enumerate(counts)])
    rng.shuffle(out)
    return [keys[i] for i in out]


def schedule(traffic: dict, seconds: float, seed: int) -> list[dict]:
    """The requests of one window: due time, rows, axis, k, pool offset."""
    rng = np.random.default_rng(seed)
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-_quantiles(n)) / rate
    rng.shuffle(gaps)
    due = np.cumsum(gaps) - gaps[0]
    due *= (seconds * (n - 1) / n) / max(due[-1], 1e-12)
    spec = traffic["rows"]
    r = np.arange(spec["min"], spec["max"] + 1)
    cdf = np.cumsum(r ** -float(spec["power"]))
    cdf /= cdf[-1]
    rows = r[np.searchsorted(cdf, _quantiles(n))]
    rng.shuffle(rows)
    axes = _shares(n, traffic["axis"], rng)
    ks = _shares(n, {int(k): v for k, v in traffic["k"].items()}, rng)
    pool = traffic["pool"]
    offsets = [int(rng.integers(0, pool[a] - int(q) + 1))
               for a, q in zip(axes, rows)]
    return [{"due": float(t), "rows": int(q), "axis": a, "k": int(k),
             "offset": o}
            for t, q, a, k, o in zip(due, rows, axes, ks, offsets)]


def open_loop(submit, requests: list[dict], payload, *, wait_s: float,
              trace: dict | None = None) -> dict:
    """Send ``requests`` on schedule through ``submit(x, axis, k)``.

    ``payload(req)`` gives a request's rows. Each request is timed from
    when it was due, so a stalled sender's wait counts. Completions are
    read by one waiter thread per ``(axis, k)`` queue, in the order the
    service answers that queue. A request with no answer ``wait_s`` after
    the last one was due is recorded as never answered.
    """
    n = len(requests)
    done_at = [math.nan] * n
    results = [None] * n
    sent_at = [math.nan] * n
    groups: dict = {}
    deadline = [math.inf]

    def waiter(q: queue.Queue):
        while True:
            item = q.get()
            if item is None:
                return
            i, ticket = item
            while not ticket.done() and time.perf_counter() < deadline[0]:
                try:
                    ticket.result(0.25)
                except TimeoutError:
                    pass
            if ticket.done():
                done_at[i] = time.perf_counter()
                results[i] = ticket.result(0.0)

    threads = []
    for req in requests:
        key = (req["axis"], req["k"])
        if key not in groups:
            groups[key] = queue.Queue()
            th = threading.Thread(target=waiter, args=(groups[key],),
                                  daemon=True, name=f"bench-wait-{key}")
            th.start()
            threads.append(th)
    t0 = time.perf_counter()
    tracing = False
    for i, req in enumerate(requests):
        due = t0 + req["due"]
        if trace is not None:
            now = time.perf_counter() - t0
            if not tracing and now >= trace["from_s"] and "t" not in trace:
                trace["start"]()
                tracing, trace["t"] = True, now
            elif tracing and now - trace["t"] >= trace["for_s"]:
                trace["stop"]()
                tracing = False
        pause = due - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
        sent_at[i] = time.perf_counter()
        ticket = submit(payload(req), req["axis"], req["k"])
        groups[(req["axis"], req["k"])].put((i, ticket))
    if tracing:
        trace["stop"]()
    deadline[0] = t0 + requests[-1]["due"] + wait_s
    for q in groups.values():
        q.put(None)
    for th in threads:
        th.join(max(deadline[0] - time.perf_counter(), 0.0) + 5.0)
    return {"t0": t0, "sent_at": sent_at, "done_at": done_at,
            "results": results, "deadline": deadline[0],
            "threads_alive": sum(th.is_alive() for th in threads)}
