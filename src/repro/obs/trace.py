"""Phase-level tracing spans (DESIGN.md §14).

``span("phase", **attrs)`` opens a nestable timing span; spans form a
per-run tree (one :class:`Trace` per thread) exportable as JSONL
(``obs.export``) and pretty-printable as a text flamegraph
(``python -m repro.obs trace.jsonl``).

Two rules make the numbers honest and the hot paths safe:

* **Fencing.** JAX dispatch is asynchronous — a wall-clock around a jit
  call measures *enqueue*, not compute. A span that wraps device work
  registers its outputs via ``sp.fence(out)``; span exit calls
  ``jax.block_until_ready`` on everything fenced *before* reading the
  clock, so the span's duration includes the device time it claims to
  measure. ``fence`` returns its argument unchanged, and under tracing
  (``jax.make_jaxpr``) ``block_until_ready`` is a no-op on tracers — a
  fenced span inside a staged function adds zero primitives to the jaxpr
  (the obs-enabled entries in ``analysis.entry_points`` pin this).
* **Off by default.** When disabled (the default; enable with
  ``configure(enabled=True)`` or ``REPRO_OBS=1``), ``span`` returns a
  shared no-op singleton: no allocation, no clock reads, no fencing —
  instrumented code pays one dict lookup and one no-op ``with``.

When enabled, every span also enters a
``jax.profiler.TraceAnnotation("obs." + name)`` and leaves it after its
fence, so a profiler trace holds the same spans on its own clock; and
every JAX trace, lowering, backend compile and persistent-cache load
reported by ``jax.monitoring`` while a span is open is recorded as a
``compile`` child span of it (:func:`_on_compile`).

Hooks live strictly outside jit: spans never touch tracer values (fence
stores a reference, it never inspects), attrs must be host scalars, and
nothing here forces a device sync except the explicit exit fence.
"""

from __future__ import annotations

import os
import threading
import time

__all__ = ["Span", "Trace", "span", "event", "configure", "enabled",
           "current_trace", "reset_trace", "TRACE_SCHEMA_VERSION"]

#: bumped when the JSONL row shape changes; validators check it.
TRACE_SCHEMA_VERSION = 1

_cfg = {"enabled": os.environ.get("REPRO_OBS", "") not in ("", "0"),
        "listening": False}
_tls = threading.local()

#: ``jax.monitoring`` duration events recorded as ``compile`` spans.
COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
})


def configure(enabled: bool | None = None) -> None:
    """Flip the global span switch (``None`` leaves it unchanged)."""
    if enabled is not None:
        _cfg["enabled"] = bool(enabled)
    if _cfg["enabled"] and not _cfg["listening"]:
        _listen()


def enabled() -> bool:
    return _cfg["enabled"]


class Span:
    """One timed phase: name, attrs, child spans, point events."""

    __slots__ = ("name", "attrs", "children", "events", "t_start", "t_end")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.children: list[Span] = []
        self.events: list[dict] = []
        self.t_start = 0.0
        self.t_end = 0.0

    @property
    def duration_s(self) -> float:
        return max(self.t_end - self.t_start, 0.0)

    def __repr__(self) -> str:  # debugging aid only
        return (f"Span({self.name!r}, {self.duration_s * 1e3:.2f}ms, "
                f"{len(self.children)} children)")


class Trace:
    """Per-thread span forest plus free (out-of-span) events."""

    __slots__ = ("roots", "events", "t0")

    def __init__(self):
        self.roots: list[Span] = []
        self.events: list[dict] = []
        self.t0 = time.perf_counter()

    def walk(self):
        """Depth-first ``(span, depth, path)`` over the whole forest."""
        def rec(sp: Span, depth: int, prefix: str):
            path = f"{prefix}/{sp.name}" if prefix else sp.name
            yield sp, depth, path
            for c in sp.children:
                yield from rec(c, depth + 1, path)
        for root in self.roots:
            yield from rec(root, 0, "")

    def find(self, name: str) -> list[Span]:
        """All spans named ``name``, depth-first order."""
        return [sp for sp, _, _ in self.walk() if sp.name == name]


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_trace() -> Trace:
    tr = getattr(_tls, "trace", None)
    if tr is None:
        tr = _tls.trace = Trace()
    return tr


def reset_trace() -> Trace:
    """Start a fresh trace for this thread (returns it)."""
    _tls.trace = Trace()
    _tls.stack = []
    return _tls.trace


def _on_compile(event: str, duration: float, **kwargs) -> None:
    """``jax.monitoring`` listener: one ``compile`` span per event.

    The span ends now and lasts ``duration``; its attrs are the event and
    the ``fun_name`` JAX passes on trace, lowering and compile. It is a
    child of the innermost open span of the reporting thread; a compile
    outside every span belongs to no phase of the program and becomes an
    ``event`` on the trace instead. An event reported inside another (a
    nested jit traced within its caller, the cache load within a backend
    compile) arrives first; when the enclosing one arrives, the earlier
    spans it covers become events on it, so ``compile`` spans never
    overlap and their durations add up to the time spent compiling.
    """
    if not _cfg["enabled"] or event not in COMPILE_EVENTS:
        return
    t_end = time.perf_counter()
    attrs = {"event": event.rsplit("/", 1)[-1]}
    if "fun_name" in kwargs:
        attrs["fun_name"] = kwargs["fun_name"]
    stack = _stack()
    if not stack:
        tr = current_trace()
        tr.events.append({"name": "compile", "t": t_end - tr.t0,
                          "attrs": dict(attrs, dur_s=duration)})
        return
    sp = Span("compile", attrs)
    sp.t_start, sp.t_end = t_end - duration, t_end
    siblings = stack[-1].children
    while (siblings and siblings[-1].name == "compile"
           and siblings[-1].t_start + siblings[-1].t_end >= 2 * sp.t_start):
        inner = siblings.pop()
        sp.events.insert(0, {"name": "compile",
                             "t": inner.t_end - current_trace().t0,
                             "attrs": dict(inner.attrs,
                                           dur_s=inner.duration_s)})
    siblings.append(sp)


def _listen() -> None:
    """Register :func:`_on_compile` with ``jax.monitoring``, once."""
    _cfg["listening"] = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_compile)


class _ActiveSpan:
    """Context manager yielded by :func:`span` when obs is enabled."""

    __slots__ = ("_span", "_fenced", "_annotation")

    def __init__(self, name: str, attrs: dict):
        self._span = Span(name, attrs)
        self._fenced: list | None = None
        self._annotation = None

    def __enter__(self) -> "_ActiveSpan":
        import jax
        stack = _stack()
        parent = stack[-1] if stack else None
        (parent.children if parent is not None
         else current_trace().roots).append(self._span)
        stack.append(self._span)
        self._annotation = jax.profiler.TraceAnnotation(
            "obs." + self._span.name)
        self._annotation.__enter__()
        self._span.t_start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        sp = self._span
        if self._fenced is not None:
            import jax
            jax.block_until_ready(self._fenced)
            self._fenced = None
        sp.t_end = time.perf_counter()
        self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            sp.attrs.setdefault("error", exc_type.__name__)
        stack = _stack()
        if stack and stack[-1] is sp:
            stack.pop()
        return False

    def fence(self, value):
        """Register device outputs to ``block_until_ready`` at span exit.

        Returns ``value`` unchanged so call sites stay expression-shaped.
        """
        if self._fenced is None:
            self._fenced = [value]
        else:
            self._fenced.append(value)
        return value

    def set(self, **attrs) -> "_ActiveSpan":
        """Attach/overwrite structured attributes (host scalars only)."""
        self._span.attrs.update(attrs)
        return self

    @property
    def span(self) -> Span:
        return self._span


class _NoopSpan:
    """Disabled-mode singleton: every method is a no-op passthrough."""

    __slots__ = ()
    span = None

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def fence(self, value):
        return value

    def set(self, **attrs) -> "_NoopSpan":
        return self


_NOOP = _NoopSpan()


def span(name: str, **attrs):
    """Open a span named ``name`` (no-op singleton when obs is disabled)."""
    if not _cfg["enabled"]:
        return _NOOP
    if not _cfg["listening"]:
        _listen()
    return _ActiveSpan(name, attrs)


def event(name: str, **attrs) -> None:
    """Record a point event on the current span (or the trace root).

    Structured sibling of a log line: recovery restores, stale-checkpoint
    warnings, kernel dispatch decisions. No-op when obs is disabled —
    callers that need the signal unconditionally should also log/count.
    """
    if not _cfg["enabled"]:
        return
    tr = current_trace()
    rec = {"name": name, "t": time.perf_counter() - tr.t0, "attrs": attrs}
    stack = _stack()
    (stack[-1].events if stack else tr.events).append(rec)
