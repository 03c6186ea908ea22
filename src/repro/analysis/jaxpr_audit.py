"""Layer 2 — jaxpr trace auditor (rules A1-A3, DESIGN.md §13).

The AST layer sees source; this layer sees what JAX will actually stage.
Entry points (``entry_points.ENTRY_POINTS``) are traced with abstract
inputs via ``jax.make_jaxpr`` and their jaxprs walked recursively through
every nested closed jaxpr (``pjit``, ``scan``, ``while``, ``cond``,
custom-call wrappers).

**A1 — RNG fused into gather-heavy equations.** The permanent regression
gate for the PR 4 defect: a ``threefry2x32``/``random_bits`` output that
reaches a ``gather`` operand *without passing a materialization barrier*
(``dot_general``, QR/Cholesky/solve/SVD — ops XLA cannot fuse a
generator through) lets XLA inline the generator into the gather and
recompute it per gathered element (measured ~7x per product). Implemented
as boolean taint propagation over the equation graph: RNG primitives
source taint, barrier primitives absorb it, a tainted ``gather`` operand
is a finding. RNG inside a ``while`` body is flagged unconditionally
(trip count is data-dependent — the draw count is not replayable);
RNG inside ``scan`` bodies is fine *by design* here (counter-derived
per-resample keys) as long as it stays barriered from gathers.

**A2 — unintended dtype promotion.** The same entry points are re-traced
under ``jax_enable_x64`` and every equation output checked for non-weak
``float64``/``complex128`` avals. With x64 off, a stray promotion (an
implicit-dtype ``random.normal``, a numpy f64 constant) is silently
truncated and invisible; under x64 it surfaces exactly where it would
change kernel numerics. Weak-typed scalars (Python literals) are exempt.

**A3 — recompile guard.** ``count_recompiles`` calls an entry point
twice with same-shape/dtype (fresh) arguments and counts XLA compile
events via the ``jax_log_compiles`` hook; any compile after warmup is a
cache miss — a non-hashable static, an accidental weak-type flip, or a
Python-object config leaking into trace identity.
"""

from __future__ import annotations

import contextlib
import logging
from typing import Callable, Iterator

import jax
from jax.extend import core as _jcore

from .findings import Finding

__all__ = ["audit_rng_gather", "audit_dtypes", "count_recompiles",
           "audit_entry_jaxpr", "RNG_SOURCES", "BARRIERS"]

#: primitives that *generate* pseudo-random bits
RNG_SOURCES = frozenset({"threefry2x32", "random_bits", "random_gamma"})
#: primitives that stop taint. Two families: linear-algebra custom calls
#: whose results XLA materializes (a generator cannot be fused through
#: them), and reductions/sorts/contractions — the PR 4 hazard is a *pure
#: elementwise* chain from generator to gather operand (each gathered
#: element recomputes its own generator lane); once the dependence
#: collapses through a reduction or reordering, per-element regeneration
#: is no longer what a fused gather would do. This is also what keeps
#: legitimate sampling (inverse-CDF via cumsum/searchsorted, permutation
#: via sort, argmin-based selection) out of the findings.
BARRIERS = frozenset({
    # materializing linear algebra
    "dot_general", "qr", "householder_product", "cholesky",
    "triangular_solve", "svd", "eigh", "lu", "custom_linear_solve",
    "conv_general_dilated",
    # reductions / reorderings that end the elementwise chain
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "reduce_window_sum",
    "reduce_window_max", "reduce_window_min", "cumsum", "cumprod", "cummax",
    "cummin", "cumlogsumexp", "sort", "top_k",
})
#: per-element consumers that replay their operand per gathered index when
#: the producer fuses in
_SINKS = frozenset({"gather"})

_OPAQUE = frozenset({"pallas_call"})  # operands are materialized pre-launch


def _is_closed(x) -> bool:
    return isinstance(x, _jcore.ClosedJaxpr)


def _sub_named(eqn):
    """Sub-jaxpr for call-like eqns whose invars map 1:1 (pjit, remat,
    custom_jvp/vjp wrappers)."""
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if _is_closed(sub) and len(sub.jaxpr.invars) == len(eqn.invars):
            return sub
        if isinstance(sub, _jcore.Jaxpr) and len(sub.invars) == len(eqn.invars):
            return _jcore.ClosedJaxpr(sub, ())
    return None


def _iter_all_subjaxprs(params: dict) -> Iterator[_jcore.ClosedJaxpr]:
    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for u in vals:
            if _is_closed(u):
                yield u
            elif isinstance(u, _jcore.Jaxpr):
                yield _jcore.ClosedJaxpr(u, ())


# --------------------------------------------------------------------------
# A1 — taint propagation
# --------------------------------------------------------------------------

class _TaintWalker:
    def __init__(self, entry: str):
        self.entry = entry
        self.findings: list[Finding] = []
        self._seen: set[tuple] = set()

    def _finding(self, message: str, evidence: str) -> None:
        key = (message, evidence)
        if key not in self._seen:
            self._seen.add(key)
            self.findings.append(Finding(
                rule="A1", path=f"entry:{self.entry}", line=0,
                message=message, evidence=evidence))

    def propagate(self, closed: _jcore.ClosedJaxpr, taint_in: list[bool],
                  path: tuple[str, ...] = (), in_while: bool = False,
                  ) -> list[bool]:
        jaxpr = closed.jaxpr
        taint: dict = {}
        for var, t in zip(jaxpr.invars, taint_in):
            taint[var] = t

        def is_t(atom) -> bool:
            return (not isinstance(atom, _jcore.Literal)
                    and taint.get(atom, False))

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            in_t = [is_t(v) for v in eqn.invars]
            sub = _sub_named(eqn)
            if name in RNG_SOURCES:
                if in_while:
                    self._finding(
                        "RNG primitive inside a while-loop body — draw "
                        "count is data-dependent, not replayable",
                        f"{' > '.join(path) or '<top>'} > {name}")
                out_t = [True] * len(eqn.outvars)
            elif name in BARRIERS:
                out_t = [False] * len(eqn.outvars)
            elif name in _SINKS:
                if in_t and in_t[0]:
                    self._finding(
                        "RNG output reaches a gather operand with no "
                        "materialization barrier — XLA can fuse the "
                        "generator into the gather (the PR 4 ~7x SpMM "
                        "regression)",
                        f"{' > '.join(path) or '<top>'} > {name}; insert an "
                        "orthonormalization / dot_general between the "
                        "sample and the sparse product")
                out_t = [any(in_t)] * len(eqn.outvars)
            elif name in _OPAQUE:
                out_t = [False] * len(eqn.outvars)
            elif name == "scan":
                nc = eqn.params["num_consts"]
                ncar = eqn.params["num_carry"]
                body = eqn.params["jaxpr"]
                cur = list(in_t)
                out = [False] * len(eqn.outvars)
                for _ in range(max(2, ncar + 1)):  # monotone fixpoint
                    out = self.propagate(body, cur, path + ("scan",),
                                         in_while)
                    changed = False
                    for i in range(ncar):
                        if out[i] and not cur[nc + i]:
                            cur[nc + i] = True
                            changed = True
                    if not changed:
                        break
                out_t = out
            elif name == "while":
                cn = eqn.params["cond_nconsts"]
                bn = eqn.params["body_nconsts"]
                cond = eqn.params["cond_jaxpr"]
                body = eqn.params["body_jaxpr"]
                carry_t = list(in_t[cn + bn:])
                for _ in range(max(2, len(carry_t) + 1)):
                    out = self.propagate(body, in_t[cn:cn + bn] + carry_t,
                                         path + ("while",), True)
                    changed = False
                    for i, t in enumerate(out):
                        if t and not carry_t[i]:
                            carry_t[i] = True
                            changed = True
                    if not changed:
                        break
                self.propagate(cond, in_t[:cn] + carry_t,
                               path + ("while_cond",), True)
                out_t = carry_t
            elif name == "cond":
                branches = eqn.params["branches"]
                outs = [self.propagate(b, in_t[1:], path + ("cond",),
                                       in_while) for b in branches]
                out_t = [any(o[i] for o in outs)
                         for i in range(len(eqn.outvars))]
            elif sub is not None:
                label = eqn.params.get("name", name)
                out_t = self.propagate(sub, in_t, path + (str(label),),
                                       in_while)
            else:
                out_t = [any(in_t)] * len(eqn.outvars)
            for v, t in zip(eqn.outvars, out_t):
                taint[v] = t
        return [is_t(v) for v in jaxpr.outvars]


def audit_rng_gather(entry: str, closed: _jcore.ClosedJaxpr) -> list[Finding]:
    """A1 over one traced entry point (inputs start untainted)."""
    walker = _TaintWalker(entry)
    walker.propagate(closed, [False] * len(closed.jaxpr.invars))
    return walker.findings


# --------------------------------------------------------------------------
# A2 — dtype promotion audit
# --------------------------------------------------------------------------

_BAD_DTYPES = ("float64", "complex128")


def audit_dtypes(entry: str, closed: _jcore.ClosedJaxpr) -> list[Finding]:
    """Flag non-weak f64/c128 equation outputs anywhere in the trace.

    Meaningful only when the trace ran under ``jax_enable_x64`` (see
    ``entry_points.trace_entry(x64=True)``) — with x64 off these dtypes
    cannot appear and the audit trivially passes.
    """
    findings: list[Finding] = []
    seen: set[tuple] = set()

    def walk(jaxpr: _jcore.Jaxpr, path: tuple[str, ...]) -> None:
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                dt = getattr(aval, "dtype", None)
                if (dt is not None and str(dt) in _BAD_DTYPES
                        and not getattr(aval, "weak_type", False)):
                    key = (name, str(dt), path)
                    if key in seen:
                        continue
                    seen.add(key)
                    findings.append(Finding(
                        rule="A2", path=f"entry:{entry}", line=0,
                        message=f"non-weak {dt} produced by {name!r} — an "
                                "implicit promotion the f32 kernel contract "
                                "silently truncates when x64 is off",
                        evidence=f"{' > '.join(path) or '<top>'} > {name}; "
                                 "pass an explicit dtype=jnp.float32"))
            for sub in _iter_all_subjaxprs(eqn.params):
                walk(sub.jaxpr, path + (str(eqn.params.get(
                    "name", name)),))

    walk(closed.jaxpr, ())
    return findings


def audit_entry_jaxpr(entry: str, closed: _jcore.ClosedJaxpr,
                      x64_traced: bool = False) -> list[Finding]:
    """A1 (+A2 when the trace ran under x64) over one entry point."""
    findings = audit_rng_gather(entry, closed)
    if x64_traced:
        findings += audit_dtypes(entry, closed)
    return findings


# --------------------------------------------------------------------------
# A3 — recompile guard
# --------------------------------------------------------------------------

@contextlib.contextmanager
def _capture_compiles() -> Iterator[list[str]]:
    """Capture XLA 'Compiling <fn> ...' events via jax_log_compiles."""
    records: list[str] = []

    class _Handler(logging.Handler):
        def emit(self, record: logging.LogRecord) -> None:
            msg = record.getMessage()
            if msg.startswith("Compiling "):
                records.append(msg)

    handler = _Handler(level=logging.DEBUG)
    logger = logging.getLogger("jax._src.interpreters.pxla")
    prev_level = logger.level
    prev_flag = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    logger.addHandler(handler)
    if logger.level > logging.DEBUG or logger.level == logging.NOTSET:
        logger.setLevel(logging.DEBUG)
    try:
        yield records
    finally:
        logger.removeHandler(handler)
        logger.setLevel(prev_level)
        jax.config.update("jax_log_compiles", prev_flag)


def count_recompiles(entry: str, fn: Callable, make_args: Callable[[], tuple],
                     repeats: int = 2) -> tuple[int, list[Finding]]:
    """Call ``fn`` on fresh same-shape args ``1 + repeats`` times; any XLA
    compile event after the warmup call is a jit cache miss.

    ``make_args`` must build *new* arrays each call (same shapes/dtypes,
    different buffers) so donated/cached-buffer effects can't mask a miss.
    Returns ``(n_misses, findings)`` — findings non-empty iff misses > 0.
    """
    fn(*make_args())  # warmup: tracing + first compile are expected
    with _capture_compiles() as records:
        for _ in range(repeats):
            out = fn(*make_args())
        jax.block_until_ready(out)
    findings = []
    if records:
        findings.append(Finding(
            rule="A3", path=f"entry:{entry}", line=0,
            message=f"{len(records)} XLA compile(s) on same-shape repeat "
                    "calls — the jit cache is missing",
            evidence="; ".join(sorted(set(records))[:4])))
    return len(records), findings
