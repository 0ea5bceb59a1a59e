"""Trace-time routing census for the precision-flow static analyzer.

Every matmul role in the model goes through exactly one of a small set of
routes (fused Pallas kernel, QDQ simulation, QDQ *fallback* from a pallas
impl, plain dot for passthrough recipes, packed serving dot).  The route
decision happens at trace time in ``core.qlinear`` — which historically
made silent fallbacks invisible: a spec the kernel cannot realize would
quietly take the QDQ path and no test could tell.

This module records those decisions.  ``capture()`` installs a
thread-local :class:`RoutingLog`; while it is active, ``core.qlinear``
and ``kernels.ops`` append one :class:`RouteEvent` per matmul-role
routing decision, tagged with the innermost static layer label (pushed
by ``models.stack``) and plan class (derived from the telemetry module
scope).  Because tracing re-enters functions (custom_vjp forward
re-trace, remat replay, scan bodies traced once per run), raw event
counts are NOT stable — consumers must dedupe by ``RouteEvent.cell()``
identity, which :meth:`RoutingLog.cells` does.

The log costs nothing when inactive (one thread-local attribute read),
and never touches traced values — only static metadata — so capturing a
trace is bit-identical to not capturing it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, List, Optional, Tuple

__all__ = ["RouteEvent", "RoutingLog", "capture", "active", "record",
           "layer_scope", "class_scope", "plan_class_for_module",
           "current_layer", "current_class", "current_cell"]

# Telemetry module scopes -> plan class (see PrecisionPlan cell classes).
# attn and cross-attn draw from the plan's attn_linear cell; ssm/ffn/moe
# all draw from ffn_linear; the LM head from head_linear.
_MODULE_TO_CLASS = {"attn": "attn", "cross": "attn",
                    "ssm": "ffn", "ffn": "ffn", "moe": "ffn",
                    "head": "head"}


def plan_class_for_module(module: str) -> Optional[str]:
    """Map a telemetry module-scope name to its PrecisionPlan class."""
    return _MODULE_TO_CLASS.get(module)


@dataclasses.dataclass(frozen=True)
class RouteEvent:
    """One matmul-role routing decision observed during tracing.

    ``layer`` is a static label: ``"L3"`` for unrolled layer 3, or the
    slice form ``"L1:8:4"`` for a scan-body position covering
    ``range(1, 8, 4)`` (scan bodies trace once per run, so one event
    stands for every layer the position covers).  ``route``:

      ``pallas``        fused kernel (``mode_a``/``mode_b``/``pipeline``
                        say how each operand is quantized in-kernel);
      ``qdq``           QDQ simulation chosen by config (impl='qdq');
      ``qdq_fallback``  a pallas impl that could NOT realize the specs —
                        ``reasons`` carries one structured string per
                        unrealizable operand;
      ``dot``           passthrough recipe lowered to a plain dot;
      ``packed_dot``    serving: pre-dequantized PackedTensor panel dot.

    ``sr_a``/``sr_b``: stochastic rounding *actually armed* for that
    operand (spec says ``:sr`` AND key material reached the call) — the
    check "SR appears exactly where specs say so" compares these against
    the plan, catching dropped-key bugs as well as spec drift.

    ``sharded``: a ``pallas`` role's quantize passes ran under
    ``shard_map`` over a mesh of more than one device (``kernels.ops``);
    ``cross_amax``: one of its amax groups spanned chips and took a
    cross-chip ``pmax`` before quantizing.
    """
    layer: Optional[str]
    cls: Optional[str]
    role: str                      # fwd | dgrad | wgrad
    route: str
    spec_a: str
    spec_b: str
    mode_a: Optional[str] = None
    mode_b: Optional[str] = None
    pipeline: Optional[str] = None
    sr_a: bool = False
    sr_b: bool = False
    reasons: Tuple[str, ...] = ()
    sharded: bool = False
    cross_amax: bool = False

    def cell(self) -> Tuple:
        """Dedupe identity: trace-order independent, retrace stable."""
        return (self.layer, self.cls, self.role, self.route,
                self.spec_a, self.spec_b, self.mode_a, self.mode_b,
                self.pipeline, self.sr_a, self.sr_b, self.reasons,
                self.sharded, self.cross_amax)

    def to_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d["reasons"] = list(self.reasons)
        return d


class RoutingLog:
    """Accumulates :class:`RouteEvent`s for one captured trace."""

    def __init__(self) -> None:
        self.events: List[RouteEvent] = []

    def add(self, ev: RouteEvent) -> None:
        self.events.append(ev)

    def cells(self) -> List[RouteEvent]:
        """Events deduped by :meth:`RouteEvent.cell`, in first-seen order.

        This is the stable census: retraces (custom_vjp fwd, remat
        replay) re-emit identical events, which collapse here.
        """
        seen = {}
        for ev in self.events:
            seen.setdefault(ev.cell(), ev)
        return list(seen.values())

    def fallbacks(self) -> List[RouteEvent]:
        return [ev for ev in self.cells() if ev.route == "qdq_fallback"]

    def to_dict(self) -> Dict:
        return {"cells": [ev.to_dict() for ev in self.cells()],
                "n_raw_events": len(self.events)}


_STATE = threading.local()


def _log() -> Optional[RoutingLog]:
    return getattr(_STATE, "log", None)


def active() -> Optional[RoutingLog]:
    """The installed RoutingLog, or None (the common, zero-cost case)."""
    return _log()


def current_layer() -> Optional[str]:
    return getattr(_STATE, "layer", None)


def current_class() -> Optional[str]:
    return getattr(_STATE, "cls", None)


def current_cell() -> Optional[Tuple[Optional[str], Optional[str]]]:
    """The (layer, class) attribution at this point of the trace, or None
    when no census is running.

    Captured by ``qlinear`` IN CONTEXT and threaded down to the matmul
    impls as a static argument: custom_vjp forward/backward rules trace
    lazily, outside the ``layer_scope``/``class_scope`` Python contexts,
    so events recorded there must carry the cell explicitly.
    """
    if _log() is None:
        return None
    return (current_layer(), current_class())


@contextlib.contextmanager
def capture():
    """Install a fresh RoutingLog for the duration of a trace."""
    prev = _log()
    log = RoutingLog()
    _STATE.log = log
    try:
        yield log
    finally:
        _STATE.log = prev


@contextlib.contextmanager
def layer_scope(label: Optional[str]):
    """Static layer label for events recorded inside (``"L3"`` or the
    scan-slice form ``"L{start}:{stop}:{step}"``).  No-op when no log is
    installed or ``label`` is None."""
    if _log() is None or label is None:
        yield
        return
    prev = getattr(_STATE, "layer", None)
    _STATE.layer = label
    try:
        yield
    finally:
        _STATE.layer = prev


@contextlib.contextmanager
def class_scope(module: str):
    """Plan-class attribution from a telemetry module scope name."""
    if _log() is None:
        yield
        return
    prev = getattr(_STATE, "cls", None)
    _STATE.cls = plan_class_for_module(module) or prev
    try:
        yield
    finally:
        _STATE.cls = prev


def record(role: str, route: str, spec_a, spec_b, *,
           mode_a: Optional[str] = None, mode_b: Optional[str] = None,
           pipeline: Optional[str] = None,
           sr_a: bool = False, sr_b: bool = False,
           reasons: Tuple[str, ...] = (),
           cell: Optional[Tuple[Optional[str], Optional[str]]] = None,
           sharded: bool = False, cross_amax: bool = False) -> None:
    """Append a routing decision (no-op unless a log is installed).

    ``cell`` overrides the ambient (layer, class) attribution — required
    for events recorded from lazily-traced custom_vjp rules (see
    :func:`current_cell`)."""
    log = _log()
    if log is None:
        return
    layer, cls = cell if cell is not None else (current_layer(),
                                                current_class())
    log.add(RouteEvent(
        layer=layer, cls=cls, role=role, route=route,
        spec_a=str(spec_a), spec_b=str(spec_b), mode_a=mode_a, mode_b=mode_b,
        pipeline=pipeline, sr_a=sr_a, sr_b=sr_b, reasons=tuple(reasons),
        sharded=sharded, cross_amax=cross_amax))
