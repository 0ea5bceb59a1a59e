"""qlint: static precision-flow analyzer for compiled train/decode steps.

Traces a step (jaxpr + compiled per-device HLO — no execution) and audits
it against the resolved :class:`~repro.core.recipe.PrecisionPlan`.  Four
check families:

  * **kernel presence** — every (layer, class, role) cell the plan routes
    through the fused Pallas pipeline has matching ``pallas_call``
    equations in the graph (and ``qrole_*``-scoped ops in the per-device
    HLO); QDQ fallbacks are enumerated with their structured reasons
    (``core.qlinear.kernel_unsupported_reason`` vocabulary);
  * **role safety** — cells a protection preset keeps in BF16 are never
    fed through a quantize op (a ``qdq_*`` marker under a ``qrole_*``
    scope must be explained by the routing census, and every census cell's
    specs must match the plan's resolved cell), stochastic rounding is
    armed exactly where specs say ``:sr`` (dropped-key bugs included), and
    no f32 operand reaches a kernel-routed matmul (the model computes in
    ``cfg.dtype``);
  * **comms** — with a mesh and fp8 gradient compression, the gradient
    all-reduce payload dtype matches the quantize-before-communicate
    policy (``f8e4m3fn``, or its ``f16`` XLA:CPU legalization), and the
    block/tile quant-scale placement table still shards scales with their
    operand's reduction axis (the PR-6 policy,
    ``core.quantize.scale_logical_axes``);
  * **recompile budget** — a census over ``Trainer``-compiled step graphs
    flags step-cache keys outside the expected plan set (unexpected
    retraces).

Ground truth comes from three independent layers that must agree: the
trace-time routing census (``core.routing``, recorded at the exact dot
call), the jaxpr (``pallas_call`` equations + ``qrole_*``/``qdq_*``
named-scope markers), and the compiled HLO text (shared walker in
``analysis.hlo``).  The census says what the code *decided*; the graphs
say what was actually *staged*; the plan says what was *asked for* —
qlint cross-checks all three.

CLI::

    python -m repro.analysis.qlint --config tiny --plan fine_grained_fp4 \
        [--impl pallas] [--mesh 2,1] [--decode] [--json out.json] \
        [--expect FILE] [--update-expectations]

``--expect`` compares the normalized findings against a committed
expectations JSON (CI gate); ``--update-expectations`` rewrites that file
from the current audit (run it after an intentional routing change and
commit the diff).
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import re
import sys
from collections import Counter
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.hlo import collective_bytes, shape_bytes, walk_hlo
from repro.core import routing
from repro.core.quantize import QuantSpec, qdq_scope_name, scale_logical_axes
from repro.core.recipe import ROLE_SUBSETS, PrecisionPlan

__all__ = ["Finding", "QlintReport", "graph_census", "audit_cells",
           "audit_hlo_comms", "audit_scale_placement", "recompile_census",
           "audit_train_graph", "audit_decode_graph", "audit_decode_engine",
           "audit_trainer", "expectations_payload", "compare_expectations",
           "main"]

_TRAIN_ROLES = ("fwd", "dgrad", "wgrad")
# Payload dtypes acceptable for the fp8 gradient all-reduce: the real
# thing, or what XLA:CPU legalizes float8 collectives to (see
# analysis.hlo._WIRE_SCALE).
_FP8_WIRE_DTYPES = {"f8e4m3fn", "f8e5m2", "f16"}
# all-reduce payloads at or below this are shared-scale scalars (the fp8
# compressor's per-leaf global-amax reductions), not gradient bytes
_SCALE_AR_BYTES = 256

_QROLE_RE = re.compile(r"qrole_([a-z]+)")
_QDQ_RE = re.compile(r"qdq_[0-9A-Za-z_]+")


# ---------------------------------------------------------------------------
# Findings / report
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Finding:
    """One audit observation.

    ``severity``: ``violation`` (gate-failing), ``fallback`` (a pallas impl
    cell that took the QDQ path — counted separately because the tiny-
    config gate requires zero of them), or ``info``.
    """
    check: str          # kernel_presence | role_safety | comms | recompile
    severity: str       # violation | fallback | info
    where: str          # cell / op / key identifier
    message: str

    def to_dict(self) -> Dict[str, str]:
        return dataclasses.asdict(self)


class QlintReport:
    """Findings + census for one audited graph (or graph family)."""

    def __init__(self, label: str):
        self.label = label
        self.cells: List[Dict[str, Any]] = []
        self.summary: Dict[str, Any] = {}
        self.findings: List[Finding] = []

    # -- accounting --------------------------------------------------------

    def add(self, finding: Finding) -> None:
        self.findings.append(finding)

    def extend(self, findings: Sequence[Finding]) -> None:
        self.findings.extend(findings)

    def violations(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "violation"]

    def fallbacks(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "fallback"]

    @property
    def ok(self) -> bool:
        return not self.violations()

    # -- serialization -----------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        return {"label": self.label,
                "cells": self.cells,
                "summary": self.summary,
                "findings": [f.to_dict() for f in self.findings],
                "n_violations": len(self.violations()),
                "n_fallbacks": len(self.fallbacks())}

    def human_report(self) -> str:
        out = [f"== qlint: {self.label} =="]
        s = self.summary
        if s:
            out.append("  " + ", ".join(f"{k}={v}" for k, v in s.items()
                                        if not isinstance(v, dict)))
        for c in self.cells:
            bits = [f"{c['layer'] or '-':>8} {c['cls'] or '-':>5}",
                    f"{c['role']:>5} -> {c['route']:<12}",
                    f"{c['spec_a']} | {c['spec_b']}"]
            extras = []
            if c.get("pipeline"):
                extras.append(c["pipeline"])
            if c.get("sr_a") or c.get("sr_b"):
                extras.append("sr=" + ("a" if c["sr_a"] else "")
                              + ("b" if c["sr_b"] else ""))
            if c.get("reasons"):
                extras.append("; ".join(c["reasons"]))
            out.append("  " + "  ".join(bits)
                       + (("  [" + ", ".join(extras) + "]") if extras
                          else ""))
        if not self.findings:
            out.append("  findings: none")
        for f in self.findings:
            out.append(f"  [{f.severity.upper():>9}] {f.check}: "
                       f"{f.where}: {f.message}")
        out.append(f"  => {len(self.violations())} violation(s), "
                   f"{len(self.fallbacks())} fallback(s)")
        return "\n".join(out)


# ---------------------------------------------------------------------------
# Jaxpr walking
# ---------------------------------------------------------------------------

def _as_jaxprs(v) -> List[Any]:
    name = type(v).__name__
    if name == "ClosedJaxpr":
        return [v.jaxpr]
    if name == "Jaxpr":
        return [v]
    if isinstance(v, (tuple, list)):
        return [j for x in v for j in _as_jaxprs(x)]
    return []


def _name_stack(eqn) -> str:
    try:
        return str(eqn.source_info.name_stack)
    except AttributeError:
        return ""


def _iter_eqns(jaxpr, prefix: str = "") -> Iterator[Tuple[Any, str]]:
    """(equation, full name-stack path) pairs, recursing into sub-jaxprs
    (scan bodies, pjit calls, custom_vjp call jaxprs, remat).

    Name stacks are RELATIVE to their enclosing jaxpr — an equation inside
    a pjit/remat/scan sub-jaxpr only carries the scopes entered since that
    call, while the call equation itself carries the outer scopes.  The
    walk therefore accumulates the ancestor call equations' stacks into
    ``prefix`` so e.g. a ``pallas_call`` staged under ``qrole_wgrad`` is
    attributable even though its own stack is empty.
    """
    for eqn in jaxpr.eqns:
        stack = _name_stack(eqn)
        full = f"{prefix}/{stack}" if prefix and stack else (prefix or stack)
        yield eqn, full
        for v in eqn.params.values():
            for sub in _as_jaxprs(v):
                yield from _iter_eqns(sub, full)


def graph_census(closed_jaxpr, compute_dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Census of precision-flow markers in a (post-autodiff) jaxpr.

    Returns ``pallas_calls`` (role -> count, attributed via the
    ``qrole_*`` named scopes), ``qdq_markers`` ((role, scope-name) ->
    count; role ``"-"`` for quantize ops outside any matmul role, e.g. the
    KV-cache codec), and ``f32_kernel_operands`` — pallas_call equations
    with a floating operand wider than ``compute_dtype`` (the "no f32
    upcast into fp4-routed matmuls" check), the splash flash-attention
    kernels excepted: their backward reads the float32 softmax
    statistics.
    """
    pallas = Counter()
    qdq = Counter()
    wide = []
    n_eqns = 0
    wide_bits = jnp.finfo(compute_dtype).bits
    for eqn, stack in _iter_eqns(closed_jaxpr.jaxpr):
        n_eqns += 1
        roles = _QROLE_RE.findall(stack)
        role = roles[-1] if roles else None
        if eqn.primitive.name == "pallas_call":
            pallas[role or "-"] += 1
            splash = str(eqn.params.get("name", "")).startswith("splash_")
            for var in (() if splash else eqn.invars):
                aval = getattr(var, "aval", None)
                dt = getattr(aval, "dtype", None)
                # Matrix operands only: scalar/vector kernel parameters
                # (seeds, eps floors) are legitimately f32.
                if (dt is not None and len(getattr(aval, "shape", ())) >= 2
                        and jnp.issubdtype(dt, jnp.floating)
                        and jnp.finfo(dt).bits > wide_bits):
                    wide.append(f"qrole_{role or '?'}: {dt} operand "
                                f"{getattr(aval, 'shape', '?')}")
        for marker in _QDQ_RE.findall(stack):
            qdq[(role or "-", marker)] += 1
    return {"pallas_calls": dict(pallas),
            "qdq_markers": {f"{r}/{m}": c for (r, m), c in qdq.items()},
            "f32_kernel_operands": wide,
            "n_eqns": n_eqns}


# ---------------------------------------------------------------------------
# Census-vs-plan audit (kernel presence + role safety)
# ---------------------------------------------------------------------------

def _label_layers(label: Optional[str], n_layers: int) -> List[int]:
    """Layer indices a census label covers ('L3' -> [3]; the scan-slice
    form 'L1:8:4' -> [1, 5]; None (the lm-head) -> [])."""
    if label is None:
        return []
    body = label[1:]
    parts = body.split(":")
    if len(parts) == 1:
        return [int(parts[0])]
    start, stop, step = (int(p) for p in parts)
    return [i for i in range(start, stop, step) if i < n_layers]


def _role_specs(mm, role: str) -> Tuple[QuantSpec, QuantSpec]:
    sa, sb = ROLE_SUBSETS[role]
    return getattr(mm, sa), getattr(mm, sb)


def _expected_routes(mm, role: str, impl: str, packed: bool) -> Tuple[str, ...]:
    from repro.core.qlinear import kernel_quant_mode
    if packed:
        if mm.fwd_x.is_passthrough:
            # protected params (lm head, embeddings) are never packed, so
            # a passthrough cell may be a plain dot over the bf16 weight
            return ("packed_dot", "dot")
        if impl in ("pallas", "pallas_two_pass"):
            return (("pallas",) if kernel_quant_mode(mm.fwd_x) is not None
                    else ("qdq_fallback",))
        return ("qdq",)
    if mm.is_passthrough:
        return ("dot",)
    if impl in ("pallas", "pallas_two_pass"):
        sa, sb = _role_specs(mm, role)
        ok = (kernel_quant_mode(sa) is not None
              and kernel_quant_mode(sb) is not None)
        return ("pallas",) if ok else ("qdq_fallback",)
    return ("qdq",)


def audit_cells(cells: Sequence[routing.RouteEvent], plan: PrecisionPlan,
                impl: str, *, roles: Sequence[str] = _TRAIN_ROLES,
                classes: Sequence[str] = ("attn", "ffn"),
                packed: bool = False) -> List[Finding]:
    """Role-safety + kernel-presence audit of the routing census vs the
    resolved plan.

    Checks per census cell: operand specs match the plan's resolved
    (layer, class, role) cell (a quantized spec on a role the plan keeps
    passthrough is the "protected BF16 cell fed through quantize"
    violation), SR armed exactly per spec, route matches what ``impl``
    should produce, fallbacks enumerated.  Coverage: every (layer, class)
    cell of the plan must be traced for every expected role.
    """
    findings: List[Finding] = []
    n_layers = plan.n_layers
    seen: Dict[Tuple[int, str, str], routing.RouteEvent] = {}
    head_seen = False

    for ev in cells:
        if ev.role == "sdpa":
            continue  # the attention core: no matmul role of the plan
        where = f"{ev.layer or 'head'}/{ev.cls or '?'}/{ev.role}"
        if ev.cls is None:
            findings.append(Finding(
                "role_safety", "violation", where,
                "census event with no class attribution — a matmul ran "
                "outside the module scopes"))
            continue
        if ev.cls == "head":
            head_seen = True
            mms = [("head", plan.for_class("head"))]
        else:
            layers = _label_layers(ev.layer, n_layers)
            if not layers:
                findings.append(Finding(
                    "role_safety", "violation", where,
                    f"census event with unparseable layer label "
                    f"{ev.layer!r}"))
                continue
            mms = [(i, plan.layer(i).for_class(ev.cls)) for i in layers]
        for layer_i, mm in mms:
            if isinstance(layer_i, int):
                seen[(layer_i, ev.cls, ev.role)] = ev
            if packed and ev.role == "fwd":
                # serving panel: census rhs is the pre-dequantized operand
                want_a, want_b = mm.fwd_x, None
            else:
                want_a, want_b = _role_specs(mm, ev.role)
            for op, want, got, sr in (("lhs", want_a, ev.spec_a, ev.sr_a),
                                      ("rhs", want_b, ev.spec_b, ev.sr_b)):
                if want is None:
                    continue
                if want.to_str() != got:
                    sev = "violation"
                    if want.is_passthrough:
                        msg = (f"protected (passthrough {want.to_str()}) "
                               f"{op} operand fed through quantize as "
                               f"{got}")
                    else:
                        msg = (f"{op} operand spec {got} does not match "
                               f"the plan's {want.to_str()}")
                    findings.append(Finding("role_safety", sev,
                                            f"{where}:{op}", msg))
                    continue
                if bool(want.stochastic) != bool(sr):
                    msg = ("plan spec says :sr but stochastic rounding is "
                           "not armed (dropped key?)"
                           if want.stochastic else
                           "stochastic rounding armed on a non-:sr spec")
                    findings.append(Finding("role_safety", "violation",
                                            f"{where}:{op}", msg))
            expects = _expected_routes(mm, ev.role, impl, packed)
            if ev.route not in expects:
                want = (repr(expects[0]) if len(expects) == 1
                        else f"one of {sorted(expects)}")
                findings.append(Finding(
                    "kernel_presence", "violation", where,
                    f"routed via {ev.route!r}, expected {want} for "
                    f"impl={impl!r}"))
            if ev.route == "qdq_fallback":
                findings.append(Finding(
                    "kernel_presence", "fallback", where,
                    "pallas impl fell back to QDQ: "
                    + ("; ".join(ev.reasons) or "no reason recorded")))

    # Coverage: every plan cell must have been traced.
    for i in range(n_layers):
        for cls in classes:
            mm = plan.layer(i).for_class(cls)
            need = roles if not mm.is_passthrough else ("fwd",)
            if packed:
                need = ("fwd",)
            for role in need:
                if (i, cls, role) not in seen:
                    findings.append(Finding(
                        "kernel_presence", "violation",
                        f"L{i}/{cls}/{role}",
                        "plan cell never traced — no routing event"))
    if not head_seen:
        findings.append(Finding("kernel_presence", "violation",
                                "head/fwd",
                                "lm-head matmul never traced"))
    return findings


def audit_graph_vs_census(graph: Dict[str, Any],
                          cells: Sequence[routing.RouteEvent]
                          ) -> List[Finding]:
    """Cross-check the jaxpr census against the routing census.

    Every role with pallas-routed cells that quantize an operand (or run
    the streaming kernel) must stage at least as many ``pallas_call``
    equations as it has such cells (remat/unroll can only add replays,
    never remove calls); every ``qdq_*`` marker under a
    ``qrole_*`` scope must be explained by a QDQ-routed census cell of
    that role (an unexplained one means a quantize op reached a path the
    census never sanctioned); f32 operands on kernel calls are
    violations.
    """
    findings: List[Finding] = []
    pallas_cells = Counter()
    allowed_markers = set()
    for ev in cells:
        # A bf16 x bf16 cell off the streaming pipeline is XLA's dot alone.
        if ev.route == "pallas" and (ev.pipeline == "stream"
                                     or ev.mode_a != "pass"
                                     or ev.mode_b != "pass"):
            pallas_cells[ev.role] += 1
        if ev.route in ("qdq", "qdq_fallback", "dot", "packed_dot"):
            for spec_str in (ev.spec_a, ev.spec_b):
                spec = QuantSpec.from_str(spec_str)
                if not spec.is_passthrough:
                    allowed_markers.add((ev.role, qdq_scope_name(spec)))

    calls = graph.get("pallas_calls", {})
    for role, n_cells in pallas_cells.items():
        n_calls = calls.get(role, 0)
        if n_calls < n_cells:
            findings.append(Finding(
                "kernel_presence", "violation", f"qrole_{role}",
                f"census routes {n_cells} cell(s) through pallas but the "
                f"jaxpr stages only {n_calls} pallas_call(s)"))
    for role in calls:
        if role != "-" and role not in pallas_cells:
            findings.append(Finding(
                "kernel_presence", "violation", f"qrole_{role}",
                "pallas_call in the graph with no pallas-routed census "
                "cell for that role"))

    for key, count in graph.get("qdq_markers", {}).items():
        role, marker = key.split("/", 1)
        if role == "-":
            continue  # codec outside matmul roles (KV cache, serving)
        if (role, marker) not in allowed_markers:
            findings.append(Finding(
                "role_safety", "violation", f"qrole_{role}/{marker}",
                f"quantize op ({count}x) under qrole_{role} that no "
                "census cell sanctions — quantize fed into a protected "
                "path?"))

    for msg in graph.get("f32_kernel_operands", []):
        findings.append(Finding(
            "role_safety", "violation", msg.split(":")[0],
            "operand wider than the compute dtype reaches a kernel-routed "
            "matmul: " + msg))
    return findings


# ---------------------------------------------------------------------------
# HLO-level checks (kernel evidence + comms)
# ---------------------------------------------------------------------------

def hlo_role_ops(hlo_text: str) -> Dict[str, int]:
    """ops-per-role census of ``qrole_*`` markers surviving into the
    compiled per-device HLO (kernel-presence evidence after fusion)."""
    counts = Counter()
    for op in walk_hlo(hlo_text):
        opn = op.op_name
        if not opn:
            continue
        for role in _QROLE_RE.findall(opn):
            counts[role] += 1
    return dict(counts)


def audit_hlo_comms(hlo_text: str, *, expect_fp8: bool) -> Tuple[
        Dict[str, Any], List[Finding]]:
    """Gradient all-reduce payload audit over the compiled HLO.

    ``expect_fp8``: the step was built with ``grad_compression='fp8'`` and
    a data axis > 1, so every gradient-payload all-reduce inside the
    ``collective`` graph span must carry an fp8-class payload
    (``f8e4m3fn``, or ``f16`` — its XLA:CPU legalization); a bf16/f32
    payload there means the gradient bytes went uncompressed.  The fp8
    compressor also emits one tiny f32 amax reduction per gradient leaf
    (the shared-scale ``reduce_max`` collectives); those are scale
    metadata, not payload, and are censused separately rather than
    flagged.  Returns (census, findings); the census also carries the
    shared walker's per-dtype byte counts.
    """
    findings: List[Finding] = []
    grad_ars: List[Tuple[str, str]] = []
    scale_ars: List[Tuple[str, str]] = []
    for op in walk_hlo(hlo_text):
        if op.base != "all-reduce" or op.variant == "-done":
            continue
        shape = op.payload_shape()
        dtype = shape[0] if shape else "?"
        opn = op.op_name or ""
        if "collective" not in opn:
            continue
        nbytes = shape_bytes(*shape) if shape else 0
        if "reduce_max" in opn or nbytes <= _SCALE_AR_BYTES:
            scale_ars.append((dtype, opn))
        else:
            grad_ars.append((dtype, opn))
    census = {"grad_allreduce_dtypes":
              dict(Counter(d for d, _ in grad_ars)),
              "scale_allreduce_dtypes":
              dict(Counter(d for d, _ in scale_ars)),
              "bytes": {k: v for k, v in collective_bytes(hlo_text).items()
                        if k.startswith("raw_all-reduce")}}
    if expect_fp8:
        if not grad_ars:
            findings.append(Finding(
                "comms", "violation", "all-reduce",
                "fp8 gradient compression expected but no payload "
                "all-reduce in the 'collective' span"))
        for dtype, opn in grad_ars:
            if dtype not in _FP8_WIRE_DTYPES:
                findings.append(Finding(
                    "comms", "violation", opn[:80],
                    f"gradient all-reduce payload is {dtype}, not the "
                    f"compressed fp8 wire dtype "
                    f"({sorted(_FP8_WIRE_DTYPES)})"))
    return census, findings


def audit_scale_placement(plan: PrecisionPlan) -> List[Finding]:
    """The PR-6 quant-scale placement policy, checked against the resolved
    plan: block/tile scale grids must shard WITH their operand's reduction
    axis (the per-128-group count inherits the reduction dim's logical
    name), token/tensor scales must collapse/replicate it.  Catches policy
    -table drift for exactly the granularities the plan actually uses.
    """
    findings = []
    grans = set()
    for i in range(plan.n_layers):
        for cls in ("attn", "ffn"):
            mm = plan.layer(i).for_class(cls)
            for role in _TRAIN_ROLES:
                for spec in _role_specs(mm, role):
                    if not spec.is_passthrough:
                        grans.add(spec.granularity)
    for gran in sorted(grans):
        for red_axis, red_name in ((1, "col"), (0, "row")):
            logical = scale_logical_axes(gran, red_axis, ("row", "col"))
            with_red = red_name in logical
            if gran in ("block", "tile") and not with_red:
                findings.append(Finding(
                    "comms", "violation", f"scale[{gran}]",
                    f"{gran} scales no longer shard with the reduction "
                    f"axis (axis {red_axis} -> {logical})"))
            if gran in ("token", "tensor") and with_red:
                findings.append(Finding(
                    "comms", "violation", f"scale[{gran}]",
                    f"{gran} scales must replicate along the reduction "
                    f"axis but got {logical}"))
    return findings


# ---------------------------------------------------------------------------
# Recompile budget
# ---------------------------------------------------------------------------

def _plan_fingerprint(plan) -> str:
    blob = json.dumps(plan.to_dict(), sort_keys=True).encode()
    return hashlib.md5(blob).hexdigest()[:10]


def recompile_census(trainer, extra_plans: Sequence[PrecisionPlan] = ()
                     ) -> Tuple[Dict[str, Any], List[Finding]]:
    """Cache-key census over the trainer's compiled step graphs.

    Expected plan set: the stage-1 plan, the schedule's stage-2 target,
    every plan the controller has materialized, plus ``extra_plans``.
    Keys are content-addressed ``(plan, telemetry)`` tuples, so a key
    whose plan is outside that set — or more compiled graphs than
    |plans| x |telemetry variants| — is an unexpected retrace.
    """
    findings: List[Finding] = []
    target = trainer.schedule.target_plan
    if callable(target):
        target = target()
    expected = {_plan_fingerprint(trainer.plan), _plan_fingerprint(target)}
    if trainer.controller is not None:
        cache = getattr(trainer.controller, "_plan_cache", {})
        expected |= {_plan_fingerprint(p) for p in cache.values()}
    expected |= {_plan_fingerprint(p) for p in extra_plans}
    observed = [(_plan_fingerprint(plan), tel)
                for (plan, tel) in trainer._steps]
    tel_variants = {tel for _, tel in observed}
    budget = len(expected) * max(1, len(tel_variants))
    for fp, tel in observed:
        if fp not in expected:
            findings.append(Finding(
                "recompile", "violation", f"step[{fp},tel={tel}]",
                "compiled step graph for a plan outside the expected set "
                "(unexpected retrace)"))
    if len(observed) > budget:
        findings.append(Finding(
            "recompile", "violation", "steps",
            f"{len(observed)} compiled step graphs exceed the budget of "
            f"{budget} ({len(expected)} plan(s) x "
            f"{max(1, len(tel_variants))} telemetry variant(s))"))
    census = {"n_compiled": len(observed),
              "budget": budget,
              "keys": [f"{fp}:tel={tel}" for fp, tel in observed]}
    return census, findings


# ---------------------------------------------------------------------------
# Graph drivers
# ---------------------------------------------------------------------------

def _synth_batch(cfg, batch: int, seq: int) -> Dict[str, jnp.ndarray]:
    toks = jax.random.randint(jax.random.PRNGKey(0), (batch, seq), 0,
                              cfg.vocab_size)
    return {"tokens": toks, "targets": toks}


def _finish_report(report: QlintReport, log: routing.RoutingLog,
                   plan: PrecisionPlan, impl: str, graph: Dict[str, Any],
                   *, roles=_TRAIN_ROLES, packed=False,
                   hlo_text: Optional[str] = None,
                   expect_fp8: bool = False) -> QlintReport:
    cells = log.cells()
    report.cells = [ev.to_dict() for ev in cells]
    report.extend(audit_cells(cells, plan, impl, roles=roles,
                              packed=packed))
    report.extend(audit_graph_vs_census(graph, cells))
    report.extend(audit_scale_placement(plan))
    report.summary = {
        "n_cells": len(cells),
        "n_fallback_cells": len(log.fallbacks()),
        "pallas_calls": graph["pallas_calls"],
        "qdq_markers": graph["qdq_markers"],
        "n_eqns": graph["n_eqns"],
    }
    if hlo_text is not None:
        role_ops = hlo_role_ops(hlo_text)
        report.summary["hlo_role_ops"] = role_ops
        pallas_roles = {ev.role for ev in cells if ev.route == "pallas"}
        for role in sorted(pallas_roles - set(role_ops)):
            report.add(Finding(
                "kernel_presence", "violation", f"hlo/qrole_{role}",
                "no op with this role's scope marker survives into the "
                "per-device HLO"))
        comms, findings = audit_hlo_comms(hlo_text, expect_fp8=expect_fp8)
        report.summary["comms"] = comms
        report.extend(findings)
    return report


def audit_train_graph(cfg, tcfg, *, label: str = "train",
                      batch: Optional[int] = None,
                      seq: Optional[int] = None,
                      compile_hlo: bool = True,
                      plan: Optional[PrecisionPlan] = None) -> QlintReport:
    """Trace one jitted train step (no execution) and audit it.

    ``plan`` overrides the trainer-resolved plan as the AUDIT REFERENCE
    only — the traced graph still runs the trainer's plan.  That is the
    seeded-violation hook: trace plan B, audit against plan A, and the
    role-safety checks must fire.
    """
    from repro.models import build_model
    from repro.train.trainer import Trainer

    model = build_model(cfg)
    trainer = Trainer(model, tcfg, pipeline=None, jit=True)
    audit_plan = plan if plan is not None else trainer.plan
    state = trainer.init_state()
    b = _synth_batch(cfg, batch or tcfg.global_batch, seq or tcfg.seq_len)
    step = trainer._step_fn(trainer.plan)
    args = (state.params, state.opt_state, state.comp_state, b,
            jnp.zeros((), jnp.int32), jnp.ones((), jnp.float32))
    report = QlintReport(label)
    with routing.capture() as log:
        jaxpr = jax.make_jaxpr(step)(*args)
        hlo_text = None
        if compile_hlo:
            hlo_text = step.lower(*args).compile().as_text()
    graph = graph_census(jaxpr, jnp.dtype(cfg.dtype))
    dp = trainer.rules.dp_size if trainer.rules is not None else 1
    expect_fp8 = tcfg.grad_compression == "fp8" and dp > 1
    _finish_report(report, log, audit_plan, cfg.linear_impl, graph,
                   hlo_text=hlo_text, expect_fp8=expect_fp8)
    census, findings = recompile_census(trainer)
    report.summary["recompile"] = census
    report.extend(findings)
    return report


def audit_decode_graph(cfg, recipe, *, label: str = "decode",
                       n_slots: int = 2, max_len: int = 64,
                       kv_format: Optional[str] = "fp8_e4m3",
                       fmt: str = "fp4_e2m1",
                       compile_hlo: bool = True) -> QlintReport:
    """Build a packed-weight :class:`DecodeEngine` and audit its batched
    generate-step graph (quantize-once panels -> ``packed_dot``/fused
    activation-quant routes; forward role only)."""
    from repro.models import build_model
    from repro.train.serving_runtime import (DecodeEngine,
                                             quantize_weights_for_serving)

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qparams = quantize_weights_for_serving(model, params, fmt, packed=True)
    engine = DecodeEngine(model, qparams, n_slots=n_slots, max_len=max_len,
                          recipe=recipe, kv_format=kv_format, jit=True)
    return audit_decode_engine(engine, label=label, compile_hlo=compile_hlo)


def audit_decode_engine(engine, *, label: str = "decode",
                        compile_hlo: bool = True) -> QlintReport:
    """Audit an existing engine's generate-step graph (its ``qlint_report``
    hook).  Fwd-only: the serving path has no backward matmuls."""
    cfg = engine.model.cfg
    plan = PrecisionPlan.uniform(engine.recipe, cfg.n_layers)
    toks = jnp.zeros((engine.n_slots, 1), jnp.int32)
    live = jnp.zeros((engine.n_slots,), bool)
    args = (engine.params, engine.cache, toks, live)
    report = QlintReport(label)
    packed = any(type(p).__name__ == "PackedTensor"
                 for p in jax.tree.leaves(
                     engine.params,
                     is_leaf=lambda x: type(x).__name__ == "PackedTensor"))
    with routing.capture() as log:
        jaxpr = jax.make_jaxpr(engine._generate_impl)(*args)
        hlo_text = None
        if compile_hlo:
            hlo_text = (jax.jit(engine._generate_impl).lower(*args)
                        .compile().as_text())
    graph = graph_census(jaxpr, jnp.dtype(cfg.dtype))
    return _finish_report(report, log, plan, cfg.linear_impl, graph,
                          roles=("fwd",), packed=packed,
                          hlo_text=hlo_text, expect_fp8=False)


def audit_trainer(trainer, *, label: str = "trainer",
                  compile_hlo: bool = False) -> QlintReport:
    """The :meth:`Trainer.qlint_report` backend: audit the trainer's
    ACTIVE plan's step graph plus the recompile-budget census over every
    step graph the trainer has compiled so far."""
    cfg = trainer.model.cfg
    tcfg = trainer.tcfg
    b = _synth_batch(cfg, tcfg.global_batch, tcfg.seq_len)
    state = trainer.init_state()
    step = trainer._step_fn(trainer.plan)
    args = (state.params, state.opt_state, state.comp_state, b,
            jnp.zeros((), jnp.int32), jnp.ones((), jnp.float32))
    report = QlintReport(label)
    with routing.capture() as log:
        jaxpr = jax.make_jaxpr(step)(*args)
        hlo_text = (step.lower(*args).compile().as_text()
                    if compile_hlo else None)
    graph = graph_census(jaxpr, jnp.dtype(cfg.dtype))
    dp = trainer.rules.dp_size if trainer.rules is not None else 1
    expect_fp8 = tcfg.grad_compression == "fp8" and dp > 1
    _finish_report(report, log, trainer.plan, cfg.linear_impl, graph,
                   hlo_text=hlo_text, expect_fp8=expect_fp8)
    census, findings = recompile_census(trainer)
    report.summary["recompile"] = census
    report.extend(findings)
    return report


# ---------------------------------------------------------------------------
# Expectations (CI gate)
# ---------------------------------------------------------------------------

def expectations_payload(reports: Sequence[QlintReport]) -> Dict[str, Any]:
    """The normalized, diff-stable subset committed as the CI gate: the
    deduped cell census plus marker counts per graph, and the global
    violation/fallback totals (which the gate requires to be zero)."""
    out: Dict[str, Any] = {"version": 1, "graphs": {}}
    for r in reports:
        cells = sorted(
            ({k: v for k, v in c.items()} for c in r.cells),
            key=lambda c: (c["layer"] or "", c["cls"] or "", c["role"],
                           c["route"]))
        out["graphs"][r.label] = {
            "cells": cells,
            "pallas_calls": r.summary.get("pallas_calls", {}),
            "qdq_markers": r.summary.get("qdq_markers", {}),
            "n_violations": len(r.violations()),
            "n_fallbacks": len(r.fallbacks()),
        }
    out["n_violations"] = sum(len(r.violations()) for r in reports)
    out["n_fallbacks"] = sum(len(r.fallbacks()) for r in reports)
    return out


def compare_expectations(payload: Dict[str, Any],
                         expected: Dict[str, Any]) -> List[str]:
    """Differences between the current audit and the committed
    expectations, as human-readable strings (empty = gate passes)."""
    diffs: List[str] = []
    for key in ("n_violations", "n_fallbacks"):
        if payload.get(key) != expected.get(key):
            diffs.append(f"{key}: expected {expected.get(key)}, got "
                         f"{payload.get(key)}")
    exp_graphs = expected.get("graphs", {})
    got_graphs = payload.get("graphs", {})
    for label in sorted(set(exp_graphs) | set(got_graphs)):
        if label not in got_graphs:
            diffs.append(f"graph {label!r}: missing from this audit")
            continue
        if label not in exp_graphs:
            diffs.append(f"graph {label!r}: not in the expectations file "
                         "(run --update-expectations)")
            continue
        e, g = exp_graphs[label], got_graphs[label]
        for key in ("cells", "pallas_calls", "qdq_markers",
                    "n_violations", "n_fallbacks"):
            if e.get(key) != g.get(key):
                diffs.append(f"graph {label!r}: {key} drifted\n"
                             f"    expected: {json.dumps(e.get(key))[:400]}\n"
                             f"    got:      {json.dumps(g.get(key))[:400]}")
    return diffs


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _parse_mesh(s: Optional[str]) -> Optional[Tuple[int, ...]]:
    if not s:
        return None
    return tuple(int(p) for p in s.split(","))


def build_reports(config: str, plan_name: str, *, impl: str = "pallas",
                  mesh: Optional[Tuple[int, ...]] = None,
                  decode: bool = False, seq: int = 32, batch: int = 4,
                  compile_hlo: bool = True) -> List[QlintReport]:
    """The CLI's graph family: unrolled + scanned train steps, optionally
    a data-sharded step with fp8 gradient comms, optionally the packed
    decode graph."""
    from repro.configs.base import TrainConfig, get_config
    from repro.core.recipe import RECIPES

    base = get_config(config).replace(linear_impl=impl)
    tcfg = TrainConfig(recipe=plan_name, total_steps=8, global_batch=batch,
                       seq_len=seq)
    reports = [
        audit_train_graph(base.replace(scan_layers=False), tcfg,
                          label="train_unroll", compile_hlo=compile_hlo),
        audit_train_graph(base.replace(scan_layers=True), tcfg,
                          label="train_scan", compile_hlo=compile_hlo),
    ]
    if mesh is not None:
        import numpy as np
        need = int(np.prod(mesh))
        have = len(jax.devices())
        if have < need:
            raise SystemExit(
                f"--mesh {mesh} needs {need} devices but only {have} are "
                f"visible; on CPU set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
        dp = mesh[0]
        tcfg_m = dataclasses.replace(
            tcfg, mesh_shape=mesh, fsdp=False,
            grad_compression="fp8" if dp > 1 else "none")
        reports.append(audit_train_graph(
            base.replace(scan_layers=True), tcfg_m,
            label=f"train_mesh{'x'.join(map(str, mesh))}",
            compile_hlo=compile_hlo))
    if decode:
        reports.append(audit_decode_graph(
            base, RECIPES[plan_name], label="decode_packed",
            compile_hlo=compile_hlo))
    return reports


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.analysis.qlint",
        description="Static precision-flow audit of compiled step graphs")
    ap.add_argument("--config", default="tiny")
    ap.add_argument("--plan", default="fine_grained_fp4",
                    help="recipe name (core.recipe.RECIPES)")
    ap.add_argument("--impl", default="pallas",
                    choices=["qdq", "pallas", "pallas_two_pass"])
    ap.add_argument("--mesh", default=None,
                    help="comma mesh shape, e.g. 2,1 (data,model); adds a "
                         "sharded train graph with fp8 gradient comms")
    ap.add_argument("--decode", action="store_true",
                    help="also audit the packed-weight decode graph")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip compile; jaxpr-level checks only")
    ap.add_argument("--json", default=None,
                    help="write the full findings JSON here")
    ap.add_argument("--expect", default=None,
                    help="expectations JSON to gate against")
    ap.add_argument("--update-expectations", action="store_true",
                    help="rewrite --expect from this audit instead of "
                         "gating")
    args = ap.parse_args(argv)

    reports = build_reports(args.config, args.plan, impl=args.impl,
                            mesh=_parse_mesh(args.mesh), decode=args.decode,
                            seq=args.seq, batch=args.batch,
                            compile_hlo=not args.no_hlo)

    for r in reports:
        print(r.human_report())
        print()

    n_viol = sum(len(r.violations()) for r in reports)
    n_fall = sum(len(r.fallbacks()) for r in reports)
    print(f"qlint: {len(reports)} graph(s), {n_viol} violation(s), "
          f"{n_fall} fallback(s)")

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"reports": [r.to_dict() for r in reports]}, f,
                      indent=1, sort_keys=True)
        print(f"qlint: findings JSON -> {args.json}")

    payload = expectations_payload(reports)
    if args.expect:
        if args.update_expectations:
            with open(args.expect, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
                f.write("\n")
            print(f"qlint: expectations updated -> {args.expect}")
        else:
            with open(args.expect) as f:
                expected = json.load(f)
            diffs = compare_expectations(payload, expected)
            for d in diffs:
                print(f"qlint: EXPECTATION DRIFT: {d}")
            if diffs:
                return 2
            print("qlint: expectations match")
    return 1 if n_viol else 0


if __name__ == "__main__":
    sys.exit(main())
