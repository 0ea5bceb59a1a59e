"""Device time by layer scope, and the loop's ``dispatch`` and ``wait``
spans, from a profiler trace of the window.

The program names its layers in the step graph with ``jax.named_scope``:
``embed``, ``attn``, ``mlp``, ``norm`` and ``head`` in the model, ``optim``
(clipping and the update) in the step.  The names reach each HLO
instruction's ``metadata={op_name=...}`` and not the trace, whose device
events carry the instruction's text alone (``bench/trace.py``).  So, after
the window, ``step_scope_map`` compiles the trainer's step again from
abstract shapes, as the window ran it and afresh (``step_hlo`` says why
both), and maps each instruction's name to the innermost layer scope of
its ``op_name`` (``scope_map`` says what an instruction without one
takes).
``reduce_profile`` then splits each chip's device time: ops of the step's
``XLA Modules`` event by scope (``unscoped`` where the map has none),
ops outside it as ``other_modules``.

``window(ctx)`` is what a metric reader calls.  ``bench/run.py`` hands a
reader the reduced trace alone, so ``window`` finds the trace's directory
and the cell in the frame of ``run.read_trace`` that called the reader,
reduces once per trace, prints the scope table on standard error and
returns the result.  A program without the scopes or the spans (one
older than them) reads as nothing: no scope time, no spans.
"""
from __future__ import annotations

import collections
import os
import re
import sys
import traceback
from typing import Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

__all__ = ["LAYER_SCOPES", "innermost_scope", "scope_map", "step_hlo",
           "aligned_scope_map", "step_scope_map", "reduce_profile",
           "reduce_dir", "mean_scope_s", "window"]

LAYER_SCOPES = ("embed", "attn", "mlp", "norm", "head", "optim")
SPANS = ("dispatch", "wait")
_HEAVY = ("dot", "convolution", "custom-call")
_PART = re.compile(r"(?:[\w\-]+\()*([\w\-.]*)\)*")
_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_LINE = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = .*?\b([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_METADATA = re.compile(r", metadata=\{[^}]*\}")
_FIRST_COMP = re.compile(r"^(?:ENTRY )?%", re.M)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def innermost_scope(op_name: str) -> Optional[str]:
    """The last layer scope on an ``op_name`` path, seen through the
    transforms JAX wraps around a scope (``transpose(jvp(head))``)."""
    for part in reversed(op_name.split("/")):
        m = _PART.fullmatch(part)
        if m and m.group(1) in LAYER_SCOPES:
            return m.group(1)
    return None


def _operands(line: str, start: int) -> List[str]:
    """Names of the instructions in the operand list that opens at
    ``line[start]``."""
    depth = 0
    for i in range(start, len(line)):
        depth += line[i] == "("
        depth -= line[i] == ")"
        if depth == 0:
            return _REF.findall(line, start, i)
    return _REF.findall(line, start)


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> layer scope, over every computation of a
    compiled module's text (``compiled.as_text()``).  An instruction takes
    the innermost layer scope of its own ``op_name``; failing that, a
    fusion takes its matmuls' scope or that of most instructions it fuses;
    failing that, an instruction whose users all have one scope takes it
    (the copies and slices the compiler adds carry no ``op_name`` and
    exist for their users).  Instructions with none are left out."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    users: Dict[str, List[str]] = collections.defaultdict(list)
    body: Dict[str, List[Tuple[str, str]]] = collections.defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = _HEADER.match(line)
            comp = m.group(1) if m else None
            continue
        m = _LINE.match(line)
        if m is None:
            continue
        name, opcode = m.groups()
        op = _OP_NAME.search(line)
        own[name] = innermost_scope(op.group(1)) if op else None
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        for ref in _operands(line, m.end() - 1):
            users[ref].append(name)
        if comp is not None:
            body[comp].append((name, opcode))

    def fused_scope(comp_name: str, seen: frozenset) -> Optional[str]:
        heavy, light = collections.Counter(), collections.Counter()
        for name, opcode in body.get(comp_name, ()):
            s = own.get(name)
            if s is None and name in calls and calls[name] not in seen:
                s = fused_scope(calls[name], seen | {calls[name]})
            if s is not None:
                (heavy if opcode in _HEAVY else light)[s] += 1
        best = heavy or light
        return best.most_common(1)[0][0] if best else None

    out = {}
    for name, s in own.items():
        if s is None and name in calls:
            s = fused_scope(calls[name], frozenset([calls[name]]))
        if s is not None:
            out[name] = s
    changed = True
    while changed:
        changed = False
        for name in own:
            if name in out or not users.get(name):
                continue
            found = {out.get(u) for u in users[name]}
            if len(found) == 1 and None not in found:
                out[name] = found.pop()
                changed = True
    return out


def step_hlo(cell) -> Tuple[str, str]:
    """The compiled text of the step the cell's window runs, compiled again
    from abstract shapes of the window's arguments: as the window ran it
    (JAX's persistent cache, where on, serves the same executable), and
    afresh.  JAX leaves source metadata out of the cache's key, so a cached
    executable carries the ``op_name``s of whichever program first compiled
    it, and a few of its instruction names can differ from a fresh
    compile's (reshapes are numbered by their metadata)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    import program
    import weights

    def lower():
        # a new Program jits the step anew: a lowering, once compiled,
        # keeps its executable, and JAX caches lowerings by function
        prog = program.Program(cell)
        tr = prog.trainer
        fn = tr._step_fn(tr._active_plan(program.CHECKED_STEPS))
        sds = jax.ShapeDtypeStruct
        params = jax.eval_shape(prog._init, weights.seed_key(0))
        opt = jax.eval_shape(prog._opt_init, params)
        comp = sds((), jnp.float32, sharding=prog.c_sh)
        batch = {k: sds(v.shape, v.dtype)
                 for k, v in prog.feed(0).batch(0).items()}
        return fn.lower(params, opt, comp, batch, sds((), jnp.int32),
                        sds((), jnp.float32))

    ran = lower().compile().as_text()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        fresh = lower().compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return ran, fresh


def _names(text: str) -> List[str]:
    return [m.group(1) for m in map(_LINE.match, text.splitlines()) if m]


def _by_position(text: str, names: List[str]) -> str:
    """The computations with each instruction named by its position and
    no metadata: equal for two compiles of one program."""
    pos = {n: f"#{i}" for i, n in enumerate(names)}
    text = _METADATA.sub("", text[_FIRST_COMP.search(text).start():])
    return _REF.sub(lambda m: pos.get(m.group(1), m.group(0)), text)


def aligned_scope_map(ran: str, fresh: str) -> Dict[str, str]:
    """``scope_map`` of the fresh compile's metadata, keyed by the names of
    the executable that ran: the two are the same program, instruction for
    instruction in order, but for metadata and a few names."""
    fresh_map = scope_map(fresh)
    a, b = _names(ran), _names(fresh)
    if a == b:
        return fresh_map
    if _by_position(ran, a) != _by_position(fresh, b):
        _log("[scopes] the executable that ran differs from a fresh "
             "compile; its instructions are mapped by name")
        return fresh_map
    return {x: fresh_map[y] for x, y in zip(a, b) if y in fresh_map}


def step_scope_map(cell) -> Tuple[str, Dict[str, str]]:
    """(module name, instruction -> scope) of the cell's step, keyed by the
    names of the executable the window ran."""
    ran, fresh = step_hlo(cell)
    m = re.match(r"HloModule ([\w.\-]+)", ran)
    return (m.group(1) if m else ""), aligned_scope_map(ran, fresh)


def _window_bounds(pd, devs) -> Tuple[float, float]:
    """The traced window as ``trace.reduce_profile`` takes it: from the
    first to the last of the loop's spans, else the device events."""
    spans = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
             for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name in ("data", "step", "host")]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    ends = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for evs in devs for e in evs["ops"]]
    if not ends:
        return 0.0, 0.0
    return min(s for s, _ in ends), max(e for _, e in ends)


def _inside(intervals: List[Tuple[float, float]], t: float) -> bool:
    lo, hi = 0, len(intervals)
    while lo < hi:
        mid = (lo + hi) // 2
        if intervals[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    return lo > 0 and t < intervals[lo - 1][1]


def reduce_profile(pd, n_devices: int, smap: Optional[Dict[str, str]] = None,
                   module: str = "") -> Dict:
    """``trace.reduce_profile``'s numbers, unchanged, plus:

    * ``span_s``, ``span_n``: total seconds and count of the loop's
      ``dispatch`` and ``wait`` spans;
    * with ``smap``: ``scope_s``, per chip, device seconds of the ops by
      layer scope, ``unscoped`` and ``other_modules``; ``module_s``, per
      chip, the length of the step module's events (``module``, the
      compiled module's name); ``scope_kernel_s``, per chip, the part
      of each scope's seconds in Mosaic kernels; ``unscoped_ops``, the first
      chip's ten
      largest unscoped ops by name (as the breakdown groups them)."""
    red = _bench_trace().reduce_profile(pd, n_devices)
    span_s = {k: 0.0 for k in SPANS}
    span_n = {k: 0 for k in SPANS}
    devs = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            lines = {line.name: list(line.events) for line in plane.lines}
            devs.append((int(m.group(1)), {
                "ops": lines.get("XLA Ops", []),
                "modules": lines.get("XLA Modules", [])}))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in span_s:
                        span_s[ev.name] += ev.duration_ns * 1e-9
                        span_n[ev.name] += 1
    red["span_s"], red["span_n"] = span_s, span_n
    if smap is None:
        return red
    devs = [d for _, d in sorted(devs, key=lambda x: x[0])][:n_devices]
    tr = _bench_trace()
    w0, w1 = _window_bounds(pd, devs)
    scope_s, module_s, kernel_s = [], [], []
    unscoped: Dict[str, float] = {}
    for i, d in enumerate(devs):
        mods = sorted((ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9)
                      for ev in d["modules"]
                      if module and ev.name.split("(")[0] == module)
        module_s.append(sum(max(0.0, min(e, w1) - max(s, w0))
                            for s, e in mods))
        acc = {k: 0.0 for k in LAYER_SCOPES + ("unscoped", "other_modules")}
        kern = dict.fromkeys(acc, 0.0)
        for ev in d["ops"]:
            s = ev.start_ns * 1e-9
            e = s + ev.duration_ns * 1e-9
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            parsed = tr.parse_instr(ev.name)
            if parsed is not None and parsed[2] in tr._NESTING:
                continue    # its body's events are counted one by one
            if not _inside(mods, s):
                key = "other_modules"
            else:
                key = smap.get(parsed[0] if parsed else ev.name, "unscoped")
            acc[key] += e - s
            if tr.is_kernel(ev.name, parsed):
                kern[key] += e - s
            if key == "unscoped" and i == 0:
                op = tr._op_key(parsed, parsed[1] if parsed else ev.name,
                                ev.name)
                unscoped[op] = unscoped.get(op, 0.0) + (e - s)
        scope_s.append(acc)
        kernel_s.append(kern)
    red["scope_s"], red["module_s"] = scope_s, module_s
    red["scope_kernel_s"] = kernel_s
    red["unscoped_ops"] = sorted(unscoped.items(), key=lambda kv: -kv[1])[:10]
    return red


def _bench_trace():
    """``bench/trace.py`` by its path (the standard library has a module
    named ``trace`` too)."""
    mod = sys.modules.get("bench_trace")
    if mod is None:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_trace", os.path.join(BENCH_DIR, "trace.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules["bench_trace"] = mod
    return mod


def reduce_dir(trace_dir: str, n_devices: int,
               smap: Optional[Dict[str, str]] = None, module: str = ""):
    from jax.profiler import ProfileData
    tr = _bench_trace()
    return reduce_profile(ProfileData.from_file(tr.find_xplane(trace_dir)),
                          n_devices, smap, module)


def mean_scope_s(red: Dict, key: str = "scope_s") -> Dict[str, float]:
    """Device seconds by scope (of ``key``), averaged over the chips."""
    per = red.get(key) or []
    if not per:
        return {}
    return {k: sum(d[k] for d in per) / len(per) for k in per[0]}


def print_table(red: Dict, steps: int) -> None:
    n = red["span_n"]
    for k in SPANS:
        if n[k]:
            _log(f"[scopes] {k}: {1e3 * red['span_s'][k] / n[k]:.3f} ms a "
                 f"span, {n[k]} spans")
    sc = mean_scope_s(red)
    if not sc:
        return
    in_module = sum(v for k, v in sc.items() if k != "other_modules")
    mod = sum(red["module_s"]) / max(1, len(red["module_s"]))
    _log(f"[scopes] step module: {1e3 * mod / steps:.3f} ms a step of "
         f"module events, {1e3 * in_module / steps:.3f} ms of ops in it")
    kern = mean_scope_s(red, "scope_kernel_s")
    for k, v in sorted(sc.items(), key=lambda kv: -kv[1]):
        share = 100.0 * v / in_module if in_module > 0 else 0.0
        _log(f"[scopes] {k:>14}: {1e3 * v / steps:10.3f} ms a step"
             + (f", {share:6.2f}% of the step module's ops"
                if k != "other_modules" else "")
             + f"; Mosaic kernels {1e3 * kern[k] / steps:.3f} ms")
    _log("[scopes] largest unscoped (ms a step): " + ", ".join(
        f"{k} {1e3 * v / steps:.3f}" for k, v in red["unscoped_ops"]))


def _caller() -> Optional[Dict]:
    """The locals of ``run.read_trace`` up the stack, if it is there."""
    f = sys._getframe(1)
    while f is not None:
        if f.f_code.co_name == "read_trace" and "trace_dir" in f.f_locals:
            return f.f_locals
        f = f.f_back
    return None


_DONE: Dict[str, Optional[Dict]] = {}


def window(ctx) -> Optional[Dict]:
    """The scoped reduction of the traced window the reader's ``ctx``
    belongs to (``ctx["scoped"]`` where the caller made it), made once per
    trace; None where no trace is found."""
    if "scoped" in ctx:
        return ctx["scoped"]
    loc = _caller()
    if loc is None:
        return None
    trace_dir = loc["trace_dir"]
    if trace_dir in _DONE:
        return _DONE[trace_dir]
    module, smap = "", None
    try:
        module, smap = step_scope_map(loc["cell"])
    except Exception:   # the spans are still read without the map
        _log("[scopes] no op -> scope map:\n" + traceback.format_exc())
    red = reduce_dir(trace_dir, len(loc["devices"]), smap, module)
    print_table(red, ctx["steps"])
    _DONE[trace_dir] = red
    return red


def main(argv=None) -> int:
    """Write the op -> scope map of a cell's step (default: the small model
    of ``record_trace.py``) as JSON, for a trace recorded beside it.  Run
    it where the trace was recorded: instruction names are the chip
    compiler's."""
    import argparse
    import json

    import cells
    import run
    ap = argparse.ArgumentParser(description=main.__doc__.splitlines()[0])
    ap.add_argument("--workload", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    run.enable_compile_cache()
    if args.workload:
        cell = cells.load_cell(args.workload)
    else:
        import record_trace
        cell = record_trace.SMALL
    module, smap = step_scope_map(cell)
    with open(args.out, "w") as f:
        json.dump({"module": module, "scopes": smap}, f, indent=0,
                  sort_keys=True)
    _log(f"[scopes] {len(smap)} instructions of {module}: "
         f"{dict(collections.Counter(smap.values()))}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
