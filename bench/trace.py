"""From a profiler trace of the window to the numbers the metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX's own
``ProfileData``.  What it relies on, read by hand from a trace of a v5e
chip (see ``PERF.md``):

* each chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
  event per operation executed on the chip's core, one at a time;
* an event's name is the whole HLO instruction, ``%<op>.<n> = <output
  shapes> <opcode>(<operand shapes and names>), ...``; device times are on
  the host's clock, so a step's ops fall inside its host ``step`` span;
* ``while`` (a ``lax.scan``) and ``conditional`` events enclose the events
  of their bodies;
* a Pallas (Mosaic) kernel is an operation with
  ``custom_call_target="tpu_custom_call"``, or a ``kind=kCustom`` fusion
  of such a call with its operands' slicing; the program's kernels are
  named ``_fused_qmm`` (``kernels/fp4_matmul.py``) in both forms;
* collectives are the operations named ``all-gather``, ``all-reduce``,
  ``reduce-scatter``, ``collective-permute`` or ``all-to-all`` (and their
  ``-start``/``-done`` halves); time inside them is time the core waits;
* the host plane ``/host:CPU`` holds the training loop's spans ``data``,
  ``step`` and ``host`` (``repro.telemetry.profiler.phase_span``).

``bench/record_trace.py`` prints what a trace holds.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

__all__ = ["find_xplane", "reduce_dir", "reduce_profile", "union_length",
           "KERNEL_MARK"]

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
KERNEL_NAMES = ("_fused_qmm",)
_NESTING = ("while", "conditional", "call")
_INSTR = re.compile(r"^%([^ ]+) = (.*?) ([a-z][a-z0-9-]*)\((.*)$", re.S)
_COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")
_HOST_SPANS = ("data", "step", "host")
_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 0.5,
                "u4": 0.5, "f64": 8, "s64": 8, "u64": 8, "s16": 2, "u16": 2}
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union_length(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _stats(ev) -> Dict:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


def parse_instr(name: str):
    """(op, base name, opcode, output shapes, operand shapes) of one
    event name, or None where it is no HLO instruction.  Shapes are
    ``(bytes per element, dims)``."""
    m = _INSTR.match(name)
    if not m:
        return None
    op, out, opcode, rest = m.groups()
    depth, cut = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            cut = i
            break
    return (op, re.sub(r"\.\d+$", "", op), opcode, _parse_shapes(out),
            _parse_shapes(rest[:cut]))


def _parse_shapes(s: str):
    return [(_DTYPE_BYTES[d], tuple(int(x) for x in dims.split(",") if x))
            for d, dims in _SHAPE.findall(s)]


def _op_key(parsed, base: str, name: str) -> str:
    """What the breakdown groups device time by: the op's name without
    its numbers and rematerialization marks, and a fusion's kind
    (``kLoop`` elementwise, ``kInput`` reductions, ``kOutput`` matmul and
    convolution epilogues, ``kCustom``)."""
    key = re.sub(r"\.(\d+|remat\d*|clone)(?=\.|$)", "", base)
    key = re.sub(r"\.(\d+|remat\d*|clone)(?=\.|$)", "", key)
    if parsed is not None and parsed[2] == "fusion":
        m = re.search(r"kind=(k[A-Za-z]+)", name)
        if m:
            key += f" ({m.group(1)})"
    return key


def is_kernel(name: str, parsed) -> bool:
    return KERNEL_MARK in name or (parsed is not None
                                   and parsed[1] in KERNEL_NAMES)


def reduce_profile(pd, n_devices: int) -> Dict:
    """The window's numbers from a ``ProfileData``."""
    devs = []
    host = {k: [] for k in _HOST_SPANS}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devs.append((int(m.group(1)), list(line.events)))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host:
                        host[ev.name].append(
                            (ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
    devs = [evs for _, evs in sorted(devs)][:n_devices]
    spans = sorted(s for v in host.values() for s in v)
    if spans:
        w0, w1 = spans[0][0], max(e for _, e in spans)
    else:
        starts = [e.start_ns * 1e-9 for evs in devs for e in evs]
        ends = [(e.start_ns + e.duration_ns) * 1e-9 for evs in devs
                for e in evs]
        w0, w1 = (min(starts), max(ends)) if starts else (0.0, 0.0)
    window = w1 - w0

    per_dev = []
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for i, evs in enumerate(devs):
        iv, kernels, coll = [], [], 0.0
        for ev in evs:
            s = ev.start_ns * 1e-9
            e = s + ev.duration_ns * 1e-9
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            iv.append((s, e))
            parsed = parse_instr(ev.name)
            base = parsed[1] if parsed else ev.name
            if parsed is not None and parsed[2] in _NESTING:
                continue    # its body's events are counted one by one
            if i == 0:
                key = _op_key(parsed, base, ev.name)
                op_time[key] = op_time.get(key, 0.0) + (e - s)
            if _COLLECTIVE.match(base):
                coll += e - s
            if is_kernel(ev.name, parsed):
                kernels.append({"name": parsed[0] if parsed else ev.name,
                                "seconds": e - s,
                                "outputs": parsed[3] if parsed else None,
                                "operands": parsed[4] if parsed else None})
        per_dev.append({"busy_s": union_length(iv), "kernels": kernels,
                        "collective_s": coll})
        if i == 0:
            gaps = _idle_gaps(iv, host, w0, w1)
    busy = (sum(d["busy_s"] for d in per_dev) / len(per_dev)
            if per_dev else 0.0)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window,
        "busy_s": busy,
        "devices": per_dev,
        "host_s": {k: sum(e - s for s, e in v) for k, v in host.items()},
        "host_n": {k: len(v) for k, v in host.items()},
        "breakdown": {"device_ops": [[k, v] for k, v in top],
                      "idle_gaps": [[k, v] for k, v in gaps[:10]]},
    }


def _idle_gaps(iv, host, w0, w1):
    """Idle stretches of one chip, longest first, each cut where the host
    passed from one span of the loop to the next and named by the span
    the host was in (``outside`` between spans)."""
    gaps, end = [], w0
    for s, e in sorted(iv):
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if w1 > end:
        gaps.append((end, w1))
    spans = sorted((a, b, k) for k, v in host.items() for a, b in v)
    out = []
    for s, e in gaps:
        t = s
        for a, b, k in spans:
            if b <= t or a >= e:
                continue
            if a > t:
                out.append(("outside", a - t))
            out.append((k, min(b, e) - max(a, t)))
            t = min(b, e)
        if e > t:
            out.append(("outside", e - t))
    return sorted(out, key=lambda x: -x[1])


def reduce_dir(trace_dir: str, n_devices: int) -> Dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)),
                          n_devices)
