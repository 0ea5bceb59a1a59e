"""What a traced window of a mesh cell holds beyond ``bench/trace.py``'s
reduction: each chip's collective time that no other operation overlaps.

``bench/run.py`` hands a metric reader the reduced trace alone, so, as
``scopes.window`` does, ``window(ctx)`` finds the trace's directory and
its devices in the frame of ``run.read_trace`` that called the reader,
reduces once per trace, prints a ``[collectives]`` line on standard error
and returns the result.

Collectives are the operations ``bench/trace.py`` counts in
``collective_s`` (``all-gather``, ``all-reduce``, ``reduce-scatter``,
``collective-permute``, ``all-to-all``, their ``-start``/``-done`` halves)
and the ``async-collective-start``/``-done`` fusions XLA:TPU makes of the
FSDP gathers.  A collective's time is exposed where no other operation of
the same chip's ``XLA Ops`` line runs (``while``/``conditional``/``call``
events enclose their bodies and are not counted as running).  A trace with
no collective reads as no exposed time.
"""
from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import scopes  # noqa: E402

__all__ = ["is_collective", "exposed_s", "reduce_profile", "window"]

_COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|async-collective)")


def is_collective(base: str) -> bool:
    return bool(_COLLECTIVE.match(base))


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def exposed_s(coll: List[Tuple[float, float]],
              other: List[Tuple[float, float]]) -> float:
    """Length of the union of ``coll`` outside the union of ``other``."""
    a, b = _merge(coll), _merge(other)
    total = sum(e - s for s, e in a)
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total -= min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def reduce_profile(pd, n_devices: int) -> Dict:
    """Per chip: ``exposed_s`` (collective seconds no other op overlaps)
    and ``collective_s`` (the collectives' union)."""
    tr = scopes._bench_trace()
    devs = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            lines = {line.name: list(line.events) for line in plane.lines}
            devs.append((int(m.group(1)), {"ops": lines.get("XLA Ops", [])}))
    devs = [d for _, d in sorted(devs, key=lambda x: x[0])][:n_devices]
    w0, w1 = scopes._window_bounds(pd, devs)
    out = []
    for d in devs:
        coll, other = [], []
        for ev in d["ops"]:
            s = ev.start_ns * 1e-9
            e = s + ev.duration_ns * 1e-9
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            parsed = tr.parse_instr(ev.name)
            if parsed is not None and parsed[2] in tr._NESTING:
                continue
            base = parsed[1] if parsed else ev.name
            (coll if is_collective(base) else other).append((s, e))
        out.append({"exposed_s": exposed_s(coll, other),
                    "collective_s": sum(b - a for a, b in _merge(coll))})
    return {"devices": out}


def print_table(red: Dict, steps: int) -> None:
    devs = red["devices"]
    if not devs:
        return
    n = len(devs) * steps
    coll = 1e3 * sum(d["collective_s"] for d in devs) / n
    exp = 1e3 * sum(d["exposed_s"] for d in devs) / n
    print(f"[collectives] {coll:.3f} ms a step, exposed {exp:.3f} ms; per "
          "chip exposed (ms a step): "
          + " ".join(f"{1e3 * d['exposed_s'] / steps:.3f}" for d in devs),
          file=sys.stderr, flush=True)


_DONE: Dict[str, Optional[Dict]] = {}


def window(ctx) -> Optional[Dict]:
    """This module's reduction of the traced window the reader's ``ctx``
    belongs to (``ctx["mesh"]`` where the caller made it), made once per
    trace; None where no trace is found."""
    if "mesh" in ctx:
        return ctx["mesh"]
    loc = scopes._caller()
    if loc is None:
        return None
    trace_dir = loc["trace_dir"]
    if trace_dir not in _DONE:
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(
            scopes._bench_trace().find_xplane(trace_dir))
        _DONE[trace_dir] = reduce_profile(pd, len(loc["devices"]))
        print_table(_DONE[trace_dir], ctx["steps"])
    return _DONE[trace_dir]
