"""Persistent ``(bm, bn, bk)`` autotuning for the fused quantized matmul.

The streaming kernel's MXU tiling is a real knob: the best tile shape
depends on the problem shape, operand dtypes, quant granularities and
stored-layout transposes.  This module owns the tuning table that maps a
**problem key** to a measured-best tiling:

    m256_n256_k256/float32xfloat32/block:tile/nn/b128 -> (bm, bn, bk)

Key fields, in order: effective GEMM dims (M, N, K), operand dtype names,
quant granularity pair, trans pair (``n``/``t`` for each operand), quant
block size.  Everything that changes the kernel's inner loop is in the key;
everything that doesn't (SR on/off, stats on/off, formats of equal width)
is deliberately left out so one measurement covers the whole family.

Three layers:

* **Committed JSON** (``tuning_table.json`` next to this file, schema
  ``qmm_tuning_table.v1``) — regenerated by ``kernel_bench --autotune``,
  validated in CI (``python -m repro.kernels.autotune --validate``).
* **In-process table** (``get_table``/``set_table``) — lazily loaded from
  the JSON once per process; tests swap it wholesale.
* **LRU resolution cache** (``resolve_tiles``) — the per-call-site lookup
  ``fused_qmm`` makes when no explicit tiles are passed.  Returns ``None``
  on a miss (caller falls back to the ``_pick_tile`` heuristic) and
  *ignores* table entries whose tiles don't divide the shape, so a stale
  table can never break a matmul — only fail to speed it up.

The table steers only the streaming pipeline (``pipeline="stream"``);
the default two-pass pipeline runs its product on XLA's dot and takes no
tiles.  Tile resolution is value-safe: the kernel is bit-identical across
any ``(bm, bn)`` (output tiling never touches the math), and ``bk`` only
changes the f32 accumulation *order*.  Quantized values, stats and SR
draws never depend on the tiling (quant groups are ``block``-sized and SR
noise is keyed by global element coordinate), so a table hit vs a
heuristic fallback differs at most in dot-product summation order, never
in any quantization decision.
"""
from __future__ import annotations

import argparse
import functools
import json
import re
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

SCHEMA = "qmm_tuning_table.v1"
DEFAULT_TABLE_PATH = Path(__file__).with_name("tuning_table.json")

_KEY_RE = re.compile(
    r"^m(\d+)_n(\d+)_k(\d+)/[\w.]+x[\w.]+/\w+:\w+/[nt][nt]/b(\d+)$")


def tuning_key(m: int, n: int, k: int, dtypes: Tuple[str, str],
               modes: Tuple[str, str], trans: Tuple[bool, bool],
               block: int = 128) -> str:
    """Canonical problem key (see module docstring for the field layout)."""
    return "/".join((
        f"m{m}_n{n}_k{k}",
        "x".join(dtypes),
        ":".join(modes),
        ("t" if trans[0] else "n") + ("t" if trans[1] else "n"),
        f"b{block}",
    ))


@dataclass
class TuningTable:
    """In-memory tuning table; ``entries[key] = {bm, bn, bk, us}``."""

    entries: Dict[str, dict] = field(default_factory=dict)
    meta: dict = field(default_factory=dict)

    @classmethod
    def load(cls, path) -> "TuningTable":
        data = json.loads(Path(path).read_text())
        assert data.get("schema") == SCHEMA, data.get("schema")
        return cls(entries=dict(data.get("entries", {})),
                   meta=dict(data.get("meta", {})))

    def save(self, path) -> None:
        payload = {"schema": SCHEMA, "meta": self.meta,
                   "entries": {k: self.entries[k]
                               for k in sorted(self.entries)}}
        Path(path).write_text(json.dumps(payload, indent=2, sort_keys=True)
                              + "\n")

    def lookup(self, key: str) -> Optional[Tuple[int, int, int]]:
        ent = self.entries.get(key)
        if ent is None:
            return None
        return int(ent["bm"]), int(ent["bn"]), int(ent["bk"])

    def record(self, key: str, bm: int, bn: int, bk: int,
               us: float) -> None:
        self.entries[key] = {"bm": int(bm), "bn": int(bn), "bk": int(bk),
                             "us": round(float(us), 2)}


# --------------------------------------------------------------------------
# Process-wide table + LRU resolution (what fused_qmm consults)
# --------------------------------------------------------------------------

_table: Optional[TuningTable] = None

# Distinct-resolution counters (resolve_tiles is LRU-cached, so repeats of
# the same problem don't re-count).  Tests use these to prove a table entry
# was actually consulted and applied.
COUNTERS = {"hit": 0, "miss": 0}


def get_table() -> TuningTable:
    """The process-wide table, lazily loaded from the committed JSON."""
    global _table
    if _table is None:
        if DEFAULT_TABLE_PATH.exists():
            _table = TuningTable.load(DEFAULT_TABLE_PATH)
        else:
            _table = TuningTable()
    return _table


def set_table(table: Optional[TuningTable]) -> None:
    """Swap the process-wide table (None re-arms the lazy JSON load) and
    drop the resolution cache so stale tiles can't be served."""
    global _table
    _table = table
    resolve_tiles.cache_clear()


@functools.lru_cache(maxsize=4096)
def resolve_tiles(m: int, n: int, k: int, *, dtypes: Tuple[str, str],
                  modes: Tuple[str, str], trans: Tuple[bool, bool],
                  block: int = 128) -> Optional[Tuple[int, int, int]]:
    """Table lookup for one problem; None on miss or unusable entry."""
    tiles = get_table().lookup(tuning_key(m, n, k, dtypes, modes, trans,
                                          block))
    if tiles is not None:
        bm, bn, bk = tiles
        ok = all(t > 0 and t % block == 0 for t in tiles)
        if ok and m % bm == 0 and n % bn == 0 and k % bk == 0:
            COUNTERS["hit"] += 1
            return tiles
    COUNTERS["miss"] += 1
    return None


# --------------------------------------------------------------------------
# The measurement sweep (kernel_bench --autotune drives this)
# --------------------------------------------------------------------------

def candidate_tiles(dim: int, block: int = 128,
                    max_mult: int = 4) -> List[int]:
    """Tile candidates for one dim: multiples of ``block`` that divide it."""
    return [c * block for c in range(1, max_mult + 1)
            if dim % (c * block) == 0]


def _best_time_us(fn, reps: int) -> float:
    import jax
    jax.block_until_ready(fn())  # compile outside the timed region
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def autotune_qmm(m: int, n: int, k: int, *,
                 a_mode: str = "block", b_mode: str = "tile",
                 a_fmt: str = "fp4_e2m1", b_fmt: str = "fp4_e2m1",
                 dtype: str = "float32",
                 trans_a: bool = False, trans_b: bool = False,
                 block: int = 128, pipeline: str = "stream",
                 reps: int = 3, interpret: Optional[bool] = None,
                 table: Optional[TuningTable] = None,
                 ) -> Tuple[Tuple[int, int, int], float]:
    """Sweep ``(bm, bn, bk)`` candidates for one problem and record the
    winner into ``table`` (default: the process-wide table) under the same
    key ``fused_qmm`` will later look up.  Returns ``(tiles, best_us)``."""
    import itertools

    import jax
    import jax.numpy as jnp

    from repro.kernels.fp4_matmul import fused_qmm, interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    dt = jnp.dtype(dtype)
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a_shape = (k, m) if trans_a else (m, k)
    b_shape = (n, k) if trans_b else (k, n)
    a = jax.random.normal(ka, a_shape, jnp.float32).astype(dt)
    b = jax.random.normal(kb, b_shape, jnp.float32).astype(dt)

    best_tiles, best_us = None, float("inf")
    for bm, bn, bk in itertools.product(candidate_tiles(m, block),
                                        candidate_tiles(n, block),
                                        candidate_tiles(k, block)):
        us = _best_time_us(
            lambda: fused_qmm(a, b, a_mode=a_mode, b_mode=b_mode,
                              a_fmt=a_fmt, b_fmt=b_fmt, trans_a=trans_a,
                              trans_b=trans_b, block=block, bm=bm, bn=bn,
                              bk=bk, pipeline=pipeline, interpret=interpret),
            reps)
        if us < best_us:
            best_tiles, best_us = (bm, bn, bk), us
    assert best_tiles is not None, (m, n, k, block)

    key = tuning_key(m, n, k, (dt.name, dt.name), (a_mode, b_mode),
                     (trans_a, trans_b), block)
    tab = table if table is not None else get_table()
    tab.record(key, *best_tiles, best_us)
    resolve_tiles.cache_clear()
    return best_tiles, best_us


# --------------------------------------------------------------------------
# Validation (CI gate for the committed table)
# --------------------------------------------------------------------------

def validate_table(path) -> List[str]:
    """Return a list of problems with the table file at ``path`` (empty =
    valid): schema tag, key syntax, tiles that are positive multiples of
    the key's block and divide the key's dims, and a lookup round-trip."""
    errors: List[str] = []
    try:
        data = json.loads(Path(path).read_text())
    except (OSError, json.JSONDecodeError) as e:
        return [f"unreadable table: {e}"]
    if data.get("schema") != SCHEMA:
        errors.append(f"schema {data.get('schema')!r} != {SCHEMA!r}")
        return errors
    entries = data.get("entries")
    if not isinstance(entries, dict) or not entries:
        errors.append("entries missing or empty")
        return errors
    table = TuningTable(entries=entries)
    for key, ent in entries.items():
        mobj = _KEY_RE.match(key)
        if not mobj:
            errors.append(f"{key}: malformed key")
            continue
        m, n, k, block = (int(g) for g in mobj.groups())
        try:
            bm, bn, bk = (int(ent[f]) for f in ("bm", "bn", "bk"))
            us = float(ent["us"])
        except (KeyError, TypeError, ValueError) as e:
            errors.append(f"{key}: malformed entry ({e})")
            continue
        for name, tile, dim in (("bm", bm, m), ("bn", bn, n),
                                ("bk", bk, k)):
            if tile <= 0 or tile % block != 0:
                errors.append(f"{key}: {name}={tile} not a positive "
                              f"multiple of block {block}")
            elif dim % tile != 0:
                errors.append(f"{key}: {name}={tile} does not divide {dim}")
        if not us > 0:
            errors.append(f"{key}: non-positive us {us}")
        if table.lookup(key) != (bm, bn, bk):
            errors.append(f"{key}: lookup round-trip failed")
    return errors


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--validate", metavar="PATH",
                    default=str(DEFAULT_TABLE_PATH),
                    help="tuning-table JSON to validate")
    args = ap.parse_args(argv)
    errors = validate_table(args.validate)
    if errors:
        for e in errors:
            print(f"FAIL {e}")
        return 1
    n = len(TuningTable.load(args.validate).entries)
    print(f"OK {args.validate}: {n} entries valid")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
