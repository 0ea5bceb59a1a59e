"""Persistent (bm, bn, bk) tuning table: keying, persistence, resolution,
validation, and the proof that a table hit is actually APPLIED by
``fused_qmm`` (and is bit-identical to the heuristic fallback).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels.autotune import (COUNTERS, DEFAULT_TABLE_PATH, SCHEMA,
                                    TuningTable, resolve_tiles, set_table,
                                    tuning_key, validate_table)
from repro.kernels.fp4_matmul import fused_qmm


@pytest.fixture(autouse=True)
def _restore_table():
    """Every test swaps the process-wide table; re-arm the lazy JSON load
    afterwards so other test files see the committed table again."""
    yield
    set_table(None)


def _table(key, bm, bn, bk, us=1.0):
    t = TuningTable()
    t.record(key, bm, bn, bk, us)
    return t


def test_tuning_key_format():
    key = tuning_key(256, 512, 384, ("float32", "bfloat16"),
                     ("block", "tile"), (False, True), 128)
    assert key == "m256_n512_k384/float32xbfloat16/block:tile/nt/b128"
    assert autotune._KEY_RE.match(key)


def test_table_save_load_round_trip(tmp_path):
    key = tuning_key(256, 256, 256, ("float32", "float32"),
                     ("block", "tile"), (False, False))
    t = _table(key, 128, 256, 128, us=42.125)
    path = tmp_path / "t.json"
    t.save(path)
    data = json.loads(path.read_text())
    assert data["schema"] == SCHEMA
    t2 = TuningTable.load(path)
    assert t2.lookup(key) == (128, 256, 128)
    assert t2.entries[key]["us"] == 42.12  # rounded on record
    assert t2.lookup("no/such/key") is None


def test_resolve_tiles_hit_miss_and_bad_entry():
    dt, modes, tr = ("float32", "float32"), ("block", "tile"), (False, False)
    key = tuning_key(256, 256, 256, dt, modes, tr)
    set_table(_table(key, 128, 128, 128))
    assert resolve_tiles(256, 256, 256, dtypes=dt, modes=modes,
                         trans=tr) == (128, 128, 128)
    # miss: different shape
    assert resolve_tiles(512, 256, 256, dtypes=dt, modes=modes,
                         trans=tr) is None
    # unusable entry (tiles don't divide the keyed shape) -> ignored, not
    # an error: a stale table can only fail to speed things up
    bad = tuning_key(384, 384, 384, dt, modes, tr)
    set_table(_table(bad, 256, 256, 256))
    assert resolve_tiles(384, 384, 384, dtypes=dt, modes=modes,
                         trans=tr) is None


def test_set_table_clears_resolution_cache():
    dt, modes, tr = ("float32", "float32"), ("block", "tile"), (False, False)
    key = tuning_key(256, 256, 256, dt, modes, tr)
    set_table(_table(key, 128, 128, 128))
    assert resolve_tiles(256, 256, 256, dtypes=dt, modes=modes,
                         trans=tr) == (128, 128, 128)
    set_table(_table(key, 256, 256, 256))  # must not serve the stale 128s
    assert resolve_tiles(256, 256, 256, dtypes=dt, modes=modes,
                         trans=tr) == (256, 256, 256)


def test_validate_table(tmp_path):
    key = tuning_key(256, 256, 256, ("float32", "float32"),
                     ("block", "tile"), (False, False))
    good = tmp_path / "good.json"
    _table(key, 128, 256, 128, us=3.5).save(good)
    assert validate_table(good) == []

    bad = tmp_path / "bad.json"
    t = TuningTable()
    t.record(key, 96, 256, 128, us=3.5)          # 96 not a block multiple
    t.record("not a key", 128, 128, 128, us=1.0)  # malformed key
    t.record(tuning_key(256, 256, 256, ("float32", "float32"),
                        ("block", "block"), (False, False)),
             512, 128, 128, us=1.0)               # 512 does not divide 256
    t.save(bad)
    errors = validate_table(bad)
    assert len(errors) == 3
    assert any("not a positive multiple" in e for e in errors)
    assert any("malformed key" in e for e in errors)
    assert any("does not divide" in e for e in errors)

    assert validate_table(tmp_path / "absent.json")  # unreadable


def test_committed_table_is_valid():
    assert DEFAULT_TABLE_PATH.exists(), DEFAULT_TABLE_PATH
    assert validate_table(DEFAULT_TABLE_PATH) == []


def test_table_tiling_is_applied_and_bit_identical(monkeypatch):
    """A table hit must (a) actually be consulted — the hit counter grows —
    (b) actually be APPLIED — the tiles reaching the jit'd pipeline body
    are the table's, not the heuristic's — and (c) be bit-identical to the
    heuristic fallback (the table entry keeps the heuristic's bk, so even
    the f32 accumulation order matches; bm/bn never touch the math)."""
    import importlib
    fm = importlib.import_module("repro.kernels.fp4_matmul")

    m = n = k = 384  # _pick_tile heuristic gives (384, 384, 384)
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (m, k), jnp.float32)
    b = jax.random.normal(kb, (k, n), jnp.float32) * 0.05
    kw = dict(a_mode="block", b_mode="tile", pipeline="stream",
              interpret=True)

    applied = {}
    orig = fm._fused_qmm

    def spy(a_, b_, **kws):
        applied.update(bm=kws["bm"], bn=kws["bn"], bk=kws["bk"])
        return orig(a_, b_, **kws)

    monkeypatch.setattr(fm, "_fused_qmm", spy)

    set_table(TuningTable())
    y_fallback = fused_qmm(a, b, **kw)  # heuristic tiles
    assert (applied["bm"], applied["bn"], applied["bk"]) == (384, 384, 384)

    key = tuning_key(m, n, k, ("float32", "float32"), ("block", "tile"),
                     (False, False))
    set_table(_table(key, 128, 128, 384))
    hits = COUNTERS["hit"]
    y_table = fused_qmm(a, b, **kw)
    assert COUNTERS["hit"] == hits + 1, "table was not consulted"
    assert (applied["bm"], applied["bn"], applied["bk"]) == (128, 128, 384), \
        "table tiling was not applied"
    np.testing.assert_array_equal(
        np.asarray(y_table).view(np.uint8),
        np.asarray(y_fallback).view(np.uint8),
        err_msg="table hit not bit-identical to heuristic fallback")


def test_partial_explicit_tiles_skip_the_table():
    """Any explicitly-passed tile disables the lookup (explicit wins)."""
    m = n = k = 256
    ka, kb = jax.random.split(jax.random.PRNGKey(1))
    a = jax.random.normal(ka, (m, k), jnp.float32)
    b = jax.random.normal(kb, (k, n), jnp.float32)
    key = tuning_key(m, n, k, ("float32", "float32"), ("block", "tile"),
                     (False, False))
    set_table(_table(key, 128, 128, 128))
    resolve_tiles.cache_clear()
    before = dict(COUNTERS)
    fused_qmm(a, b, a_mode="block", b_mode="tile", bm=256,
              pipeline="stream", interpret=True)
    assert dict(COUNTERS) == before, "partial tiles must skip the lookup"
