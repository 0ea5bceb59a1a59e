"""The op -> layer-scope map and the scoped trace reduction
(``bench/scopes.py``), and the readers of ``dispatch_ms``, ``attn_ms`` and
``optim_ms``, on the CPU: a tiny cell's step compiled here, the stored
v5e trace ``small.xplane.pb``, and a trace of the tiny cell's loop."""
import math
import os
import re
import tempfile

import jax
import pytest

import _bench
from tiny import cell as tiny_cell

run = _bench.module("run")
scopes = _bench.module("scopes")
program = _bench.module("program")

TR = run.load_module("trace")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
READERS = ("dispatch_ms", "attn_ms", "optim_ms")


def test_innermost_scope_sees_through_transforms():
    f = scopes.innermost_scope
    assert f("jit(train_step)/transpose(jvp(head))/norm/mul") == "norm"
    assert f("jit(train_step)/transpose(jvp(head))/reduce_sum") == "head"
    assert f("jit(train_step)/jvp()/while/body/closed_call/checkpoint/"
             "rematted_computation/attn/qrole_fwd/jit(_fused_qmm)") == "attn"
    assert f("jit(train_step)/optim/mul") == "optim"
    assert f("jit(train_step)/jvp()/while/body/dynamic_slice") is None
    assert f("jit(embedding_lookup)/gather") is None


def test_scope_map_rules():
    text = """HloModule jit_f, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %m = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(f)/mlp/mul"}
  ROOT %a = f32[8]{0} add(%m, %p0), metadata={op_name="jit(f)/add"}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %copy-start = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%x)
  %copy-done = f32[8]{0} copy-done(%copy-start)
  %fusion.3 = f32[8]{0} fusion(%copy-done), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/add"}
  %n = f32[8]{0} negate(%fusion.3), metadata={op_name="jit(f)/transpose(jvp(attn))/neg"}
  ROOT %s = f32[8]{0} subtract(%n, %x), metadata={op_name="jit(f)/sub"}
}
"""
    m = scopes.scope_map(text)
    assert m["fusion.3"] == "mlp"          # from what it fuses
    assert m["n"] == "attn"                # through the transforms
    assert m["copy-done"] == m["copy-start"] == "mlp"   # from its user
    assert "s" not in m and "x" not in m   # x feeds two scopes' ops


def test_aligned_map_follows_the_executable_that_ran():
    """A cached executable can name a few instructions otherwise than a
    fresh compile of the same program: the map takes the fresh compile's
    scopes under the names of the one that ran."""
    fresh = """HloModule jit_f

ENTRY %main.3 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %reshape.7 = f32[8]{0} reshape(%x), metadata={op_name="jit(f)/attn/r"}
  ROOT %n = f32[8]{0} negate(%reshape.7), metadata={op_name="jit(f)/mlp/n"}
}
"""
    ran = fresh.replace("reshape.7", "reshape.9").replace("/attn/", "/")
    assert scopes.aligned_scope_map(ran, fresh) == {
        "x": "attn", "reshape.9": "attn", "n": "mlp"}
    other = ran.replace("negate", "abs")
    assert scopes.aligned_scope_map(other, fresh) == scopes.scope_map(fresh)


def test_map_takes_fresh_metadata_under_a_stale_cache(tmp_path):
    """JAX keeps source metadata out of its persistent cache's key: after
    a program without the layer scopes filled the cache, the executable
    the window runs carries that program's ``op_name``s, and the map must
    still name the scopes, from a fresh compile."""
    from jax._src import source_info_util as siu
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    was = {k: getattr(jax.config, k) for k in keys}
    enter = siu.ExtendNameStackContextManager.__enter__

    def no_layer_scopes(self):
        if self.name in scopes.LAYER_SCOPES:
            self.prev = siu._source_info_context.context
            return self.prev.name_stack
        return enter(self)

    cell = tiny_cell()
    try:
        for k, v in zip(keys, (str(tmp_path), 0.0, 0, True)):
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        siu.ExtendNameStackContextManager.__enter__ = no_layer_scopes
        scopes.step_hlo(cell)                 # the older program's compile
        siu.ExtendNameStackContextManager.__enter__ = enter
        ran, fresh = scopes.step_hlo(cell)
    finally:
        siu.ExtendNameStackContextManager.__enter__ = enter
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert "/attn/" not in ran and "/attn/" in fresh
    assert set(scopes.LAYER_SCOPES) <= set(
        scopes.aligned_scope_map(ran, fresh).values())


@pytest.fixture(scope="module")
def tiny_fused_hlo():
    ran, fresh = scopes.step_hlo(tiny_cell(linear_impl="pallas"))
    assert scopes.aligned_scope_map(ran, fresh) == scopes.scope_map(fresh)
    return fresh


def test_map_scopes_every_dot_of_a_fused_step(tiny_fused_hlo):
    """Every matmul of a tiny fused ``paper_fp4`` step, forward, backward
    and recomputed, gets a layer scope, and each scope appears."""
    text = tiny_fused_hlo
    smap = scopes.scope_map(text)
    dots = re.findall(r"^\s+(?:ROOT )?%([\w.\-]+) = \S+ dot\(.*$", text,
                      re.M)
    assert dots
    assert all(d in smap for d in dots), [d for d in dots
                                          if d not in smap][:5]
    lines = {m.group(1): m.group(0) for m in re.finditer(
        r"^\s+(?:ROOT )?%([\w.\-]+) = \S+ dot\(.*$", text, re.M)}
    assert any("transpose(" in lines[d] for d in dots)
    assert any("rematted_computation" in lines[d] for d in dots)
    assert set(scopes.LAYER_SCOPES) <= set(smap.values())


def test_scoped_reduction_keeps_the_old_numbers():
    """With or without a map, every key ``trace.reduce_profile`` returns
    keeps its value; the map's buckets hold every op of the window."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(SMALL)
    old = TR.reduce_profile(pd, n_devices=1)
    plain = scopes.reduce_profile(pd, 1)
    mapped = scopes.reduce_profile(pd, 1, {}, "jit_train_step")
    for red in (plain, mapped):
        assert {k: red[k] for k in old} == old
    assert plain["span_n"] == {"dispatch": 0, "wait": 0}  # spans came later
    sc = mapped["scope_s"][0]
    assert set(sc) == set(scopes.LAYER_SCOPES) | {"unscoped",
                                                  "other_modules"}
    # an empty map leaves every op of the step's module unscoped
    assert sum(sc[k] for k in scopes.LAYER_SCOPES) == 0.0
    assert 0 < sc["unscoped"] <= mapped["module_s"][0] <= old["window_s"]
    assert sc["unscoped"] + sc["other_modules"] == pytest.approx(
        sum(old_op_time(pd).values()))


def old_op_time(pd):
    """Device seconds of the window's ops by name, as the breakdown of
    ``trace.reduce_profile`` sums them before keeping its ten largest."""
    host = [(ev.start_ns, ev.start_ns + ev.duration_ns)
            for p in pd.planes if p.name == "/host:CPU" for line in p.lines
            for ev in line.events if ev.name in ("data", "step", "host")]
    w0, w1 = min(a for a, _ in host), max(b for _, b in host)
    out = {}
    for p in pd.planes:
        if p.name != "/device:TPU:0":
            continue
        for line in p.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                parsed = TR.parse_instr(ev.name)
                if parsed is not None and parsed[2] in TR._NESTING:
                    continue
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e > s:
                    out[ev.name] = out.get(ev.name, 0.0) + (e - s) * 1e-9
    return out


def read_trace(cell, trace_dir, devices, n_steps):
    """Stands in for ``run.read_trace``: the readers find the trace and the
    cell in its frame."""
    ctx = {"steps": n_steps}
    return {name: run.load_module("metrics/" + name).read(ctx)
            for name in READERS}


def test_readers_on_a_cpu_trace_of_the_loop():
    """On a CPU trace of the tiny cell's loop the host spans are read and
    the device scopes, which need a TPU's planes, read as nothing."""
    cell = tiny_cell()
    prog = program.Program(cell)
    prog.first_steps(11)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=run.trace_options())
        prog.train(2)
        jax.profiler.stop_trace()
        got = read_trace(cell, d, jax.devices()[:1], 2)
    assert got["dispatch_ms"] is not None and math.isfinite(
        got["dispatch_ms"]) and got["dispatch_ms"] > 0
    assert got["attn_ms"] is None and got["optim_ms"] is None


SCOPED = os.path.join(DATA, "small_scoped.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    """The second stored trace: ``bench/record_trace.py``'s small model
    (two fused-kernel ``paper_fp4`` steps, width 256, batch 2 x 256) with
    the loop's spans and the step's layer scopes, recorded on a v5e with
    its op -> scope map (``python3 bench/scopes.py --out``) and trimmed,
    as ``small.xplane.pb`` was, to the planes and lines the reductions
    read."""
    import json

    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "small_scoped.scopes.json")) as f:
        m = json.load(f)
    return scopes.reduce_profile(ProfileData.from_file(SCOPED), 1,
                                 m["scopes"], m["module"])


def test_scoped_fixture_is_small():
    assert os.path.getsize(SCOPED) < 1 << 20


def test_readers_on_the_scoped_trace(scoped):
    ctx = {"scoped": scoped, "steps": 2}
    got = {name: run.load_module("metrics/" + name).read(ctx)
           for name in READERS}
    assert all(v is not None and math.isfinite(v) and v > 0
               for v in got.values()), got
    assert scoped["span_n"] == {"dispatch": 2, "wait": 2}
    assert scoped["host_n"]["step"] == 2


def test_scopes_account_for_the_step_module(scoped):
    """Every op of the step's module lands in one bucket, ``unscoped`` in
    under 2% of them, and each layer scope has device time."""
    sc = scopes.mean_scope_s(scoped)
    in_module = sum(v for k, v in sc.items() if k != "other_modules")
    assert in_module <= scoped["module_s"][0]
    assert in_module >= 0.9 * scoped["module_s"][0]
    assert sc["unscoped"] < 0.02 * in_module
    assert all(sc[k] > 0 for k in scopes.LAYER_SCOPES)
