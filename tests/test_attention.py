"""Chunked flash attention vs naive oracle; caches; SWA; the route each
attention core takes (flash kernels or chunked)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import routing
from repro.core.recipe import RECIPES
from repro.kernels.ref import flash_attention_ref
from repro.models import build_model
from repro.models.attention import (attention, attn_param_specs,
                                    chunked_attention, cross_attention,
                                    cross_attn_param_specs, init_attn_cache,
                                    _update_cache)


def _qkv(b=2, s=128, h=4, kvh=2, d=32, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("chunk", [16, 37, 128, 200])
@pytest.mark.parametrize("unroll", [False, True])
def test_chunked_matches_naive_causal(chunk, unroll):
    q, k, v = _qkv()
    pos = jnp.arange(128, dtype=jnp.int32)
    out = chunked_attention(q, k, v, pos, pos, causal=True, chunk=chunk,
                            unroll=unroll)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_noncausal_matches_naive():
    q, k, v = _qkv()
    pos = jnp.arange(128, dtype=jnp.int32)
    out = chunked_attention(q, k, v, pos, pos, causal=False, chunk=32)
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_sliding_window_masks_far_tokens():
    """Output at position p must not depend on keys older than the window."""
    q, k, v = _qkv(s=64)
    pos = jnp.arange(64, dtype=jnp.int32)
    w = 16
    out = chunked_attention(q, k, v, pos, pos, causal=True, window=w,
                            chunk=16)
    # perturb keys/values at positions < 32; outputs at p >= 48 (p - kpos
    # >= w for all perturbed kpos) must be identical
    k2 = k.at[:, :32].add(100.0)
    v2 = v.at[:, :32].add(-50.0)
    out2 = chunked_attention(q, k2, v2, pos, pos, causal=True, window=w,
                             chunk=16)
    np.testing.assert_allclose(np.asarray(out[:, 48:]),
                               np.asarray(out2[:, 48:]), rtol=1e-5,
                               atol=1e-5)
    assert float(jnp.abs(out[:, :30] - out2[:, :30]).max()) > 0


def test_invalid_cache_slots_are_masked():
    """k_pos == -1 (unwritten ring slots) must contribute nothing."""
    q, k, v = _qkv(s=32)
    pos = jnp.arange(32, dtype=jnp.int32)
    kpos = pos.at[20:].set(-1)
    out = chunked_attention(q, k, v, pos, kpos, causal=True, chunk=8)
    ref = flash_attention_ref(q[:, :], k[:, :20], v[:, :20], causal=False)
    # compare only queries >= 19 which see all 20 valid keys causally
    np.testing.assert_allclose(np.asarray(out[:, 19]),
                               np.asarray(ref[:, 19]), rtol=1e-4, atol=1e-4)


def test_fully_masked_chunk_guard():
    """A chunk where every key is masked must not produce NaNs."""
    q, k, v = _qkv(s=16)
    pos = jnp.arange(16, dtype=jnp.int32)
    kpos = jnp.full((16,), -1, jnp.int32)  # everything invalid
    out = chunked_attention(q, k, v, pos, kpos, causal=True, chunk=4)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_ring_buffer_update_wraps():
    from repro.configs.base import get_config
    cfg = get_config("tiny").replace(sliding_window=8)
    cache = init_attn_cache(cfg, batch=1, max_len=8)
    hd = cfg.resolved_head_dim
    for t in range(12):
        kt = jnp.full((1, 1, cfg.n_kv_heads, hd), float(t))
        new, k_all, v_all, kpos = _update_cache(
            cache, kt, kt, jnp.asarray(t), cfg.sliding_window)
        cache = new
    # slots hold positions 4..11 (last 8), wrapped
    assert sorted(np.asarray(cache["pos"]).tolist()) == list(range(4, 12))
    slot_of_11 = int(np.where(np.asarray(cache["pos"]) == 11)[0][0])
    assert slot_of_11 == 11 % 8
    np.testing.assert_array_equal(np.asarray(cache["k"][0, slot_of_11, 0]),
                                  11.0)


# ---------------------------------------------------------------------------
# The route of each attention core (routing census, role "sdpa")
# ---------------------------------------------------------------------------

FM = importlib.import_module("repro.kernels.fp4_matmul")


def _attn_params(cfg, kv_dim=None):
    specs = (cross_attn_param_specs(cfg, kv_dim) if kv_dim
             else attn_param_specs(cfg))
    keys = jax.random.split(jax.random.PRNGKey(0), len(specs))
    return {n: jax.random.normal(k, s.shape, jnp.float32) * 0.1
            for k, (n, s) in zip(keys, sorted(specs.items()))}


def _sdpa_events(fn):
    """Trace ``fn`` (nothing runs) and return its ``sdpa`` census cells
    as (route, reasons)."""
    with routing.capture() as log:
        jax.make_jaxpr(fn)()
    return {(ev.route, ev.reasons) for ev in log.cells()
            if ev.role == "sdpa"}


def _self_attn(cfg, s=128, **kw):
    x = jnp.ones((2, s, cfg.d_model), jnp.float32)
    return lambda: attention(_attn_params(cfg), cfg, x,
                             RECIPES["bf16"].attn_linear, **kw)


def test_sdpa_routes_flash_through_the_model_on_a_tpu(monkeypatch,
                                                      chip_precision):
    """With the platform test reading a TPU, every layer's self-attention
    of a tiny model's training forward records ``flash``."""
    monkeypatch.setattr(FM, "interpret_mode", lambda: False)
    cfg = get_config("tiny").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    toks = jnp.zeros((2, 128), jnp.int32)
    with routing.capture() as log:
        jax.make_jaxpr(lambda: model.forward(
            params, {"tokens": toks, "targets": toks}, RECIPES["bf16"]))()
    cells = [ev for ev in log.cells() if ev.role == "sdpa"]
    assert cells and all(ev.route == "flash" and ev.reasons == ()
                         and ev.cls == "attn" and ev.layer
                         and ev.spec_a == "float32" for ev in cells)


@pytest.mark.parametrize("case,tpu,want", [
    ("self", True, ("flash", ())),
    ("self", False, ("chunked", ("not_tpu",))),
    ("cache", True, ("chunked", ("cache",))),
    ("window", True, ("chunked", ("window",))),
    ("positions", True, ("chunked", ("positions",))),
    ("seq_len", True, ("chunked", ("seq_len",))),
    ("cross", True, ("chunked", ("cross",))),
    ("highest", True, ("chunked", ("precision",))),
    ("float32", True, ("chunked", ("precision",))),
])
def test_sdpa_route_and_reason(monkeypatch, chip_precision, case, tpu,
                               want):
    """Self-attention takes the flash kernels only on a TPU, with no
    cache, no window, default positions, a length that is a multiple of
    128 and a default matmul precision the kernels' bf16 dots compile at;
    every other call records ``chunked`` with its reason."""
    monkeypatch.setattr(FM, "interpret_mode", lambda: not tpu)
    cfg = get_config("tiny").replace(dtype="float32")
    if case in ("highest", "float32"):
        with jax.default_matmul_precision(case):
            assert _sdpa_events(_self_attn(cfg)) == {want}
        return
    if case == "self":
        fn = _self_attn(cfg)
    elif case == "cache":
        fn = _self_attn(cfg, cache=init_attn_cache(cfg, 2, 256,
                                                   jnp.float32),
                        cache_len=jnp.asarray(0, jnp.int32))
    elif case == "window":
        fn = _self_attn(cfg.replace(sliding_window=64))
    elif case == "positions":
        fn = _self_attn(cfg, positions=jnp.arange(128, dtype=jnp.int32))
    elif case == "seq_len":
        fn = _self_attn(cfg, s=96)
    else:
        x = jnp.ones((2, 128, cfg.d_model), jnp.float32)
        kv = jnp.ones((2, 128, cfg.d_model), jnp.float32)
        fn = lambda: cross_attention(  # noqa: E731
            _attn_params(cfg, cfg.d_model), cfg, x,
            RECIPES["bf16"].attn_linear, kv_states=kv)
    assert _sdpa_events(fn) == {want}


@pytest.mark.parametrize("mesh_shape,kw,want", [
    ((4, 1), {}, ()),                                  # FSDP: batch split
    ((1, 2), {}, ()),                                  # TP: heads split
    ((1, 4), {}, ("kv_heads_split",)),                 # 2 KV heads on 4
    # the KV sequence takes the model axis, so the KV heads cannot
    ((1, 2), {"seq_parallel": True}, ("seq_sharded", "kv_heads_split")),
    # 4 heads do not split over 8: the call would repeat on every chip
    ((1, 8), {}, ("mesh_axis_unused",)),
    # the fp8-compressed data-parallel step's per-shard body: its rules
    # leave the batch unmapped, the vmap's stacked dim carries the data
    ((4, 1), {"manual_data": True}, ("mesh_axis_unused",)),
    # a layout the kernels could split, kept off them by the model's cache
    ((4, 1), {"cache": True}, ("cache",)),
])
def test_sdpa_mesh_layouts(monkeypatch, chip_precision, mesh_shape, kw,
                           want):
    """Under a mesh the kernels run inside ``shard_map`` with whole
    sequence axes, each chip's KV heads the ones its query heads read and
    every mesh axis split by batch or heads; other layouts keep the
    chunked path, with no ``shard_map`` specs."""
    from jax.sharding import AbstractMesh

    from repro.distributed.sharding import default_rules
    from repro.models.attention import _flash_route
    monkeypatch.setattr(FM, "interpret_mode", lambda: False)
    cfg = get_config("tiny")                       # 4 heads, 2 KV heads
    manual = kw.pop("manual_data", False)
    cache = {} if kw.pop("cache", False) else None
    rules = default_rules(AbstractMesh(mesh_shape, ("data", "model")), cfg,
                          **kw)
    if manual:
        rules = rules.manual_over(rules.dp_axes)
    q = jax.ShapeDtypeStruct((8, 128, 4, 16), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((8, 128, 2, 16), jnp.bfloat16)
    reasons, specs = _flash_route(q, k, rules, cache=cache, window=0,
                                  default_positions=True)
    assert reasons == want
    assert (specs is None) == bool(want)
