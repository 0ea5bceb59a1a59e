"""Hillclimb-born distribution features (EXPERIMENTS.md §Perf)."""
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.distributed.sharding import default_rules


class FakeMesh:
    def __init__(self, shape, axes):
        self.shape = dict(zip(axes, shape))
        self.axis_names = axes
        self.size = int(np.prod(shape))


MESH = FakeMesh((16, 16), ("data", "model"))


def test_free_head_shard_unlocks_weight_sharding():
    cfg = get_config("llama3.2-3b")  # 24 heads % 16 != 0
    base = default_rules(MESH, cfg)
    free = default_rules(MESH, cfg, free_head_shard=True)
    shape = (3072, 3072)
    assert base._spec(base.param_rules, ("embed", "heads"), shape) == \
        P("data", None)
    assert free._spec(free.param_rules, ("embed", "heads"), shape) == \
        P("data", "model")
    # activation head dims (count=24) still replicate under free sharding
    assert free._spec(free.act_rules, ("batch", "seq", "heads", None),
                      (256, 4096, 24, 128)) == P("data", None, None, None)


def test_context_parallel_act_rule():
    cfg = get_config("llama3.2-3b")
    rules = default_rules(MESH, cfg, act_overrides={"seq_q": ("model",)})
    spec = rules._spec(rules.act_rules, ("batch", "heads", "seq_q", None),
                       (16, 24, 4096, 128))
    # heads (24) can't take model; seq_q (4096) does
    assert spec == P("data", None, "model", None)


def test_split_mamba_projection_shardings():
    cfg = get_config("jamba-1.5-large-398b")
    from repro.models.ssm import mamba_param_specs
    rules = default_rules(MESH, cfg)
    specs = mamba_param_specs(cfg)
    def spec_of(k):
        sp = specs[k]
        return rules._spec(rules.param_rules, sp.axes, sp.shape)
    assert spec_of("in_x") == P("data", "model")   # 256 heads % 16 == 0
    # jamba has n_groups=8 < 16 -> B/C replicate on the groups dim
    assert spec_of("in_b") == P("data", None)
    # dt projection shards on head count (256 % 16 == 0)
    assert spec_of("in_dt") == P("data", "model")


def test_mamba_groups_granule_blocks_nondivisible():
    cfg = get_config("mamba2-780m")  # n_groups=1 -> B/C replicated
    from repro.models.ssm import mamba_param_specs
    rules = default_rules(MESH, cfg)
    specs = mamba_param_specs(cfg)
    def spec_of(k):
        sp = specs[k]
        return rules._spec(rules.param_rules, sp.axes, sp.shape)
    assert spec_of("in_b") == P("data", None)
    # but x/z projections shard on heads (48 % 16 == 0)
    assert spec_of("in_x") == P("data", "model")


def test_bf16eq_collective_metric():
    from repro.analysis.hlo import collective_bytes
    hlo = """
  %a = f32[1024]{0} all-reduce(%p), to_apply=%add
  %b = bf16[1024]{0} all-gather(%q)
"""
    out = collective_bytes(hlo)
    assert out["effective_total"] == pytest.approx(2 * 4096 + 2048)
    assert out["effective_total_bf16eq"] == pytest.approx(4096 + 2048)


def test_all_fp4_sched_recipe_registered():
    from repro.core.recipe import RECIPES, PrecisionPlan
    r = RECIPES["all_fp4_sched"]
    assert r.target_precision_frac == 0.1
    from repro.core.schedule import TargetPrecisionSchedule
    s = TargetPrecisionSchedule(PrecisionPlan.uniform(r, 4), 100)
    assert s.switch_step == 90


def test_pallas_attention_impl_matches_chunked(monkeypatch, chip_precision):
    """On a TPU, self-attention takes the splash flash kernels (here in
    interpret mode, the platform test patched as the TPU compile tests do)
    and the model must match its chunked path, both at the chip's matmul
    precision."""
    import functools
    import importlib
    import jax
    from repro.core import routing
    from repro.core.recipe import RECIPES
    from repro.models import build_model
    fm = importlib.import_module("repro.kernels.fp4_matmul")
    ops = importlib.import_module("repro.kernels.ops")
    cfg = importlib.import_module("repro.configs.tiny").CONFIG.replace(
        dtype="float32")
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 128), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "targets": toks}
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))

    def forward():
        with routing.capture() as log:
            logits, _ = model.forward(params, batch, RECIPES["bf16"])
        return logits, {ev.route for ev in log.cells() if ev.role == "sdpa"}

    chunked, routes = forward()
    assert routes == {"chunked"}
    monkeypatch.setattr(fm, "interpret_mode", lambda: False)
    monkeypatch.setattr(ops, "flash_attention", functools.partial(
        ops.flash_attention, interpret=True))
    flash, routes = forward()
    assert routes == {"flash"}
    np.testing.assert_allclose(np.asarray(flash), np.asarray(chunked),
                               rtol=2e-4, atol=2e-4)
