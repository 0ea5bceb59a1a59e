"""qlint static analyzer: HLO walker parsing, comms payload audit,
fallback-reason vocabulary, seeded role-safety violations, recompile
census, and the expectations gate."""
import dataclasses
import json

from repro.analysis import qlint
from repro.analysis.hlo import (HloOp, collective_bytes, parse_collectives,
                                walk_hlo)
from repro.analysis.qlint import (Finding, QlintReport, audit_hlo_comms,
                                  audit_scale_placement,
                                  compare_expectations,
                                  expectations_payload)
from repro.configs.base import TrainConfig, get_config
from repro.core.qlinear import kernel_quant_mode, kernel_unsupported_reason
from repro.core.quantize import QuantSpec
from repro.core.recipe import RECIPES, PrecisionPlan


# ---------------------------------------------------------------------------
# shared HLO walker
# ---------------------------------------------------------------------------

_HLO = """\
HloModule jit_step, entry_computation_layout={()->f32[]}

ENTRY %main {
  %p0 = f32[8,16]{1,0} parameter(0)
  %ar = f16[1024,512]{1,0} all-reduce(%x), replica_groups={{0,1}}, \
metadata={op_name="jit(train_step)/jit(main)/collective/add"}
  %ags = (bf16[8]{0}, bf16[16]{0}) all-gather-start(%p1), dimensions={0}
  %agd = bf16[16]{0} all-gather-done(%ags)
  %amax = f32[] all-reduce(%m), to_apply=%max_f32, \
metadata={op_name="jit(train_step)/jit(main)/collective/reduce_max"}
  %add.7 = f32[8,16]{1,0} add(%p0, %p0)
}
"""


def test_walk_hlo_parses_ops_shapes_and_metadata():
    ops = {op.mnemonic: op for op in walk_hlo(_HLO)}
    assert "parameter" in ops and "add" in ops
    ar = ops["all-reduce"]
    assert isinstance(ar, HloOp)
    assert ar.base == "all-reduce" and ar.variant == ""
    ag = ops["all-gather-start"]
    assert ag.base == "all-gather" and ag.variant == "-start"
    # async -start tuples keep every buffer; payload is the largest
    assert ag.payload_shape() == ("bf16", "16")
    assert ops["all-gather-done"].variant == "-done"


def test_walk_hlo_op_name_extraction():
    ops = [op for op in walk_hlo(_HLO) if op.op_name]
    paths = {op.op_name for op in ops}
    assert "jit(train_step)/jit(main)/collective/add" in paths
    assert "jit(train_step)/jit(main)/collective/reduce_max" in paths


def test_parse_collectives_counts_start_once():
    ops = parse_collectives(_HLO)
    kinds = sorted(k for k, _, _ in ops)
    # -done skipped, -start counted once; the two genuine all-reduces
    assert kinds == ["all-gather", "all-reduce", "all-reduce"]
    cb = collective_bytes(_HLO)
    assert cb["n_ops"] == 3
    # one f16[1024,512] payload at factor 2 dominates
    assert cb["raw_all-reduce_f16"] == 1024 * 512 * 2


# ---------------------------------------------------------------------------
# comms audit: fp8 wire payloads vs the scalar amax scale reductions
# ---------------------------------------------------------------------------

def test_audit_hlo_comms_clean_fp8_with_scale_reductions():
    census, findings = audit_hlo_comms(_HLO, expect_fp8=True)
    # the f16 payload is the legalized fp8 gradient; the scalar f32
    # reduce_max is the shared-scale amax reduction, censused not flagged
    assert findings == []
    assert census["grad_allreduce_dtypes"] == {"f16": 1}
    assert census["scale_allreduce_dtypes"] == {"f32": 1}


def test_audit_hlo_comms_flags_uncompressed_payload():
    bad = _HLO.replace("f16[1024,512]", "f32[1024,512]")
    _, findings = audit_hlo_comms(bad, expect_fp8=True)
    assert any(f.check == "comms" and f.severity == "violation"
               and "f32" in f.message for f in findings)


def test_audit_hlo_comms_requires_a_payload_allreduce():
    no_payload = "\n".join(l for l in _HLO.splitlines() if "%ar " not in l)
    _, findings = audit_hlo_comms(no_payload, expect_fp8=True)
    assert any("no payload all-reduce" in f.message for f in findings)
    # without fp8 expectation the same text is fine
    _, findings = audit_hlo_comms(no_payload, expect_fp8=False)
    assert findings == []


# ---------------------------------------------------------------------------
# structured fallback reasons (kernel support vocabulary)
# ---------------------------------------------------------------------------

def test_fallback_reason_vocabulary():
    ok = QuantSpec("fp4_e2m1", "block", block=128)
    assert kernel_unsupported_reason(ok) is None
    assert kernel_quant_mode(ok) is not None
    odd_block = QuantSpec("fp4_e2m1", "block", block=64)
    reason = kernel_unsupported_reason(odd_block)
    assert reason is not None and reason.startswith("unsupported_block")
    assert kernel_quant_mode(odd_block) is None
    clip_only = QuantSpec("fp16", "tensor")
    reason = kernel_unsupported_reason(clip_only)
    assert reason is not None and reason.startswith("unsupported_dtype")


# ---------------------------------------------------------------------------
# label parsing + scale placement policy
# ---------------------------------------------------------------------------

def test_label_layers_unroll_and_scan_forms():
    assert qlint._label_layers("L3", 8) == [3]
    assert qlint._label_layers("L0:2:1", 8) == [0, 1]
    assert qlint._label_layers(None, 8) == []


def test_scale_placement_policy_clean_on_paper_plan():
    plan = PrecisionPlan.uniform(RECIPES["fine_grained_fp4"], 2)
    assert audit_scale_placement(plan) == []


# ---------------------------------------------------------------------------
# expectations gate
# ---------------------------------------------------------------------------

def _report(label, route="pallas"):
    r = QlintReport(label)
    r.cells = [{"layer": "L0", "cls": "ffn", "role": "fwd", "route": route,
                "spec_a": "fp4_e2m1@block128", "spec_b": "fp4_e2m1@tile128",
                "sr_a": False, "sr_b": False, "mode_a": "block",
                "mode_b": "tile", "pipeline": "stream", "reasons": []}]
    r.summary = {"pallas_calls": {"fwd": 2}, "qdq_markers": {}}
    return r


def test_expectations_roundtrip_and_drift():
    payload = expectations_payload([_report("g")])
    assert compare_expectations(payload, json.loads(json.dumps(payload))) \
        == []
    drifted = expectations_payload([_report("g", route="qdq_fallback")])
    diffs = compare_expectations(drifted, payload)
    assert diffs and any("cells" in d for d in diffs)
    missing = compare_expectations({"graphs": {}, "n_violations": 0,
                                    "n_fallbacks": 0}, payload)
    assert any("missing" in d for d in missing)


def test_expectations_count_violations():
    r = _report("g")
    r.add(Finding("role_safety", "violation", "L0/ffn/fwd:lhs", "seeded"))
    payload = expectations_payload([r])
    assert payload["n_violations"] == 1
    assert payload["graphs"]["g"]["n_violations"] == 1


# ---------------------------------------------------------------------------
# traced-graph audits (jaxpr only — no XLA compile, keep these fast)
# ---------------------------------------------------------------------------

def _tcfg(**kw):
    kw.setdefault("recipe", "fine_grained_fp4")
    kw.setdefault("total_steps", 4)
    # 4 x 32 = 128 tokens: the block128 wgrad kernels need a full group
    # along the token-reduction dim or they'd legitimately fall back
    kw.setdefault("global_batch", 4)
    kw.setdefault("seq_len", 32)
    kw.setdefault("log_every", 0)
    return TrainConfig(**kw)


def test_f32_kernel_operands_flag_any_kernel_but_splash():
    """A kernel with a float32 matrix operand is flagged wherever it
    sits, also outside a matmul role; the splash attention kernels, whose
    backward reads the float32 softmax statistics, are not."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from repro.kernels.ops import flash_attention

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    x = jnp.ones((8, 128), jnp.float32)
    toy = jax.make_jaxpr(lambda x: pl.pallas_call(
        copy, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True)(x))(x)
    assert qlint.graph_census(toy)["f32_kernel_operands"] == [
        "qrole_?: float32 operand (8, 128)"]

    q = jnp.ones((1, 128, 2, 64), jnp.bfloat16)
    splash = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, interpret=True).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, q, q)
    census = qlint.graph_census(splash)
    assert census["pallas_calls"] == {"-": 3}
    assert census["f32_kernel_operands"] == []


def test_train_graph_audit_clean_and_covers_all_cells():
    cfg = get_config("tiny").replace(scan_layers=False,
                                    linear_impl="pallas")
    report = qlint.audit_train_graph(cfg, _tcfg(), label="t",
                                     compile_hlo=False)
    assert report.violations() == []
    assert report.fallbacks() == []
    assert report.ok
    # every (layer, class, role) quantized cell + the protected head
    routes = {(c["layer"], c["cls"], c["role"]): c["route"]
              for c in report.cells}
    assert routes[(None, "head", "fwd")] == "dot"
    for i in range(cfg.n_layers):
        for cls in ("attn", "ffn"):
            for role in ("fwd", "dgrad", "wgrad"):
                assert routes[(f"L{i}", cls, role)] == "pallas"
    assert report.summary["recompile"]["n_compiled"] \
        <= report.summary["recompile"]["budget"]


def test_bf16_dgrad_cells_stage_no_kernel():
    """The paper's bf16 x bf16 FFN dgrad is a pallas-routed cell that the
    two-pass pipeline runs on XLA's dot alone: the census asks no
    pallas_call of it, while a quantizing cell of the role still needs
    one, and so does a bf16 cell on the streaming kernel."""
    from repro.core.routing import RouteEvent
    bf16 = RouteEvent("L0", "ffn", "dgrad", "pallas", "bf16", "bf16",
                      mode_a="pass", mode_b="pass", pipeline="two_pass")
    fp8 = RouteEvent("L0", "attn", "dgrad", "pallas", "fp8_e4m3@token",
                     "fp8_e4m3@token", mode_a="token", mode_b="token",
                     pipeline="two_pass")
    audit = qlint.audit_graph_vs_census
    assert audit({"pallas_calls": {}}, [bf16]) == []
    assert audit({"pallas_calls": {"dgrad": 2}}, [bf16, fp8]) == []
    short = audit({"pallas_calls": {}}, [bf16, fp8])
    assert [f.check for f in short] == ["kernel_presence"]
    assert audit({"pallas_calls": {}},
                 [dataclasses.replace(bf16, pipeline="stream")])
    # and the paper's plan traced end to end: clean, dgrad cells two-pass
    cfg = get_config("tiny").replace(scan_layers=False,
                                    linear_impl="pallas")
    report = qlint.audit_train_graph(cfg, _tcfg(recipe="paper_fp4"),
                                     label="paper", compile_hlo=False)
    assert report.ok, report.violations()
    dgrad = [c for c in report.cells
             if c["cls"] == "ffn" and c["role"] == "dgrad"]
    assert dgrad and all(c["mode_a"] == c["mode_b"] == "pass"
                         and c["pipeline"] == "two_pass" for c in dgrad)


def test_seeded_violation_fails_the_gate():
    """Trace a quantized-dgrad plan but audit against the paper's
    protected plan: the role-safety check must catch the quantize on the
    BF16-protected dgrad path and fail the gate."""
    cfg = get_config("tiny").replace(scan_layers=False)
    protected = PrecisionPlan.uniform(RECIPES["paper_fp4"], cfg.n_layers)
    # sanity: the reference really protects the ffn dgrad path
    assert protected.layer(0).for_class("ffn").dgrad_g.is_passthrough
    report = qlint.audit_train_graph(cfg, _tcfg(), label="seeded",
                                     compile_hlo=False, plan=protected)
    viols = report.violations()
    assert viols, "seeded violation was not detected"
    assert any(f.check == "role_safety" and "protected" in f.message
               and "dgrad" in f.where for f in viols)
    assert not report.ok
    payload = expectations_payload([report])
    assert payload["n_violations"] > 0


def test_qdq_impl_routes_and_markers():
    cfg = get_config("tiny").replace(scan_layers=False, linear_impl="qdq")
    report = qlint.audit_train_graph(cfg, _tcfg(), label="qdq",
                                     compile_hlo=False)
    assert report.violations() == []
    # the attention core (role sdpa) is no matmul role of the plan
    quantized = [c for c in report.cells if c["cls"] in ("attn", "ffn")
                 and c["role"] != "sdpa"]
    assert quantized and all(c["route"] == "qdq" for c in quantized)
    # QDQ path stages qdq_* markers under the qrole scopes
    assert report.summary["qdq_markers"]


def test_fallback_cell_is_enumerated_with_reason(monkeypatch):
    """A block size the kernel grid cannot tile falls back to QDQ and the
    audit reports it as a fallback finding carrying the structured
    reason — not as a violation."""
    cfg = get_config("tiny").replace(scan_layers=False,
                                    linear_impl="pallas")
    base = RECIPES["fine_grained_fp4"]
    odd = dataclasses.replace(
        base, name="odd_block_test",
        ffn_linear=dataclasses.replace(
            base.ffn_linear,
            fwd_x=QuantSpec("fp4_e2m1", "block", block=64),
            fwd_w=QuantSpec("fp4_e2m1", "block", block=64)))
    monkeypatch.setitem(RECIPES, "odd_block_test", odd)
    report = qlint.audit_train_graph(cfg, _tcfg(recipe="odd_block_test"),
                                     label="odd", compile_hlo=False)
    falls = report.fallbacks()
    assert falls, "block64 spec should fall back to QDQ"
    assert any("unsupported_block" in f.message for f in falls)
    assert report.violations() == []


def test_decode_engine_audit_clean_packed():
    cfg = get_config("tiny").replace(linear_impl="pallas")
    report = qlint.audit_decode_graph(cfg, RECIPES["fine_grained_fp4"],
                                      label="dec", n_slots=2, max_len=32,
                                      compile_hlo=False)
    assert report.violations() == []
    routes = {(c["cls"], c["role"]): c["route"] for c in report.cells}
    # the protected (unpacked) lm head is a plain dot even when packed
    assert routes[("head", "fwd")] == "dot"
    assert routes[("ffn", "fwd")] == "pallas"


def test_trainer_qlint_report_hook():
    from repro.models import build_model
    from repro.train.trainer import Trainer

    cfg = get_config("tiny").replace(scan_layers=True,
                                    linear_impl="pallas")
    trainer = Trainer(build_model(cfg), _tcfg(), pipeline=None, jit=True)
    report = trainer.qlint_report()
    assert report.violations() == []
    census = report.summary["recompile"]
    assert census["n_compiled"] <= census["budget"]


def test_recompile_census_flags_foreign_plan():
    from repro.models import build_model
    from repro.train.trainer import Trainer

    cfg = get_config("tiny").replace(scan_layers=True)
    trainer = Trainer(build_model(cfg), _tcfg(), pipeline=None, jit=True)
    trainer._step_fn(trainer.plan)
    # a compiled step for a plan outside the schedule/controller set
    foreign = PrecisionPlan.uniform(RECIPES["bf16"], cfg.n_layers)
    trainer._step_fn(foreign)
    census, findings = qlint.recompile_census(trainer)
    assert any(f.check == "recompile" for f in findings)
    assert census["n_compiled"] == len(census["keys"])
