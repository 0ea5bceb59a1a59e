"""Model / training configuration dataclasses and the arch registry."""
from __future__ import annotations

import dataclasses
import importlib
from typing import List, Optional, Tuple

__all__ = ["ModelConfig", "MoESettings", "MambaSettings", "LayerSpec",
           "TrainConfig", "ControllerSettings", "get_config", "list_archs",
           "SHAPE_CELLS", "ShapeCell"]


@dataclasses.dataclass(frozen=True)
class MoESettings:
    num_experts: int
    top_k: int
    capacity_factor: float = 1.25
    group_size: int = 2048      # router group size (GShard-style)
    every_k_layers: int = 1     # MoE FFN on layers with i % k == k-1
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2


@dataclasses.dataclass(frozen=True)
class MambaSettings:
    d_state: int = 128
    d_conv: int = 4
    headdim: int = 64
    expand: int = 2
    n_groups: int = 1
    chunk: int = 256


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str = "attn"        # 'attn' | 'mamba'
    cross: bool = False        # extra cross-attention sublayer
    ffn: str = "dense"         # 'dense' | 'moe' | 'none'


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                # dense|moe|vlm|audio|ssm|hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0          # 0 -> d_model // n_heads
    activation: str = "swiglu"  # gelu|swiglu|relu2
    norm: str = "rmsnorm"      # layernorm|rmsnorm
    pos_emb: str = "rope"      # rope|learned|none
    rope_theta: float = 500000.0
    max_seq_len: int = 8192
    sliding_window: int = 0    # 0 = full attention
    tie_embeddings: bool = False
    qkv_bias: bool = False
    # family extensions
    moe: Optional[MoESettings] = None
    mamba: Optional[MambaSettings] = None
    attn_layer_period: int = 0   # hybrid: attention at i % p == p//2
    cross_attn_period: int = 0   # vlm: cross sublayer at i % p == p-2
    n_encoder_layers: int = 0    # audio enc-dec
    n_frames: int = 1500         # audio frontend stub
    n_patches: int = 1601        # vlm frontend stub
    # numerics / compile strategy
    dtype: str = "bfloat16"
    # selects nothing: attention takes the flash kernels on a TPU where it
    # can and the chunked path elsewhere (models.attention); "chunked" stays
    # accepted because saved configurations pin it
    attention_impl: str = "chunked"
    linear_impl: str = "qdq"          # qdq (unfused sim) | pallas (fused
    #                                   quantize+matmul kernel, fwd+dgrad+wgrad)
    attention_chunk: int = 1024
    # serving-side KV cache payload format (None = compute dtype; an 8-bit
    # format name, e.g. "fp8_e4m3", stores uint8 codes + per-vector scales)
    kv_cache_format: Optional[str] = None
    scan_layers: bool = True
    unroll_attention: bool = False  # python-loop KV chunks (roofline mode)
    remat: bool = True
    remat_policy: str = "full"   # full | dots | none
    z_loss: float = 0.0
    loss_chunk: int = 0          # seq-chunked head+xent (big-vocab memory)
    optimizer: str = "adamw"     # adamw | adafactor

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def layer_specs(self) -> List[LayerSpec]:
        specs = []
        for i in range(self.n_layers):
            if self.family == "ssm":
                specs.append(LayerSpec("mamba", False, "none"))
                continue
            mixer = "attn"
            if self.attn_layer_period:
                p = self.attn_layer_period
                mixer = "attn" if i % p == p // 2 else "mamba"
            cross = bool(self.cross_attn_period
                         and i % self.cross_attn_period
                         == self.cross_attn_period - 2)
            ffn = "dense"
            if mixer == "mamba" and self.family == "ssm":
                ffn = "none"
            elif self.moe is not None:
                k = self.moe.every_k_layers
                ffn = "moe" if i % k == k - 1 else "dense"
            specs.append(LayerSpec(mixer, cross, ffn))
        return specs

    def scan_period(self) -> int:
        """Smallest repeating period of layer_specs (scan group size)."""
        specs = self.layer_specs()
        n = len(specs)
        for p in range(1, n + 1):
            if n % p == 0 and all(specs[i] == specs[i % p]
                                  for i in range(n)):
                return p
        return n

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ControllerSettings:
    """Adaptive-precision controller thresholds (telemetry.controller).

    All decision rules are opt-in: a threshold of 0.0 disables that rule, so
    the default ``ControllerSettings()`` reproduces the static §3.3 schedule.
    """

    # Dynamic target-precision switch: switch to the stage-2 recipe when the
    # EMA of the forward quant relative error crosses this value (OR at the
    # schedule's fixed fraction, whichever comes first).  0 = fraction only.
    switch_error_threshold: float = 0.0
    error_ema_decay: float = 0.9
    # Per-(layer, class) demotion: sustained overflow (clip rate) above the
    # threshold for ``demote_patience`` consecutive steps promotes that one
    # plan cell to FP8 (a ``PrecisionPlan.promote`` transform — one noisy
    # layer no longer demotes the whole class).  0 = disabled.
    demote_overflow_threshold: float = 0.0
    demote_patience: int = 8
    # Loss-spike rollback: loss > spike_factor * EMA(loss) triggers a restore
    # of the last checkpoint + ``replay_steps`` steps at the target (high)
    # precision before FP4 resumes.  0 = disabled.
    spike_factor: float = 0.0
    loss_ema_decay: float = 0.9
    spike_warmup: int = 20       # steps of EMA warmup before spikes arm
    replay_steps: int = 5
    max_rollbacks: int = 2
    # Controller-driven LR backoff: each rollback multiplies the LR scale by
    # ``lr_backoff`` (e.g. 0.5); the scale then recovers geometrically to
    # 1.0 over ~``lr_recovery_steps`` clean steps.  The scale is a traced
    # scalar input of the step graph (no recompile) and persists in the
    # controller's checkpoint state.  0 = disabled.
    lr_backoff: float = 0.0
    lr_recovery_steps: int = 50
    # Telemetry-driven plan search (telemetry.controller.PlanSearcher):
    # every ``plan_search_every`` steps the searcher finalizes a measured
    # (cost, quant-error) frontier point for the running plan and applies
    # one greedy edit — promote the worst-error (layer, class) cell to FP8,
    # or, when the cost budget is exhausted, demote the healthiest cell's
    # wgrad roles to FP4 (``PrecisionPlan.demote``, the asymmetric
    # role-subset transform; dgrad is never demoted).  Search runs in
    # stage 1 only and its state (per-cell error EMAs, applied edits,
    # frontier) persists in the controller checkpoint state, so resume is
    # bit-exact.  Requires ``TrainConfig.telemetry``.
    plan_search: bool = False
    plan_search_every: int = 10       # steps between search moves
    plan_search_cost_budget: float = 0.0   # max plan_cost (1.0 = BF16
    #                                        baseline); 0 = unbounded
    plan_search_max_edits: int = 8    # total edits before the search stops
    plan_search_demote_threshold: float = 0.0  # demote cells whose error
    #                                    EMA is below this; 0 = never demote


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    recipe: str = "paper_fp4"
    total_steps: int = 200
    global_batch: int = 8
    seq_len: int = 512
    microbatch: int = 0          # 0 = no gradient accumulation
    learning_rate: float = 6e-4
    warmup_frac: float = 0.0015
    min_lr_frac: float = 0.1
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    seed: int = 0
    # fault tolerance
    checkpoint_every: int = 0    # 0 = disabled
    checkpoint_dir: str = ""
    keep_checkpoints: int = 3
    async_checkpoint: bool = False
    # distributed extras
    grad_compression: str = "none"   # none | fp8 (error-feedback)
    # Mesh-native training (Trainer builds the mesh + sharding rules when
    # mesh_shape is set; None keeps the single-device step).  mesh_axes
    # defaults to ('data', 'model') truncated/extended to len(mesh_shape)
    # by Trainer.  fsdp shards the embed params over the data axes — turn
    # it OFF when combining with grad_compression='fp8' (the manual-DP
    # compressed reduction needs data-replicated params).
    mesh_shape: Optional[Tuple[int, ...]] = None
    mesh_axes: Optional[Tuple[str, ...]] = None
    fsdp: bool = True
    log_every: int = 10
    # quantization telemetry + adaptive precision (telemetry subsystem)
    telemetry: bool = False          # in-graph quant-health stats as step aux
    telemetry_every: int = 1         # sample stats every N steps (amortizes
    #                                  the tap cost; both graphs stay static)
    telemetry_jsonl: str = ""        # append per-step rows to this JSONL file
    target_recipe: str = "bf16"      # stage-2 recipe of the §3.3 schedule
    controller: Optional[ControllerSettings] = None  # adaptive controller
    # Layer-resolved precision plan (core.recipe.PrecisionPlan) built from
    # ``recipe``: 'uniform' (every layer runs the class template) |
    # 'first_last_k' (first/last ``plan_k`` layers protected at FP8) |
    # 'ramp' (linear FP8->FP4 ramp over the first ``plan_frac`` of depth).
    plan_preset: str = "uniform"
    plan_k: int = 2                  # first_last_k: protected depth
    plan_frac: float = 0.5           # ramp: ramp fraction of the depth
    # Measured-performance observability (telemetry.profiler):
    # profiler_warmup steps are excluded from step-time statistics
    # (compile + autotune); cost_calibration optionally points at a
    # speed_factors.v1 JSON (kernel_bench --measure-speed) so the plan
    # searcher prices plans by measured wall clock instead of the paper's
    # theoretical bit-width factors.  Empty = paper factors (bit-exact
    # legacy behavior).
    profiler_warmup: int = 2
    cost_calibration: str = ""


# ---------------------------------------------------------------------------
# Assigned input-shape cells (LM-family: seq_len x global_batch).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


SHAPE_CELLS: Tuple[ShapeCell, ...] = (
    ShapeCell("train_4k", 4096, 256, "train"),
    ShapeCell("prefill_32k", 32768, 32, "prefill"),
    ShapeCell("decode_32k", 32768, 128, "decode"),
    ShapeCell("long_500k", 524288, 1, "decode"),
)

ARCHS = [
    "nemotron-4-15b", "llama3.2-3b", "h2o-danube-3-4b", "granite-34b",
    "mixtral-8x22b", "olmoe-1b-7b", "llama-3.2-vision-90b", "whisper-base",
    "mamba2-780m", "jamba-1.5-large-398b",
    # paper's own configs
    "gpt2-125m", "gpt2-335m", "gpt2-774m", "llama-125m", "llama-1b",
    # test config
    "tiny",
]


def _module_name(arch: str) -> str:
    return "repro.configs." + arch.replace("-", "_").replace(".", "_")


def get_config(arch: str) -> ModelConfig:
    """Load ``src/repro/configs/<arch>.py`` and return its CONFIG."""
    mod = importlib.import_module(_module_name(arch))
    return mod.CONFIG


def list_archs() -> List[str]:
    return list(ARCHS)
