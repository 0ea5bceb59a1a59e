"""Fault-tolerant training loop with the paper's two-stage schedule.

Responsibilities:
  * precision plans: the trainer resolves ``TrainConfig.recipe`` into a
    layer-resolved ``PrecisionPlan`` (``TrainConfig.plan_preset`` selects a
    depth-graded constructor: uniform | first_last_k | ramp) and holds one
    jitted step graph per active plan;
  * target-precision schedule (§3.3): low-precision step graph for stage 1,
    high-precision graph for the final 5-10% of steps (stage-2 recipe
    configurable via ``TrainConfig.target_recipe``; the switch is a plan
    transform);
  * adaptive precision (``TrainConfig.controller``): the telemetry-driven
    ``PrecisionController`` picks the active plan per step (dynamic early
    switch, per-(layer, class) demotion, LR backoff, and — with
    ``plan_search`` — the greedy cost-vs-quant-error plan searcher, whose
    ``ModelDims`` pricing the trainer derives from the model config) and
    can request a loss-spike rollback — restore the last checkpoint and
    replay at the target precision;
  * checkpoint/restart: atomic step-indexed checkpoints of params + optimizer
    + compression residuals + step (+ controller state + active plan); the
    plan is re-derived from the restored step and controller state, so
    ``resume()`` continues bit-exact across the switch boundary AND across
    a per-layer demotion boundary (both tested);
  * straggler monitoring: per-step wall-time EMA outlier detection with a
    pluggable action; flags are folded into the history rows and flagged
    steps are emitted as ``{"event": "straggler", ...}`` JSONL events;
  * measured-performance observability: every step is timed with device
    sync into a ``telemetry.profiler.StepTimer`` (``step_time_summary()``
    reports p50/p95/p99, tokens/sec, and MFU on a TPU); each step writes
    the ``jax.profiler`` host spans ``data``, ``step`` (holding
    ``dispatch`` and ``wait``) and ``host``, each with the stat
    ``step_num``; each history row carries the same boundaries as
    counters: ``t_host`` (from the previous step's sync to this step's
    start: its bookkeeping, the loop, and whatever the caller did between
    ``train`` calls; 0 on a trainer's first step), ``t_data``,
    ``t_dispatch``, ``t_wait`` (seconds, ``time.perf_counter``; the four
    add up to the wall time since the previous step's sync, and
    ``t_dispatch + t_wait`` is ``dt``) and ``compiles`` (backend
    compilations during the step, ``telemetry.profiler.compile_count``);
    and the JSONL stream goes through the host-offloaded
    ``AsyncJsonlWriter`` (bounded queue + writer thread) so logging never
    blocks the step;
  * eval + metrics history; optional JSONL telemetry log
    (``TrainConfig.telemetry_jsonl``);
  * mesh-native SPMD: pass ``rules=ShardingRules(...)`` (or set
    ``TrainConfig.mesh_shape`` and the trainer builds the mesh +
    ``default_rules`` itself) and every step graph is jitted with
    ``NamedSharding`` in/out specs; ``init_state`` places params, optimizer
    state and compression residuals on the mesh.  With
    ``grad_compression='fp8'`` and a data axis > 1, the step runs the
    quantize-before-communicate gradient reduction (requires
    ``fsdp=False``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import TrainConfig
from repro.core.cost_model import CostCalibration, ModelDims
from repro.core.recipe import RECIPES, PrecisionPlan
from repro.core.schedule import TargetPrecisionSchedule
from repro.distributed.sharding import ShardingRules, default_rules
from repro.models.model import Model
from repro.optim import init_compression_state
from repro.telemetry.controller import PrecisionController
from repro.telemetry.profiler import (StepTimer, compile_count,
                                      device_peak_flops, phase_span,
                                      train_step_flops)
from repro.telemetry.writer import AsyncJsonlWriter
from repro.train.train_step import (make_optimizer, make_train_step,
                                    train_step_shardings)

__all__ = ["Trainer", "TrainState", "StepTimeMonitor"]


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    comp_state: Any
    step: int


class StepTimeMonitor:
    """EMA-based straggler detector (distributed-runtime hook)."""

    def __init__(self, factor: float = 2.5, warmup: int = 5,
                 action: Optional[Callable[[int, float, float], None]] = None):
        self.factor = factor
        self.warmup = warmup
        self.ema: Optional[float] = None
        self.n = 0
        self.flagged: List[int] = []
        self.action = action

    def record(self, step: int, dt: float) -> bool:
        self.n += 1
        if self.ema is None:
            self.ema = dt
            return False
        is_straggler = (self.n > self.warmup
                        and dt > self.factor * self.ema)
        if is_straggler:
            self.flagged.append(step)
            if self.action:
                self.action(step, dt, self.ema)
        # EMA updated with clipped dt so one outlier doesn't poison it.
        self.ema = 0.9 * self.ema + 0.1 * min(dt, 3 * self.ema)
        return is_straggler


class Trainer:
    def __init__(self, model: Model, tcfg: TrainConfig,
                 pipeline, *, jit: bool = True,
                 eval_pipeline=None,
                 rules: Optional[ShardingRules] = None):
        self.model = model
        self.tcfg = tcfg
        self.pipeline = pipeline
        self.eval_pipeline = eval_pipeline
        self.rules = rules if rules is not None else self._build_rules()
        self.recipe = RECIPES[tcfg.recipe]   # class template (for reports)
        n_layers = model.cfg.n_layers
        self.plan: PrecisionPlan = self._build_plan(n_layers)
        self.schedule = TargetPrecisionSchedule(
            self.plan, tcfg.total_steps,
            target=PrecisionPlan.uniform(RECIPES[tcfg.target_recipe],
                                         n_layers))
        self._steps: Dict[tuple, Callable] = {}
        self._jit = jit
        self.monitor = StepTimeMonitor()
        self.history: List[Dict[str, float]] = []
        self.ckpt: Optional[CheckpointManager] = None
        if tcfg.checkpoint_every and tcfg.checkpoint_dir:
            self.ckpt = CheckpointManager(tcfg.checkpoint_dir,
                                          keep=tcfg.keep_checkpoints,
                                          async_save=tcfg.async_checkpoint)
        # layer-resolved flops: plan-searcher cost pricing + MFU accounting
        self.dims = ModelDims.from_config(model.cfg, seq_len=tcfg.seq_len)
        # measured wall-clock speed factors (kernel_bench --measure-speed);
        # None keeps the paper's theoretical factors bit-exact
        self.calibration: Optional[CostCalibration] = None
        if tcfg.cost_calibration:
            self.calibration = CostCalibration.from_json(tcfg.cost_calibration)
        self.controller: Optional[PrecisionController] = None
        if tcfg.controller is not None:
            self.controller = PrecisionController(self.schedule,
                                                  tcfg.controller,
                                                  dims=self.dims,
                                                  calibration=self.calibration)
        # Host-offloaded metrics pipeline: rows/events go through a bounded
        # queue to a writer thread so disk latency never lands in step time.
        self.writer: Optional[AsyncJsonlWriter] = None
        if tcfg.telemetry_jsonl:
            self.writer = AsyncJsonlWriter(tcfg.telemetry_jsonl)
        self.timer = StepTimer(warmup=tcfg.profiler_warmup)
        self._t_synced: Optional[float] = None   # clock at the last sync
        compile_count()   # the listener must be on before the first step

    # ------------------------------------------------------------------

    def _build_rules(self) -> Optional[ShardingRules]:
        """Mesh + default sharding rules from TrainConfig.mesh_shape."""
        shape = self.tcfg.mesh_shape
        if shape is None:
            return None
        from repro.distributed.mesh import make_mesh
        axes = self.tcfg.mesh_axes or ("data", "model")[:len(shape)]
        if len(axes) != len(shape):
            raise ValueError(f"mesh_axes {axes} does not match "
                             f"mesh_shape {shape}")
        mesh = make_mesh(tuple(shape), tuple(axes))
        return default_rules(mesh, self.model.cfg, fsdp=self.tcfg.fsdp)

    def _build_plan(self, n_layers: int) -> PrecisionPlan:
        """Resolve TrainConfig.recipe/plan_preset into a PrecisionPlan."""
        preset = self.tcfg.plan_preset
        if preset == "uniform":
            return PrecisionPlan.uniform(self.recipe, n_layers)
        if preset == "first_last_k":
            return PrecisionPlan.first_last_k(self.recipe, n_layers,
                                              k=self.tcfg.plan_k)
        if preset == "ramp":
            return PrecisionPlan.ramp(self.recipe, n_layers,
                                      frac=self.tcfg.plan_frac)
        raise ValueError(f"unknown plan_preset {preset!r}")

    # ------------------------------------------------------------------

    def init_state(self, seed: Optional[int] = None) -> TrainState:
        key = jax.random.PRNGKey(self.tcfg.seed if seed is None else seed)
        params = self.model.init(key, jnp.float32)
        opt = make_optimizer(self.model, self.tcfg)
        opt_state = opt.init(params)
        use_fp8 = self.tcfg.grad_compression == "fp8"
        dp_size = self.rules.dp_size if self.rules is not None else 1
        comp_state = (init_compression_state(params, dp_size=dp_size)
                      if use_fp8 else jnp.zeros((), jnp.float32))
        if self.rules is not None:
            # Place the state on the mesh up front so the first step does
            # not pay a resharding transfer (and so donation stays legal).
            p_sh, o_sh, c_sh, _, _, _ = train_step_shardings(
                self.model, self.tcfg, self.rules)[0]
            # comp_state too, even as the replicated scalar placeholder:
            # left uncommitted, its replicated step output would not match
            # it and the second step would compile again.
            params = jax.device_put(params, p_sh)
            opt_state = jax.device_put(opt_state, o_sh)
            comp_state = jax.device_put(comp_state, c_sh)
        return TrainState(params, opt_state, comp_state, 0)

    def _step_fn(self, plan: PrecisionPlan,
                 telemetry: Optional[bool] = None) -> Callable:
        tel = self.tcfg.telemetry if telemetry is None else telemetry
        key = (plan, tel)   # plans are frozen/hashable; content-addressed
        if key not in self._steps:
            tcfg = (self.tcfg if tel == self.tcfg.telemetry
                    else dataclasses.replace(self.tcfg, telemetry=tel))
            self._steps[key] = make_train_step(
                self.model, tcfg, plan, jit=self._jit, donate=False,
                rules=self.rules)
        return self._steps[key]

    def qlint_report(self, *, compile_hlo: bool = False):
        """Static precision-flow audit (``analysis.qlint``) of the active
        plan's step graph plus a recompile-budget census over every step
        graph this trainer has compiled.  Trace-only — nothing executes.
        """
        from repro.analysis import qlint
        return qlint.audit_trainer(self, compile_hlo=compile_hlo)

    # ------------------------------------------------------------------

    def resume(self) -> Optional[TrainState]:
        """Restore latest intact checkpoint, or None if there is none.

        The active plan is *re-derived* from the restored step (schedule
        fraction + persisted controller state, including per-layer
        demotions), so resuming across the precision-switch boundary or a
        demotion boundary continues with the correct graph.
        """
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return None
        ref = self.init_state()
        tree = {"params": ref.params, "opt_state": ref.opt_state,
                "comp_state": ref.comp_state}
        restored, extra = self.ckpt.restore(tree)
        if self.controller is not None and "controller" in extra:
            self.controller.load_state(extra["controller"])
        return TrainState(restored["params"], restored["opt_state"],
                          restored["comp_state"], int(extra["step"]))

    def save(self, state: TrainState) -> None:
        if self.ckpt is None:
            return
        tree = {"params": state.params, "opt_state": state.opt_state,
                "comp_state": state.comp_state}
        # The active plan's full table is persisted for forensics /
        # external tooling; resume() re-derives it from step + controller
        # state (the authoritative source), so the two can never diverge.
        extra = {"recipe": self.recipe.name,
                 "plan": self._active_plan(state.step).to_dict()}
        if self.controller is not None:
            extra["controller"] = self.controller.state_dict()
        self.ckpt.save(state.step, tree, extra=extra)

    # ------------------------------------------------------------------

    def train(self, state: Optional[TrainState] = None,
              num_steps: Optional[int] = None,
              log: Optional[Callable[[str], None]] = None) -> TrainState:
        state = state or (self.resume() or self.init_state())
        total = self.tcfg.total_steps
        end = min(total, state.step + (num_steps or total))
        log = log or (lambda s: None)
        while state.step < end:
            step = state.step
            plan = self._active_plan(step)
            if self.controller is None and self.schedule.is_switch_boundary(
                    step):
                log(f"[schedule] step {step}: switching to target precision "
                    f"({self.schedule.target_plan.name})")
            # telemetry sampling: every N-th step runs the instrumented
            # graph, the rest run the stat-free one (both static graphs)
            tel_on = self.tcfg.telemetry and (
                self.tcfg.telemetry_every <= 1
                or step % self.tcfg.telemetry_every == 0)
            fn = self._step_fn(plan, telemetry=tel_on)
            lr_scale = (self.controller.lr_scale
                        if self.controller is not None else 1.0)
            compiles = compile_count()
            t_start = time.perf_counter()
            with phase_span("data", step_num=step):
                batch = {k: jnp.asarray(v)
                         for k, v in self.pipeline.batch(step).items()}
            # dispatch + device sync is the measured step: block on an
            # output before reading the clock so dt is the device step
            # time, not just the host dispatch.
            with phase_span("step", step_num=step):
                t0 = time.perf_counter()
                with phase_span("dispatch", step_num=step):
                    params, opt_state, comp_state, metrics = fn(
                        state.params, state.opt_state, state.comp_state,
                        batch, jnp.asarray(step, jnp.int32),
                        jnp.asarray(lr_scale, jnp.float32))
                t1 = time.perf_counter()
                with phase_span("wait", step_num=step):
                    jax.block_until_ready(metrics["loss"])
                t2 = time.perf_counter()
            dt = t2 - t0
            # the loop's wall time since the last step's sync, split so the
            # four parts add up to it exactly
            phases = {"t_host": (0.0 if self._t_synced is None
                                 else t_start - self._t_synced),
                      "t_data": t0 - t_start, "t_dispatch": t1 - t0,
                      "t_wait": t2 - t1,
                      "compiles": compile_count() - compiles}
            self._t_synced = t2
            self.timer.record(dt)
            straggler = self.monitor.record(step, dt)
            state = TrainState(params, opt_state, comp_state, step + 1)
            # everything below is host-side bookkeeping, off the device
            # critical path (the async writer never blocks here)
            with phase_span("host", step_num=step):
                if straggler:
                    log(f"[straggler] step {step} took {dt:.2f}s "
                        f"(ema {self.monitor.ema:.2f}s)")
                    if self.writer is not None:
                        self.writer.write({"event": "straggler",
                                           "step": step, "dt": dt,
                                           "ema": self.monitor.ema,
                                           "factor": self.monitor.factor})
                row = {k: float(np.asarray(v)) for k, v in metrics.items()}
                row["step"] = step
                row["recipe"] = plan.name
                row["dt"] = dt
                row["straggler"] = straggler
                row.update(phases)
                self.history.append(row)
                if self.writer is not None:
                    self.writer.write(row)
                if self.tcfg.log_every and step % self.tcfg.log_every == 0:
                    log(f"step {step:5d} loss {row['loss']:.4f} "
                        f"gnorm {row['grad_norm']:.3f} lr {row['lr']:.2e} "
                        f"[{plan.name}] {dt*1000:.0f}ms")
                # controller first: a loss-spike rollback must restore a
                # checkpoint from BEFORE the spiked update, so the boundary
                # save below happens only after the row was judged healthy
                # (or after the restore, persisting the armed replay window).
                if self.controller is not None:
                    state = self._apply_controller_events(
                        state, self.controller.observe(step, row), log)
                if (self.ckpt is not None and self.tcfg.checkpoint_every
                        and (step + 1) % self.tcfg.checkpoint_every == 0):
                    self.save(state)
        if self.ckpt is not None:
            self.ckpt.wait()
        if self.writer is not None:
            self.writer.flush()   # log is complete once train() returns
        return state

    def step_time_summary(self) -> Dict[str, float]:
        """Measured step-time statistics for this trainer's run so far:
        p50/p95/p99/mean (ms), tokens/sec at the median step, and — on a
        TPU only — MFU from the model's ``ModelDims`` flops over every chip
        of the mesh (``telemetry.profiler`` summary)."""
        tokens = self.tcfg.global_batch * self.tcfg.seq_len
        devices = (list(self.rules.mesh.devices.flat)
                   if self.rules is not None else jax.devices()[:1])
        peak = None
        if devices[0].platform == "tpu":
            peak = len(devices) * device_peak_flops(devices[0])
        return self.timer.summary(
            tokens_per_step=tokens,
            flops_per_step=train_step_flops(self.dims, tokens),
            peak_flops=peak)

    # ------------------------------------------------------------------

    def _active_plan(self, step: int) -> PrecisionPlan:
        if self.controller is not None:
            return self.controller.active_plan(step)
        return self.schedule.plan_at(step)

    def _apply_controller_events(self, state: TrainState, events,
                                 log: Callable[[str], None]) -> TrainState:
        """Apply controller decisions.  switch/demote only alter which
        plan ``_active_plan`` selects next step; rollback restores the
        last checkpoint and arms the high-precision replay window (plus
        the LR backoff, which the controller already folded into its
        ``lr_scale``)."""
        ctrl = self.controller
        for ev in events:
            if self.writer is not None:
                self.writer.write(ev)
            if ev["event"] == "switch":
                log(f"[controller] step {ev['step']}: quant-error EMA "
                    f"{ev['error_ema']:.4f} crossed threshold -> early "
                    f"switch to {ev['to']}")
            elif ev["event"] == "demote":
                log(f"[controller] step {ev['step']}: sustained overflow "
                    f"({ev['overflow']:.4f}) -> demoting "
                    f"{ev['cell']} to FP8")
            elif ev["event"] == "frontier_point":
                log(f"[controller] step {ev['step']}: frontier point "
                    f"cost {ev['cost']:.3f} / quant-err {ev['error']:.4f} "
                    f"({ev['plan']})")
            elif ev["event"] == "plan_search":
                log(f"[controller] step {ev['step']}: plan search "
                    f"{ev['op']} {ev['cell']} -> cost {ev['cost']:.3f}")
            elif ev["event"] == "plan_search_done":
                log(f"[controller] step {ev['step']}: plan search done "
                    f"({ev['edits']} edits, "
                    f"{ev['frontier_size']}-point frontier)")
            elif ev["event"] == "rollback":
                # keep the attempt counter (guards infinite loops) and the
                # just-applied LR backoff across the checkpointed
                # controller state resume() reloads
                attempts = ctrl.rollbacks
                backed_off = ctrl.lr_scale
                restored = self.resume()
                if restored is None:
                    log(f"[controller] step {ev['step']}: loss spike "
                        f"({ev['loss']:.3f} vs ema {ev['loss_ema']:.3f}) "
                        "but no checkpoint to roll back to")
                    continue
                ctrl.rollbacks = max(ctrl.rollbacks, attempts)
                ctrl.lr_scale = min(ctrl.lr_scale, backed_off)
                ctrl.begin_replay(restored.step)
                log(f"[controller] step {ev['step']}: loss spike "
                    f"({ev['loss']:.3f} vs ema {ev['loss_ema']:.3f}) -> "
                    f"rollback to step {restored.step}, replaying "
                    f"{ctrl.cfg.replay_steps} steps at "
                    f"{self.schedule.target_plan.name}"
                    + (f", lr_scale {ctrl.lr_scale:.3f}"
                       if ctrl.cfg.lr_backoff > 0 else ""))
                state = restored
        return state

    # ------------------------------------------------------------------

    def evaluate(self, state: TrainState, n_batches: int = 8,
                 recipe=None) -> Dict[str, float]:
        """``recipe`` may be a PrecisionRecipe template or a PrecisionPlan
        (default: the BF16 baseline)."""
        from repro.train.train_step import make_eval_step
        recipe = recipe or RECIPES["bf16"]
        pipeline = self.eval_pipeline or self.pipeline
        fn = make_eval_step(self.model, recipe, jit=self._jit,
                            rules=self.rules)
        losses = []
        for i in range(n_batches):
            batch = {k: jnp.asarray(v)
                     for k, v in pipeline.batch(10_000_000 + i).items()}
            m = fn(state.params, batch)
            losses.append(float(np.asarray(m["loss"])))
        val_loss = float(np.mean(losses))
        return {"val_loss": val_loss, "val_ppl": float(np.exp(val_loss))}
