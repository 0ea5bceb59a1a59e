"""The system under test, built through its normal path for one cell.

``Program`` holds the cell's ``Trainer`` (``build_model``, ``TrainConfig``,
``Trainer``), makes its state from a seed with the benchmark's own
weights, and drives the first three steps through ``Trainer.train``, the
same call and feed as the window, reading what the check compares.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import data
import weights
from cells import Cell

__all__ = ["Program", "CHECKED_STEPS"]

CHECKED_STEPS = 3


class Program:
    state = None

    def __init__(self, cell: Cell, plant=None):
        from repro.configs.base import TrainConfig, get_config
        from repro.models import build_model
        from repro.train.train_step import (make_optimizer,
                                            train_step_shardings)
        from repro.train.trainer import Trainer

        self.cell = cell
        w, conf = cell.workload, cell.config
        cfg = get_config(conf["arch"]).replace(**conf["model"])
        self.model = build_model(cfg)
        self.tcfg = TrainConfig(
            recipe=w["recipe"], total_steps=w["total_steps"],
            global_batch=w["global_batch"], seq_len=w["seq_len"],
            learning_rate=w["learning_rate"], warmup_frac=w["warmup_frac"],
            mesh_shape=tuple(w["mesh_shape"]) if w["mesh_shape"] else None,
            fsdp=w["fsdp"], log_every=0, seed=0)
        self.trainer = Trainer(self.model, self.tcfg, None)
        if plant is not None:
            plant(self.trainer)
        self.lay = lay = weights.layout(cell.model)
        want = {tuple(k.key for k in p): tuple(s.shape) for p, s in
                jax.tree_util.tree_flatten_with_path(
                    self.model.abstract_params())[0]}
        got = {p: s for p, (s, _) in lay.items()}
        if want != got:
            raise RuntimeError(f"the program's parameters {want} differ "
                               f"from the benchmark's layout {got}")
        p_sh = o_sh = None
        self.c_sh = None
        if self.trainer.rules is not None:
            p_sh, o_sh, self.c_sh = train_step_shardings(
                self.model, self.tcfg, self.trainer.rules)[0][:3]
        self._init = jax.jit(lambda k: weights.init_params(lay, k),
                             out_shardings=p_sh)
        self._opt_init = jax.jit(make_optimizer(self.model, self.tcfg).init,
                                 out_shardings=o_sh)
        self.norms = jax.jit(lambda t: weights.slice_norms(t, lay))
        self.delta = jax.jit(lambda t, k: weights.slice_norms(jax.tree.map(
            jnp.subtract, t, weights.init_params(lay, k)), lay))

    def feed(self, seed: int) -> data.SyntheticTokens:
        w = self.cell.workload
        return data.SyntheticTokens(self.cell.model["vocab_size"],
                                    w["seq_len"], w["global_batch"], seed,
                                    **w["data"])

    def start(self, seed: int):
        """Fresh state from ``seed`` on the device, and the feed."""
        from repro.train.trainer import TrainState
        self.trainer.pipeline = self.feed(seed)
        self.trainer.history = []
        params = self._init(weights.seed_key(seed))
        opt_state = self._opt_init(params)
        comp = jnp.zeros((), jnp.float32)
        if self.c_sh is not None:
            comp = jax.device_put(comp, self.c_sh)
        return TrainState(params, opt_state, comp, 0)

    def first_steps(self, seed: int) -> Tuple[Dict, List[Dict], float]:
        """Runs the checked steps from ``seed``; returns the readings the
        check compares, their batches, and the warm step time in seconds.
        The state is left in ``self.state``."""
        self.state = self.start(seed)
        batches = [self.trainer.pipeline.batch(i)
                   for i in range(CHECKED_STEPS)]
        rows = np.concatenate([b["tokens"] for b in batches])
        if len({r.tobytes() for r in rows}) != len(rows):
            raise RuntimeError("the checked steps' rows are not all "
                               "different")
        self.train(1)
        # AdamW's first moment after one step is (1 - beta1) x the
        # clipped gradient it was given
        mu = self.state.opt_state.mu
        grads = np.asarray(self.norms(mu)) / (1 - self.tcfg.beta1)
        del mu
        self.train(CHECKED_STEPS - 1)
        hist = self.trainer.history
        readings = {
            "grad_norms": grads,
            "update_norms": np.asarray(self.delta(
                self.state.params, weights.seed_key(seed))),
            "losses": [r["loss"] for r in hist[:CHECKED_STEPS]],
        }
        warm = float(np.median([r["dt"] for r in hist[1:CHECKED_STEPS]]))
        return readings, batches, warm

    def train(self, n: int) -> None:
        """``n`` steps through ``Trainer.train``, one call a step, on
        ``self.state``.  The caller of ``Trainer.train`` keeps the state it
        passed in alive for the whole call; one step a call and no other
        reference keep two states on the chip (in and out), not three."""
        for _ in range(n):
            self.state = self.trainer.train(self.state, num_steps=1)
        jax.block_until_ready((self.state.params, self.state.opt_state))

    def free(self) -> None:
        self.state = None
