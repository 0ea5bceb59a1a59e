"""Drive a whole run at a tiny size on the CPU, the chip look skipped, with
the timed path broken underneath: the check must say ``correct`` false for
each fault a training cell can have, and true for the sound program.  The
tiny cell compares the numbers the FP4 cells compare (``grad_gap`` and
``update_gap``; ``loss_gap`` has no reading there that separates), so a
fault is caught here as it would be at the cells' size."""
import jax
import pytest

import _bench
from tiny import LIMITS, cell as tiny_cell

run = _bench.module("run")

SEED = 3_000_000_019


def _wrap_step(trainer, edit):
    orig = trainer._step_fn

    def step_fn(plan, telemetry=None):
        fn = orig(plan, telemetry)

        def broken(params, opt, comp, batch, step, lr):
            out = fn(params, opt, comp, batch, step, lr)
            return edit((params, opt, comp, batch), out)
        return broken
    trainer._step_fn = step_fn


def state_unchanged(trainer):
    _wrap_step(trainer, lambda ins, out: ins[:3] + (out[3],))


def half_batch(trainer):
    orig = trainer.model.loss
    trainer.model.loss = lambda p, b, plan: orig(
        p, {k: v[: v.shape[0] // 2] for k, v in b.items()}, plan)


def answer_altered(trainer):
    """A training step's answer is the state it returns: scale the new
    weights by 1.01 where the step produces them."""
    def edit(ins, out):
        return (jax.tree.map(lambda p: p * 1.01, out[0]),) + out[1:]
    _wrap_step(trainer, edit)


def _run(plant):
    limits = {k: v for k, v in LIMITS.items() if k != "loss_gap"}
    out = run.run_cell(tiny_cell(limits=limits), SEED, 0.3, False,
                       jax.devices()[:1], plant=plant)
    assert list(out)[-1] == "checked"
    return out


def test_sound_run_is_correct():
    out = _run(None)
    assert out["correct"], out["checked"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   answer_altered], ids=lambda f: f.__name__)
def test_fault_is_caught(fault):
    out = _run(fault)
    assert not out["correct"], out["checked"]
