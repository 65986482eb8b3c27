"""Each cell's driver, rehearsed at a tiny size on the CPU through the
benchmark's own functions: a sound run is correct, and a run with the
timed path broken underneath is not (one test per fault the cell can
have). The look for a chip is skipped; nothing else is."""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import perfbench_tiny as T  # noqa: E402

TINY_LIMITS = T.TINY_LIMITS


def _correct(out) -> bool:
    return bool(out.checks) and all(c.ok for c in out.checks)


def test_train_cell_is_correct_and_its_control_is_not():
    run, out = T.tiny_run("granite-train", limits=TINY_LIMITS, readings=True)
    assert _correct(out), [(c.name, c.value) for c in out.checks]
    assert out.end_to_end["train_tokens_per_s"] > 0
    assert out.attempted >= 1
    for fault, nums in out.records["readings"].items():
        assert any(v > TINY_LIMITS[k] for k, v in nums.items()), (fault, nums)


def test_train_fault_state_unchanged(monkeypatch):
    import repro.train.steps as steps

    def frozen(grads, state, params, cfg, lr_scale=1.0, norm_weights=None):
        import jax.numpy as jnp

        return params, dict(state, step=state["step"] + 1), {
            "grad_norm": jnp.float32(0), "lr": jnp.float32(0)}

    monkeypatch.setattr(steps, "adamw_update", frozen)
    _, out = T.tiny_run("granite-train", limits=TINY_LIMITS)
    assert not _correct(out)


def test_train_fault_half_batch(monkeypatch):
    import repro.train.steps as steps

    orig = steps.cross_entropy

    def half(logits, targets, real_vocab):
        n = logits.shape[0] // 2
        return orig(logits[:n], targets[:n], real_vocab)

    monkeypatch.setattr(steps, "cross_entropy", half)
    _, out = T.tiny_run("granite-train", limits=TINY_LIMITS)
    assert not _correct(out)
