"""A run with the timed path broken underneath comes out not correct, once
for each fault the cell can have (tiny cell, CPU, chip check skipped)."""
import functools

import jax.numpy as jnp
import pytest

import tiny  # noqa: F401  (puts the benchmark on sys.path)
import harness
import repro.core.rollout as rollout
import repro.core.trainer as trainer


def state_unchanged(mp):
    real = trainer.train_step

    def broken(state, batch, **kw):
        new, m = real(state, batch, **kw)
        return new._replace(params=state.params, opt=state.opt), m

    mp.setattr(trainer, "train_step", broken)
    return ("grad_gap", "update_gap")


def half_batch(mp):
    real = trainer.Trainer.step

    def broken(self, batch, poison=False):
        batch = dict(batch)
        mask = batch["loss_mask"].copy()
        mask[mask.shape[0] // 2:] = 0.0
        batch["loss_mask"] = mask
        return real(self, batch, poison=poison)

    mp.setattr(trainer.Trainer, "step", broken)
    return ("grad_gap", "update_gap")


def token_altered(mp):
    real = rollout._engine_step

    @functools.wraps(real)
    def broken(params, st, block_tables, cfg, ec, kv_len_hint=None):
        new, finished = real(params, st, block_tables, cfg, ec,
                             kv_len_hint=kv_len_hint)
        H, T = st["tokens"].shape
        idx = jnp.arange(H)
        pos = jnp.minimum(st["n_cached"] + 1, T - 1)
        sampled = st["active"] & (st["n_cached"] + 1 >= st["prompt_len"])
        tok = new["tokens"][idx, pos]
        alt = jnp.where(sampled, (tok + 7) % cfg.vocab_size, tok)
        return dict(new, tokens=new["tokens"].at[idx, pos].set(alt)), finished

    mp.setattr(rollout, "_engine_step", broken)
    return ("engine_lp_gap",)


def install_left_out(mp):
    mp.setattr(rollout.GenerationEngine, "stream_weight_chunk",
               lambda self, token=None: False)
    return ("install_mismatch",)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered, install_left_out],
                         ids=lambda f: f.__name__)
def test_fault_makes_the_run_incorrect(fault, monkeypatch):
    caught = fault(monkeypatch)
    out = harness.run(tiny.cell(), 2 ** 31 + 99, 0.5, trace=False,
                      t_start=0.0, log=lambda s: None)
    assert not out["correct"]
    failed = [k for k, v in out["checks"].items()
              if not v["value"] <= v["limit"]]
    assert set(failed) & set(caught), out["checks"]
