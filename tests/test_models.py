"""Per-arch smoke tests (reduced same-family variants): one forward and one
train step on CPU, asserting output shapes and no NaNs; plus decode/forward
numerical consistency across every attention/mixer family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.core.algo import RLConfig
from repro.core.trainer import Trainer
from repro.models import model as M
from repro.sharding import tree_values

KEY = jax.random.PRNGKey(0)


def _setup(arch):
    cfg = smoke_config(get_config(arch))
    params = tree_values(M.init_params(cfg, KEY))
    return cfg, params


def _inputs(cfg, B=2, S=32):
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    kw = {}
    if cfg.n_prefix_tokens:
        kw["prefix_embeds"] = jax.random.normal(
            KEY, (B, cfg.n_prefix_tokens, cfg.d_model), jnp.float32)
    return toks, pos, kw


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward(arch):
    cfg, params = _setup(arch)
    B, S = 2, 32
    toks, pos, kw = _inputs(cfg, B, S)
    out = M.forward(params, toks, pos, cfg, **kw)
    assert out["logits"].shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(out["logits"], np.float32)).all()
    if cfg.use_value_head:
        assert out["values"].shape == (B, S)
    if cfg.use_mtp:
        assert out["mtp_logits"].shape == (B, S - 1, cfg.vocab_size)


# prefix-token (prompt-tuning) archs are excluded at parametrize time
# rather than runtime-skipped: the RL trainer path is text-prompt based,
# permanently — there is nothing a skip would be waiting on
TRAIN_ARCH_IDS = [a for a in ARCH_IDS
                  if not get_config(a).n_prefix_tokens]


@pytest.mark.parametrize("arch", TRAIN_ARCH_IDS)
def test_smoke_train_step(arch):
    cfg, params = _setup(arch)
    B, S = 2, 32
    toks, pos, _ = _inputs(cfg, B, S)
    batch = {
        "tokens": toks,
        "positions": pos,
        "segment_ids": jnp.ones((B, S), jnp.int32),
        "loss_mask": jnp.ones((B, S), jnp.float32),
        "behavior_logprobs": jnp.full((B, S), -1.0, jnp.float32),
        "rewards": jnp.ones((B, S), jnp.float32) * 0.5,
    }
    tr = Trainer(cfg, params)
    m = tr.step(batch)
    assert np.isfinite(m["loss"])
    assert np.isfinite(m["grad_norm"])
    assert tr.version == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch):
    cfg, params = _setup(arch)
    cfg = dataclasses.replace(cfg, use_mtp=False)
    B, S = 2, 16
    toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S + 1)[None], (B, S + 1))
    kw = {}
    if cfg.n_prefix_tokens:
        kw["prefix_embeds"] = jax.random.normal(
            KEY, (B, cfg.n_prefix_tokens, cfg.d_model), jnp.float32)
    full = M.forward(params, toks, pos, cfg, **kw)
    pre = M.forward(params, toks[:, :S], pos[:, :S], cfg, return_cache=True, **kw)
    cache = pre["cache"]

    def pad(k, v):  # headroom so decode can write at index S
        if k in ("k", "v"):   # head-major (L,B,KV,CL,D)
            return jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, 4), (0, 0)))
        if k in ("c_kv", "k_rope"):
            return jnp.pad(v, ((0, 0), (0, 0), (0, 4), (0, 0)))
        return v

    cache = {k: pad(k, v) for k, v in cache.items()}
    # multimodal prefix rows live at the head of the cache: offset the write
    # index and RoPE positions by n_prefix
    npre = cfg.n_prefix_tokens if cfg.n_prefix_tokens else 0
    dout = M.decode_step(params, toks[:, S:S + 1], pos[:, S:S + 1] + npre,
                         cache, jnp.int32(S + npre), cfg)
    a = np.asarray(full["logits"][:, S], np.float32)
    b = np.asarray(dout["logits"][:, 0], np.float32)
    np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_segment_ids_isolate_sequences():
    """Packed rows must not attend across segment boundaries."""
    cfg = smoke_config(get_config("llama3-8b"))
    params = tree_values(M.init_params(cfg, KEY))
    S = 32
    toks = jax.random.randint(KEY, (1, S), 3, cfg.vocab_size)
    pos = jnp.concatenate([jnp.arange(16), jnp.arange(16)])[None]
    seg = jnp.concatenate([jnp.ones(16), jnp.full(16, 2)])[None].astype(jnp.int32)
    packed = M.forward(params, toks, pos, cfg, segment_ids=seg)
    solo = M.forward(params, toks[:, 16:], pos[:, 16:], cfg,
                     segment_ids=seg[:, 16:])
    np.testing.assert_allclose(
        np.asarray(packed["logits"][0, 16:], np.float32),
        np.asarray(solo["logits"][0], np.float32), atol=2e-4, rtol=2e-4)


def test_sliding_window_limits_attention():
    cfg = dataclasses.replace(smoke_config(get_config("llama3-8b")),
                              attention_variant="sliding_window",
                              sliding_window=8)
    params = tree_values(M.init_params(cfg, KEY))
    S = 32
    toks = jax.random.randint(KEY, (1, S), 0, cfg.vocab_size)
    pos = jnp.arange(S)[None]
    out_w = M.forward(params, toks, pos, cfg)
    # perturb a token far outside the window of the last position
    toks2 = toks.at[0, 2].set((toks[0, 2] + 1) % cfg.vocab_size)
    out_w2 = M.forward(params, toks2, pos, cfg)
    last = np.asarray(out_w["logits"][0, -1], np.float32)
    last2 = np.asarray(out_w2["logits"][0, -1], np.float32)
    np.testing.assert_allclose(last, last2, atol=1e-5)
