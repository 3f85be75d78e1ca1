"""use_pallas=True must match the pure-jnp model bit-for-bit-ish: same
forward logits (train path) and same decode logits, across attention and
SSD architectures."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.models import model as M
from repro.sharding import tree_values

KEY = jax.random.PRNGKey(3)


@pytest.mark.parametrize("arch", ["llama3-8b", "qwen3-32b", "mamba2-2.7b",
                                  "hymba-1.5b"])
def test_forward_parity(arch):
    cfg = dataclasses.replace(smoke_config(get_config(arch)), ssm_chunk=32)
    params = tree_values(M.init_params(cfg, KEY))
    B, S = 2, 128  # S % 128 == 0 so the flash kernel engages
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    ref = M.forward(params, toks, pos, cfg)
    kcfg = dataclasses.replace(cfg, use_pallas=True)
    out = M.forward(params, toks, pos, kcfg)
    np.testing.assert_allclose(
        np.asarray(out["logits"], np.float32),
        np.asarray(ref["logits"], np.float32), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("arch", ["llama3-8b", "mamba2-2.7b"])
def test_decode_parity(arch):
    cfg = dataclasses.replace(smoke_config(get_config(arch)), use_mtp=False)
    params = tree_values(M.init_params(cfg, KEY))
    B, S = 2, 63
    toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S + 1)[None], (B, S + 1))
    pre = M.forward(params, toks[:, :S], pos[:, :S], cfg, return_cache=True)

    def pad(k, v):  # pad cache to 64 so the decode kernel engages
        if k in ("k", "v"):
            return jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, 64 - S), (0, 0)))
        return v

    cache = {k: pad(k, v) for k, v in pre["cache"].items()}
    ref = M.decode_step(params, toks[:, S:], pos[:, S:], cache,
                        jnp.int32(S), cfg)
    kcfg = dataclasses.replace(cfg, use_pallas=True)
    out = M.decode_step(params, toks[:, S:], pos[:, S:], cache,
                        jnp.int32(S), kcfg)
    np.testing.assert_allclose(
        np.asarray(out["logits"], np.float32),
        np.asarray(ref["logits"], np.float32), atol=2e-4, rtol=2e-4)
