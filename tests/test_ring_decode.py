"""Ring-buffer sliding-window decode (the long_500k serve path): decoding
with a window-sized ring cache must match the full-sequence forward with
sliding-window attention, once the ring is warm."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.configs.base import kv_cache_specs
from repro.models import model as M
from repro.sharding import tree_values

KEY = jax.random.PRNGKey(5)


def test_ring_decode_matches_windowed_forward():
    W = 8
    cfg = dataclasses.replace(smoke_config(get_config("llama3-8b")),
                              attention_variant="sliding_window",
                              sliding_window=W, use_mtp=False)
    params = tree_values(M.init_params(cfg, KEY))
    B, S = 1, 20
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    # reference: full forward with the sliding-window mask
    ref = M.forward(params, toks, pos, cfg)["logits"]

    # ring decode: window-sized cache, token by token
    specs = kv_cache_specs(cfg, B, W)
    cache = {k: jnp.zeros(v.shape, v.dtype) for k, v in specs.items()}
    assert cache["k"].shape[3] == W  # the ring really is window-sized
    logits = []
    for t in range(S):
        out = M.decode_step(params, toks[:, t:t + 1], pos[:, t:t + 1],
                            cache, jnp.int32(t), cfg,
                            ring=(t >= W))  # masked until the ring is warm
        cache = out["cache"]
        logits.append(out["logits"][:, 0])
    dec = jnp.stack(logits, axis=1)

    # exact agreement once the ring is warm (and during warmup too, since
    # masking covers the cold slots)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-4, rtol=3e-4)


def test_ring_recompute_kv_matches_sequential_writes():
    """`recompute_kv` on a sliding-window engine (the §3 ablation): the
    gathered ring cache must hold exactly what the sequential decode loop
    would have written under the new weights — slot j gets the most recent
    position p <= n_cached-1 with p ≡ j (mod CL)."""
    from repro.core.rollout import GenerationEngine

    W = 8
    cfg = dataclasses.replace(smoke_config(get_config("llama3-8b")),
                              attention_variant="sliding_window",
                              sliding_window=W, use_mtp=False)
    params = tree_values(M.init_params(cfg, KEY))
    new_params = tree_values(M.init_params(cfg, jax.random.PRNGKey(99)))
    H, T = 3, 20
    toks = jax.random.randint(KEY, (H, T), 0, cfg.vocab_size)
    n_cached = jnp.asarray([20, 5, 0])   # wrapped ring / cold ring / empty
    specs = kv_cache_specs(cfg, H, W)
    st = {
        "tokens": toks,
        "n_cached": n_cached,
        "cache": {k: jax.random.normal(KEY, v.shape).astype(v.dtype)
                  for k, v in specs.items()},   # stale garbage everywhere
    }
    assert st["cache"]["k"].shape[3] == W   # head-major (L,H,KV,W,D)

    got = GenerationEngine._recompute_impl(new_params, st, cfg=cfg)

    pos = jnp.broadcast_to(jnp.arange(T)[None], (H, T))
    full = M.forward(new_params, toks, pos, cfg,
                     return_cache=True)["cache"]
    for key in ("k", "v"):
        # oracle: the sequential loop's ring writes of the full-length cache
        exp = np.zeros(st["cache"][key].shape, np.float32)
        valid = np.zeros((H, W), bool)
        for b, nc in enumerate(np.asarray(n_cached)):
            for p in range(int(nc)):
                exp[:, b, :, p % W] = np.asarray(full[key][:, b, :, p])
                valid[b, p % W] = True
        g = np.asarray(got[key], np.float32)
        for b in range(H):
            np.testing.assert_allclose(
                g[:, b][:, :, valid[b]], exp[:, b][:, :, valid[b]],
                atol=1e-5, rtol=1e-5, err_msg=f"{key} row {b}")
        # dead slots of empty rows must never be read anyway; nothing to
        # assert there (the gather clamps them to position 0)
