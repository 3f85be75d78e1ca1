"""Decode writes one row per slot into the slot cache in place, and
`flash_decode` reads the stacked cache where it lies (DESIGN.md §1, §5).

The oracle is the decode step as it was before: per-layer caches in the
(B,CL,KV,D) layout sliced out of the stack as scan inputs, the one-hot
write `cache*(1-onehot) + new*onehot` over every position, the reference
attention on that layout, and the stack rebuilt from the scan's outputs.
It takes and returns today's head-major cache, converting at its edges
(an exact transpose), so both paths see the same values: a decode step
and the oracle's agree bit for bit.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.configs.tiny import config as tiny_config
from repro.core.rollout import EngineConfig, GenerationEngine
from repro.data.math_task import MathTask, Problem
from repro.kernels import ops as kops
from repro.models import attention as attn
from repro.models import model as M
from repro.models.layers import apply_rope, rms_norm
from repro.sharding import tree_values

TASK = MathTask(max_operand=5, ops="+")
KEY = jax.random.PRNGKey(14)


# ---------------------------------------------------------------------------
# the oracle: the one-hot write and the (B,CL,KV,D) reference attention
# ---------------------------------------------------------------------------

def onehot_write(cache, new, index):
    """Write `new` (B,1,...) into ring-buffer `cache` (B,CL,...) at
    slot = index % CL, rewriting every position of every row."""
    CL = cache.shape[1]
    slot = jnp.mod(index, CL)
    if jnp.ndim(slot) == 0:
        start = (0, slot) + (0,) * (cache.ndim - 2)
        return jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype),
                                            start)
    onehot = (jnp.arange(CL)[None] == slot[:, None]).astype(cache.dtype)
    onehot = onehot.reshape(onehot.shape + (1,) * (cache.ndim - 2))
    return cache * (1 - onehot) + new.astype(cache.dtype) * onehot


def ref_attention(q, k_cache, v_cache, cache_index, *, scale, ring):
    """q: (B,H,Dk); caches: (B,CL,KV,D)."""
    B, H, Dk = q.shape
    CL, KV = k_cache.shape[1], k_cache.shape[2]
    qr = q.reshape(B, KV, H // KV, Dk)
    s = jnp.einsum("bgrd,bkgd->bgrk", qr, k_cache,
                   preferred_element_type=jnp.float32) * scale
    if not ring:
        valid = jnp.arange(CL)[None] < jnp.reshape(cache_index, (-1, 1))
        s = jnp.where(valid[:, None, None], s, attn.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, v_cache.shape[-1]).astype(q.dtype)


def _oracle_gqa(p, x, positions, ck, cv, cache_index, cfg, ring):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q, k = attn._maybe_qk_norm(cfg, p, q, k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    ck = onehot_write(ck, k, cache_index)
    cv = onehot_write(cv, v, cache_index)
    y = ref_attention(q[:, 0], ck, cv, cache_index + 1,
                      scale=1.0 / np.sqrt(cfg.d_head), ring=ring)
    return jnp.einsum("bhk,hkd->bd", y, p["wo"])[:, None], {"k": ck, "v": cv}


def _oracle_mla(p, x, positions, ckv, krope, cache_index, cfg, ring):
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = jnp.einsum("bsd,dr->bsr", x, p["wq_a"])
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q, p["wq_b"])
    q_nope, q_rope = q[:, 0, :, :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)[:, 0]
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., cfg.kv_lora_rank:], positions, cfg.rope_theta)
    CL = ckv.shape[1]
    ckv = onehot_write(ckv, c_kv, cache_index)
    krope = onehot_write(krope, k_rope, cache_index)
    q_latent = jnp.einsum("bhk,rhk->bhr", q_nope, p["wk_b"])
    s = jnp.einsum("bhr,bkr->bhk", q_latent, ckv,
                   preferred_element_type=jnp.float32)
    s += jnp.einsum("bhp,bkp->bhk", q_rope, krope,
                    preferred_element_type=jnp.float32)
    s *= 1.0 / np.sqrt(nope + rope)
    if not ring:
        valid = jnp.arange(CL)[None, None] < jnp.reshape(cache_index + 1,
                                                         (-1, 1, 1))
        s = jnp.where(valid, s, attn.NEG_INF)
    pw = jax.nn.softmax(s, axis=-1)
    o_latent = jnp.einsum("bhk,bkr->bhr", pw.astype(ckv.dtype), ckv,
                          preferred_element_type=jnp.float32).astype(x.dtype)
    o = jnp.einsum("bhr,rhk->bhk", o_latent, p["wv_b"])
    y = jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None]
    return y, {"c_kv": ckv, "k_rope": krope}


def _old_layout(cache):
    return {k: jnp.swapaxes(v, 2, 3) if k in ("k", "v") else v
            for k, v in cache.items()}


_new_layout = _old_layout    # the same transpose, back


def oracle_decode_step(params, tokens, positions, cache, cache_index, cfg,
                       *, ring=None, **_):
    """`model.decode_step` as it was (attention archs, slot cache)."""
    if ring is None:
        ring = cfg.attention_variant == "sliding_window"
    cache = _old_layout(cache)
    h = jnp.take(params["embed"], tokens, axis=0)
    offset, new = 0, {k: [] for k in cache}
    for gi, (kind, count) in enumerate(M.layer_groups(cfg)):
        layers = {k: v[offset:offset + count] for k, v in cache.items()}

        def body(h, inp, _kind=kind):
            lp, cs = inp

            def attn_fn(pa, x):
                if cfg.use_mla:
                    return _oracle_mla(pa, x, positions, cs["c_kv"],
                                       cs["k_rope"], cache_index, cfg, ring)
                return _oracle_gqa(pa, x, positions, cs["k"], cs["v"],
                                   cache_index, cfg, ring)

            return M._cached_layer_step(cfg, _kind, h, lp, attn_fn, None)

        h, kvs = jax.lax.scan(body, h, (params["groups"][gi], layers))
        for k in cache:
            new[k].append(kvs[k])
        offset += count
    new = _new_layout({k: jnp.concatenate(v, axis=0) for k, v in new.items()})
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    out = {"logits": jnp.einsum("bsd,dv->bsv", h, head), "cache": new}
    if cfg.use_value_head:
        out["values"] = jnp.einsum(
            "bsd,dv->bsv", h.astype(jnp.float32), params["value_head"])[..., 0]
    return out


# ---------------------------------------------------------------------------
# decode_step == the oracle, bitwise
# ---------------------------------------------------------------------------

def _case(name):
    tiny = tiny_config(vocab_size=TASK.tok.vocab_size, d_model=64,
                       n_layers=3)
    ring = dataclasses.replace(tiny, attention_variant="sliding_window",
                               sliding_window=8)
    mla = dataclasses.replace(smoke_config(get_config("deepseek-v3-671b")),
                              vocab_size=TASK.tok.vocab_size)
    return {
        # ragged per-slot positions: an empty row, a last ring slot
        "ragged": (tiny, 16, jnp.asarray([0, 5, 15, 2]), False),
        # a window-sized ring every row has wrapped past, some twice
        "ring_wrapped": (ring, 16, jnp.asarray([9, 23, 8, 17]), False),
        # lockstep decode on a warm ring: one scalar index for all rows
        "ring_lockstep": (ring, 16, jnp.int32(19), True),
        "mla": (mla, 16, jnp.asarray([0, 5, 15, 2]), False),
    }[name]


@pytest.mark.parametrize("name", ["ragged", "ring_wrapped", "ring_lockstep",
                                  "mla"])
def test_decode_step_matches_oracle(name):
    """Three consecutive decode steps from a cache full of stale values:
    the same logits and the same cache, bit for bit, as the one-hot write
    and the reference attention; rows and positions not written keep
    their stale values."""
    from repro.configs.base import kv_cache_specs
    cfg, max_len, index, ring = _case(name)
    params = tree_values(M.init_params(cfg, KEY))
    B = 4
    specs = kv_cache_specs(cfg, B, max_len)
    cache = {k: jax.random.normal(jax.random.fold_in(KEY, i), v.shape,
                                  jnp.float32).astype(v.dtype)
             for i, (k, v) in enumerate(sorted(specs.items()))}
    new = jax.jit(M.decode_step, static_argnames=("cfg", "ring"))
    old = jax.jit(oracle_decode_step, static_argnames=("cfg", "ring"))
    got, want = cache, cache
    for t in range(3):
        idx = index + t
        tok = jax.random.randint(jax.random.fold_in(KEY, 100 + t), (B, 1),
                                 0, cfg.vocab_size)
        pos = jnp.broadcast_to(jnp.reshape(idx, (-1, 1)), (B, 1))
        a = new(params, tok, pos, got, idx, cfg=cfg, ring=ring)
        b = old(params, tok, pos, want, idx, cfg=cfg, ring=ring)
        np.testing.assert_array_equal(np.asarray(a["logits"]),
                                      np.asarray(b["logits"]), err_msg=t)
        for k in cache:
            np.testing.assert_array_equal(np.asarray(a["cache"][k]),
                                          np.asarray(b["cache"][k]),
                                          err_msg=f"{k} step {t}")
        got, want = a["cache"], b["cache"]


# ---------------------------------------------------------------------------
# flash_decode on the stack with a layer index == the per-layer call
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layer, hint, CL", [
    (0, None, 64), (1, None, 64), (2, None, 64), (2, 40, 64),
    (1, None, 256), (2, 40, 256)])    # 256: heads read by columns
def test_flash_decode_reads_layer_in_place(layer, hint, CL):
    """The layer operand picks the layer inside the kernel's index maps:
    the result equals the call on that layer alone, bitwise, with and
    without the grid-shrinking length hint, whichever way the heads are
    read (`reads_by_columns`)."""
    L, B, H, KV, D, blk = 3, 3, 4, 2, 16, 64 if CL > 64 else 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kc = jax.random.normal(ks[1], (L, B, KV, CL, D))
    vc = jax.random.normal(ks[2], (L, B, KV, CL, D))
    lengths = jnp.asarray([1, 40, 17])
    stacked = kops.flash_decode(q, kc, vc, lengths, jnp.int32(layer),
                                scale=0.25, block_k=blk, max_len_hint=hint)
    alone = kops.flash_decode(q, kc[layer][None], vc[layer][None], lengths,
                              scale=0.25, block_k=blk, max_len_hint=hint)
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(alone))


# ---------------------------------------------------------------------------
# a greedy engine run == the same run through the oracle
# ---------------------------------------------------------------------------

def _engine(cfg, params, probs):
    it = iter(list(probs))
    ec = EngineConfig(n_slots=4, max_len=32, prefill_chunk=4,
                      temperature=1e-4)
    return GenerationEngine(cfg, params, ec, lambda: next(it, None), seed=5)


def test_engine_run_matches_oracle(monkeypatch):
    """20 greedy steps of a slot engine with ragged prompts and an idle
    slot (whose stale row is still written every step): the same tokens,
    and logprobs and cache to f32 rounding, as an engine whose decode step
    is the oracle."""
    cfg = tiny_config(vocab_size=TASK.tok.vocab_size, d_model=64, n_layers=2)
    params = tree_values(M.init_params(cfg, KEY))
    probs = [Problem(list(range(3, 3 + n)), 0) for n in (5, 11, 2)]
    new, old = _engine(cfg, params, probs), _engine(cfg, params, probs)
    assert new.refill() == 3 and old.refill() == 3
    for t in range(20):
        new.step(TASK)
        with monkeypatch.context() as m:   # the oracle while `old` traces
            m.setattr(M, "decode_step", oracle_decode_step)
            old.step(TASK)
        for k in ("tokens", "n_cached", "active"):
            np.testing.assert_array_equal(np.asarray(new.state[k]),
                                          np.asarray(old.state[k]),
                                          err_msg=f"{k} step {t}")
        # inside the engine's program XLA fuses the projections and the
        # log-softmax around each decode step its own way, and may order
        # their f32 sums differently: an ulp or two (the step alone is
        # bitwise, test_decode_step_matches_oracle)
        np.testing.assert_allclose(np.asarray(new.state["lp"]),
                                   np.asarray(old.state["lp"]), rtol=0,
                                   atol=2.5e-7, err_msg=f"lp step {t}")
    for k in new.state["cache"]:
        np.testing.assert_allclose(np.asarray(new.state["cache"][k]),
                                   np.asarray(old.state["cache"][k]),
                                   rtol=0, atol=2.5e-7, err_msg=k)
    assert (np.asarray(new.state["lp"]) != 0).any()
