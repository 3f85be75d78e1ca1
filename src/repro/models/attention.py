"""Attention: GQA (+qk-norm, sliding window) and MLA (DeepSeek-V3 latent).

Full-sequence paths use a *blocked* online-softmax implementation (the jnp
twin of the Pallas flash kernel) so the dry-run memory analysis reflects a
flash-attention working set instead of a materialized (S, S) score tensor.
Decode paths read a static-shape ring-buffer KV cache, stacked over layers
and head-major for GQA (DESIGN.md §1).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, cache_seq_axis
from repro.models.layers import ParamDef, apply_rope, rms_norm
from repro.shardctx import constrain

NEG_INF = -1e30
KV_SEQ = cache_seq_axis("k")           # ring axis of (L,B,KV,CL,D)
LATENT_SEQ = cache_seq_axis("c_kv")    # ring axis of (L,B,CL,r)


# ---------------------------------------------------------------------------
# parameter definitions
# ---------------------------------------------------------------------------

def attention_defs(cfg: ModelConfig, n_stack: int) -> Dict[str, ParamDef]:
    d, dt = cfg.d_model, cfg.dtype
    L = (n_stack,)
    Ll = ("layers",)
    out_scale = 0.02 / np.sqrt(2 * cfg.n_layers)
    if cfg.use_mla:
        nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        return {
            "wq_a": ParamDef(L + (d, cfg.q_lora_rank), Ll + ("p_embed", "p_lora"), dt),
            "q_norm": ParamDef(L + (cfg.q_lora_rank,), Ll + ("p_lora",), dt, -1.0),
            "wq_b": ParamDef(L + (cfg.q_lora_rank, cfg.n_heads, nope + rope),
                             Ll + ("p_lora", "p_heads", "p_head_dim"), dt),
            "wkv_a": ParamDef(L + (d, cfg.kv_lora_rank + rope), Ll + ("p_embed", "p_lora"), dt),
            "kv_norm": ParamDef(L + (cfg.kv_lora_rank,), Ll + ("p_lora",), dt, -1.0),
            "wk_b": ParamDef(L + (cfg.kv_lora_rank, cfg.n_heads, nope),
                             Ll + ("p_lora", "p_heads", "p_head_dim"), dt),
            "wv_b": ParamDef(L + (cfg.kv_lora_rank, cfg.n_heads, vd),
                             Ll + ("p_lora", "p_heads", "p_head_dim"), dt),
            "wo": ParamDef(L + (cfg.n_heads, vd, d),
                           Ll + ("p_heads", "p_head_dim", "p_embed"), dt, out_scale),
        }
    defs = {
        "wq": ParamDef(L + (d, cfg.n_heads, cfg.d_head),
                       Ll + ("p_embed", "p_heads", "p_head_dim"), dt),
        "wk": ParamDef(L + (d, cfg.n_kv_heads, cfg.d_head),
                       Ll + ("p_embed", "p_kv_heads", "p_head_dim"), dt),
        "wv": ParamDef(L + (d, cfg.n_kv_heads, cfg.d_head),
                       Ll + ("p_embed", "p_kv_heads", "p_head_dim"), dt),
        "wo": ParamDef(L + (cfg.n_heads, cfg.d_head, d),
                       Ll + ("p_heads", "p_head_dim", "p_embed"), dt, out_scale),
    }
    if cfg.use_qk_norm:
        defs["qn"] = ParamDef(L + (cfg.d_head,), Ll + ("p_head_dim",), dt, -1.0)
        defs["kn"] = ParamDef(L + (cfg.d_head,), Ll + ("p_head_dim",), dt, -1.0)
    return defs


# ---------------------------------------------------------------------------
# blocked (flash-style) causal attention — jnp reference of the Pallas kernel
# ---------------------------------------------------------------------------

def blocked_causal_attention(
    q, k, v,
    *,
    scale: float,
    segment_ids=None,
    window: int = 0,
    q_block: int = 512,
    kv_block: int = 512,
):
    """q: (B,S,H,Dk); k,v: (B,S,KV,Dk/Dv); GQA via H = KV*rep.

    Online-softmax over KV blocks; O(S * block) memory instead of O(S^2).
    `window > 0` adds a sliding-window constraint (j > i - window).
    """
    B, S, H, Dk = q.shape
    KV, Dv = k.shape[2], v.shape[-1]
    rep = H // KV
    if S % q_block or S % kv_block or S <= q_block:
        return _naive_causal_attention(q, k, v, scale=scale,
                                       segment_ids=segment_ids, window=window)
    nq, nk = S // q_block, S // kv_block
    qr = q.reshape(B, nq, q_block, KV, rep, Dk)
    kr = k.reshape(B, nk, kv_block, KV, Dk)
    vr = v.reshape(B, nk, kv_block, KV, Dv)
    seg = None
    if segment_ids is not None:
        seg = segment_ids.reshape(B, nq, q_block)

    q_pos = jnp.arange(S).reshape(nq, q_block)
    k_pos = jnp.arange(S).reshape(nk, kv_block)

    def one_q_block(qi):
        qb = qr[:, qi]  # (B,qb,KV,rep,Dk)
        qp = q_pos[qi]  # (qb,)
        sq = seg[:, qi] if seg is not None else None

        def kv_step(carry, ki):
            m, l, acc = carry
            kb, vb = kr[:, ki], vr[:, ki]
            kp = k_pos[ki]
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = qp[:, None] >= kp[None, :]
            if window:
                mask &= qp[:, None] - kp[None, :] < window
            mask = mask[None, None, None]
            if sq is not None:
                sk = segment_ids.reshape(B, nk, kv_block)[:, ki]
                mask = mask & (sq[:, None, :, None] == sk[:, None, None, :])[:, :, None]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, rep, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, rep, q_block), jnp.float32)
        a0 = jnp.zeros((B, KV, rep, q_block, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (B,KV,rep,qb,Dv)

    outs = jax.lax.map(one_q_block, jnp.arange(nq))  # (nq,B,KV,rep,qb,Dv)
    out = jnp.moveaxis(outs, 0, 1)  # (B,nq,KV,rep,qb,Dv)
    out = jnp.transpose(out, (0, 1, 4, 2, 3, 5)).reshape(B, S, H, Dv)
    return out.astype(q.dtype)


def _naive_causal_attention(q, k, v, *, scale, segment_ids=None, window=0):
    B, S, H, Dk = q.shape
    KV = k.shape[2]
    rep = H // KV
    qr = q.reshape(B, S, KV, rep, Dk)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qr, k,
                   preferred_element_type=jnp.float32) * scale
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = i >= j
    if window:
        mask &= (i - j) < window
    mask = mask[None, None, None]
    if segment_ids is not None:
        mask = mask & (segment_ids[:, None, None, :, None]
                       == segment_ids[:, None, None, None, :])
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# slot cache plumbing (DESIGN.md §1)
#
# Slot-cache leaves stay stacked over layers through the whole layer loop:
# GQA K/V head-major (L,B,KV,CL,D), MLA latents (L,B,CL,r); the ring axis
# of each is `cache_seq_axis(key)` (KV_SEQ, LATENT_SEQ). Decode writes one
# row per slot in place and the kernels read the layer where it lies.
# ---------------------------------------------------------------------------

def cache_layer(cache, layer):
    """Layer `layer` (a traced scalar) of a stacked (L,...) cache leaf."""
    return jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)


def put_cache_layer(cache, new, layer):
    """Write a whole layer back into a stacked (L,...) cache leaf."""
    return jax.lax.dynamic_update_index_in_dim(
        cache, new.astype(cache.dtype), layer, 0)


def _start(cache, layer, seq_axis, pos, row=0):
    start = [jnp.asarray(layer, jnp.int32), jnp.asarray(row, jnp.int32)]
    start += [jnp.int32(0)] * (cache.ndim - 2)
    start[seq_axis] = jnp.asarray(pos, jnp.int32)
    return start


def write_cache_rows(cache, new, layer, index, seq_axis):
    """Write each slot's new row into layer `layer` of the stacked slot
    cache, in place: nothing else in the cache is touched.

    cache: (L,B,...) with the ring on axis `seq_axis`; new: one layer,
    (B,...), with that axis of size 1; index: scalar (lockstep decode) or
    (B,) per-slot positions, written at index mod CL. One
    `dynamic_update_slice` per slot (one for all with a scalar index),
    which XLA performs in place on the donated buffer. A gather-style
    scatter (`.at[layer, rows, ..., slot].set`) is avoided on purpose:
    XLA gives it a layout of its own and relayouts the whole stack after
    every layer."""
    slot = jnp.mod(index, cache.shape[seq_axis])
    new = new.astype(cache.dtype)[None]                       # (1,B,...)
    if jnp.ndim(slot) == 0:
        return jax.lax.dynamic_update_slice(
            cache, new, _start(cache, layer, seq_axis, slot))
    for b in range(new.shape[1]):
        cache = jax.lax.dynamic_update_slice(
            cache, new[:, b:b + 1], _start(cache, layer, seq_axis, slot[b], b))
    return cache


# ---------------------------------------------------------------------------
# paged cache plumbing (DESIGN.md §9)
#
# Pool leaves are (n_pages, page_size, ...); `block_tables` (B, n_blocks)
# maps each slot's logical ring block to a physical page. The default read
# path gathers the per-slot contiguous view and runs the UNCHANGED
# attention math on it, which makes the paged engine bit-identical to the
# slot engine by construction (the valid region of the view equals the
# slot cache exactly; trash-page garbage only appears at positions every
# mask already excludes). Writes scatter into the pool; the engine's COW
# discipline guarantees the written page has refcount 1, so no scatter
# ever races except on the trash page (never read).
# ---------------------------------------------------------------------------

def paged_gather(pool, block_tables):
    """pool: (NP,PS,...) -> per-slot view (B, NB*PS, ...)."""
    v = jnp.take(pool, block_tables, axis=0)
    return v.reshape((v.shape[0], v.shape[1] * v.shape[2]) + v.shape[3:])


def settled(*views):
    """Materialize attention inputs before jnp attention reads them. A slot
    layer slice and a gathered page view then feed the same code whatever
    XLA would fuse into the dots, which keeps the paged engine bitwise
    equal to the slot engine."""
    return jax.lax.optimization_barrier(views)


def paged_gather_heads(pool, block_tables):
    """K/V pool (NP,PS,KV,D) -> per-slot view (B,KV,CL,D): one layer in
    the slot cache's head-major layout, so the attention that reads it is
    the slot path's own."""
    return jnp.swapaxes(paged_gather(pool, block_tables), 1, 2)


def write_cache_paged(pool, new, index, block_tables):
    """Paged twin of `write_cache_rows`: write `new` (B,1,...) at ring
    position index mod CL of each row. Inactive rows' block-table entries
    point at the trash page, which absorbs their static-shape stale
    writes."""
    B = new.shape[0]
    PS, NB = pool.shape[1], block_tables.shape[1]
    CL = NB * PS
    pos = jnp.broadcast_to(jnp.mod(index, CL), (B,))
    blk = pos // PS
    off = pos - blk * PS
    pages = jnp.take_along_axis(block_tables, blk[:, None], axis=1)[:, 0]
    cur = jnp.take(pool, pages, axis=0)                       # (B,PS,...)
    oh = (jnp.arange(PS)[None] == off[:, None]).astype(pool.dtype)
    oh = oh.reshape(oh.shape + (1,) * (pool.ndim - 2))
    merged = cur * (1 - oh) + new.astype(pool.dtype) * oh
    return pool.at[pages].set(merged)


def write_cache_chunk_paged(pool, new, offset, write_mask, block_tables):
    """Paged twin of `write_cache_chunk`. The engine keeps chunk size a
    divisor of page_size, so the chunk [offset, offset+C) lies inside ONE
    logical block. Masked rows merge back exactly what they gathered
    (identity write): live rows' pages are untouched and trash-page
    duplicates all write identical bytes."""
    C = new.shape[1]
    PS = pool.shape[1]
    blk = offset // PS
    off = offset - blk * PS
    pages = jnp.take(block_tables, blk[None], axis=1)[:, 0]   # (B,)
    cur = jnp.take(pool, pages, axis=0)                       # (B,PS,...)
    merged = new.astype(pool.dtype)
    if write_mask is not None:
        old = jax.lax.dynamic_slice_in_dim(cur, off, C, axis=1)
        shape = write_mask.shape + (1,) * (pool.ndim - write_mask.ndim)
        merged = jnp.where(write_mask.reshape(shape), merged, old)
    cur = jax.lax.dynamic_update_slice_in_dim(cur, merged, off, axis=1)
    return pool.at[pages].set(cur)


def decode_block_k(cache_len: int) -> int:
    """flash_decode KV block size for a given cache length — shared with
    the engine's kv_len_hint bucketing so the two layers cannot desync."""
    return min(256, cache_len)


def uses_flash_decode(cfg: ModelConfig, cache_len: int) -> bool:
    """True when decode attention takes the Pallas flash-decode kernel
    (GQA only; MLA decodes through the absorbed jnp path)."""
    return cfg.use_pallas and not cfg.use_mla and cache_len % 64 == 0


def decode_attention(q, k_cache, v_cache, cache_index, *, scale, ring: bool):
    """q: (B,H,Dk); caches: one head-major layer (B,KV,CL,D). One-token
    flash-decode reference, the jnp twin of the kernel. It reads the layer
    as a (B,CL,KV,D) view: XLA's CPU backend refuses some bf16 dots with
    an f32 result in the head-major form.

    ring=True: the cache is a full ring buffer (all slots valid).
    ring=False: slots >= cache_index are masked out. cache_index may be a
    scalar or per-slot (B,).
    """
    B, H, Dk = q.shape
    KV, CL = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    k_cache, v_cache = jnp.swapaxes(k_cache, 1, 2), jnp.swapaxes(v_cache, 1, 2)
    qr = q.reshape(B, KV, rep, Dk)
    s = jnp.einsum("bgrd,bkgd->bgrk", qr, k_cache,
                   preferred_element_type=jnp.float32) * scale
    if not ring:
        idx = jnp.reshape(cache_index, (-1, 1))  # scalar -> (1,1); (B,) -> (B,1)
        valid = jnp.arange(CL)[None] < idx
        s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA layer (train/prefill + decode)
# ---------------------------------------------------------------------------

def _maybe_qk_norm(cfg, p, q, k):
    if cfg.use_qk_norm:
        q = rms_norm(q, p["qn"], cfg.norm_eps)
        k = rms_norm(k, p["kn"], cfg.norm_eps)
    return q, k


def _use_flash_kernel(cfg, S, segment_ids, window) -> bool:
    return (cfg.use_pallas and segment_ids is None and window == 0
            and S % 128 == 0)


def gqa_forward(p, x, positions, cfg: ModelConfig, segment_ids=None,
                return_kv: bool = False):
    """Full-sequence causal GQA. x: (B,S,d)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q, k = _maybe_qk_norm(cfg, p, q, k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    window = cfg.sliding_window if cfg.attention_variant == "sliding_window" else 0
    S = x.shape[1]
    if _use_flash_kernel(cfg, S, segment_ids, window):
        from repro.kernels import ops as kops
        out = kops.flash_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), scale=1.0 / np.sqrt(cfg.d_head),
            interpret=cfg.pallas_interpret)
        out = jnp.swapaxes(out, 1, 2)
    else:
        out = blocked_causal_attention(
            q, k, v, scale=1.0 / np.sqrt(cfg.d_head),
            segment_ids=segment_ids, window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if return_kv:
        return y, (k, v)
    return y


def _decode_lengths(B, CL, cache_index, ring: bool):
    """Valid cache slots per row for the decode kernels, clamped to CL:
    once a ring cache has wrapped (cache_index >= CL) every slot is valid,
    and the clamp keeps the early exit tight."""
    if ring:
        return jnp.full((B,), CL, jnp.int32)
    return jnp.broadcast_to(jnp.minimum(
        jnp.asarray(cache_index + 1, jnp.int32), CL), (B,))


def gqa_decode(p, x, positions, cache_k, cache_v, layer, cache_index,
               cfg: ModelConfig, ring: bool, kv_len_hint=None,
               block_tables=None, paged_kernel: bool = False):
    """One-token decode of layer `layer`. x: (B,1,d); caches: the stacked
    head-major slot cache (L,B,KV,CL,Dk), or stacked page pools
    (L,NP,PS,KV,Dk) when `block_tables` (B,NB) is given. Returns y and the
    new stacked caches.

    Slot path: each slot's new K/V row is written in place
    (`write_cache_rows`) and the attention reads the layer where it lies
    (`flash_decode` takes the stack and the layer index).

    kv_len_hint: optional static upper bound on the valid cache length
    across the batch (host-mirrored by the engine); shrinks the flash-decode
    KV grid instead of relying on per-block `pl.when` skips alone.

    Paged path: write the token into its page, then either gather the
    per-slot view and run the IDENTICAL attention below (default —
    bit-equal to the slot cache), or, with paged_kernel, hand the block
    table straight to `flash_decode_paged` (scalar-prefetch; no gather)."""
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q, k = _maybe_qk_norm(cfg, p, q, k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    scale = 1.0 / np.sqrt(cfg.d_head)
    if block_tables is None:
        CL = cache_k.shape[3]
        cache_k = write_cache_rows(cache_k, jnp.swapaxes(k, 1, 2), layer,
                                   cache_index, KV_SEQ)
        cache_v = write_cache_rows(cache_v, jnp.swapaxes(v, 1, 2), layer,
                                   cache_index, KV_SEQ)
        view_k, view_v, at = cache_k, cache_v, layer
    else:
        pool_k = write_cache_paged(cache_layer(cache_k, layer), k,
                                   cache_index, block_tables)
        pool_v = write_cache_paged(cache_layer(cache_v, layer), v,
                                   cache_index, block_tables)
        cache_k = put_cache_layer(cache_k, pool_k, layer)
        cache_v = put_cache_layer(cache_v, pool_v, layer)
        CL = block_tables.shape[1] * pool_k.shape[1]
        if paged_kernel and uses_flash_decode(cfg, CL):
            from repro.kernels import ops as kops
            y = kops.flash_decode_paged(
                q[:, 0], pool_k, pool_v, block_tables,
                _decode_lengths(B, CL, cache_index, ring), scale=scale,
                max_len_hint=kv_len_hint, interpret=cfg.pallas_interpret)
            y = jnp.einsum("bhk,hkd->bd", y, p["wo"])[:, None]
            return y, (cache_k, cache_v)
        view_k = paged_gather_heads(pool_k, block_tables)[None]
        view_v = paged_gather_heads(pool_v, block_tables)[None]
        at = 0
    if uses_flash_decode(cfg, CL):
        from repro.kernels import ops as kops
        y = kops.flash_decode(q[:, 0], view_k, view_v,
                              _decode_lengths(B, CL, cache_index, ring), at,
                              scale=scale, block_k=decode_block_k(CL),
                              max_len_hint=kv_len_hint,
                              interpret=cfg.pallas_interpret)
    else:
        view_k, view_v = settled(cache_layer(view_k, at),
                                 cache_layer(view_v, at))
        y = decode_attention(q[:, 0], view_k, view_v, cache_index + 1,
                             scale=scale, ring=ring)
    y = jnp.einsum("bhk,hkd->bd", y, p["wo"])[:, None]
    return y, (cache_k, cache_v)


# ---------------------------------------------------------------------------
# MLA layer (DeepSeek-V3): naive expansion for train/prefill, absorbed decode
# ---------------------------------------------------------------------------

def mla_forward(p, x, positions, cfg: ModelConfig, segment_ids=None,
                return_kv: bool = False):
    B, S, _ = x.shape
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = jnp.einsum("bsd,dr->bsr", x, p["wq_a"])
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q, p["wq_b"])  # (B,S,H,nope+rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv, k_rope = kv[..., :cfg.kv_lora_rank], kv[..., cfg.kv_lora_rank:]
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)  # shared 1-head rope

    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"])
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None], (B, S, cfg.n_heads, rope))],
        axis=-1)
    q_full = constrain(q_full, ("batch", "seq", "heads", None))
    k_full = constrain(k_full, ("batch", "seq", "heads", None))
    out = blocked_causal_attention(
        q_full, k_full, v, scale=1.0 / np.sqrt(nope + rope),
        segment_ids=segment_ids)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if return_kv:
        return y, (c_kv, k_rope)
    return y


def mla_decode(p, x, positions, cache_ckv, cache_krope, layer, cache_index,
               cfg: ModelConfig, ring: bool, block_tables=None,
               paged_kernel: bool = False):
    """Absorbed MLA decode of layer `layer`: scores in latent space, cache
    stays compressed. Latent caches are stacked (L,B,CL,r); each slot's
    new latent row is written in place. With `block_tables`, they are
    stacked page pools (L,NP,PS,r) — write the token's latent into its
    page, gather the per-slot view, and run the identical absorbed
    attention (bit-equal to the slot cache)."""
    del paged_kernel  # MLA decodes through the absorbed jnp path
    B = x.shape[0]
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = jnp.einsum("bsd,dr->bsr", x, p["wq_a"])
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q, p["wq_b"])  # (B,1,H,nope+rope)
    q_nope, q_rope = q[:, 0, :, :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)[:, 0]  # (B,H,rope)

    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., cfg.kv_lora_rank:], positions, cfg.rope_theta)
    if block_tables is None:
        cache_ckv = write_cache_rows(cache_ckv, c_kv, layer, cache_index,
                                     LATENT_SEQ)
        cache_krope = write_cache_rows(cache_krope, k_rope, layer,
                                       cache_index, LATENT_SEQ)
        view_ckv = cache_layer(cache_ckv, layer)
        view_krope = cache_layer(cache_krope, layer)
    else:
        pool_ckv = write_cache_paged(cache_layer(cache_ckv, layer), c_kv,
                                     cache_index, block_tables)
        pool_krope = write_cache_paged(cache_layer(cache_krope, layer),
                                       k_rope, cache_index, block_tables)
        cache_ckv = put_cache_layer(cache_ckv, pool_ckv, layer)
        cache_krope = put_cache_layer(cache_krope, pool_krope, layer)
        view_ckv = paged_gather(pool_ckv, block_tables)
        view_krope = paged_gather(pool_krope, block_tables)
    CL = view_ckv.shape[1]
    view_ckv, view_krope = settled(view_ckv, view_krope)

    # absorb W_uk into q: (B,H,nope) x (r,H,nope) -> (B,H,r)
    q_latent = jnp.einsum("bhk,rhk->bhr", q_nope, p["wk_b"])
    s = jnp.einsum("bhr,bkr->bhk", q_latent, view_ckv,
                   preferred_element_type=jnp.float32)
    s += jnp.einsum("bhp,bkp->bhk", q_rope, view_krope,
                    preferred_element_type=jnp.float32)
    s *= 1.0 / np.sqrt(nope + rope)
    if not ring:
        idx = jnp.reshape(cache_index + 1, (-1, 1, 1))
        valid = jnp.arange(CL)[None, None] < idx
        s = jnp.where(valid, s, NEG_INF)
    pw = jax.nn.softmax(s, axis=-1)
    o_latent = jnp.einsum("bhk,bkr->bhr", pw.astype(view_ckv.dtype), view_ckv,
                          preferred_element_type=jnp.float32).astype(x.dtype)
    o = jnp.einsum("bhr,rhk->bhk", o_latent, p["wv_b"])
    y = jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None]
    return y, (cache_ckv, cache_krope)


# ---------------------------------------------------------------------------
# chunked prefill: a C-token query block against the slot cache + itself
# ---------------------------------------------------------------------------

def write_cache_chunk(cache, new, layer, offset, seq_axis, write_mask=None):
    """Write `new` — one layer in the cache's layout, (B,...), with C rows
    on the ring axis — into layer `layer` of the stacked slot cache
    (L,B,...) at [offset, offset+C) of axis `seq_axis`, in place.

    write_mask may be (B,) — only admitted rows may be touched (the others
    hold live K/V of in-progress sequences) — or (B,C) to additionally
    restrict which chunk positions are written (ring-buffer caches must
    not write garbage beyond a row's prompt: once the ring wraps, stale
    high-position garbage would alias into low slots that count-based
    decode masking treats as valid). The caller passes `offset` already
    reduced mod CL; chunk size divides CL so the slice never shifts.
    """
    merged = new.astype(cache.dtype)[None]                    # (1,B,...)
    start = _start(cache, layer, seq_axis, offset)
    if write_mask is not None:
        old = jax.lax.dynamic_slice(cache, start, merged.shape)
        shape = [1] * cache.ndim
        shape[1] = write_mask.shape[0]
        if write_mask.ndim == 2:
            shape[seq_axis] = write_mask.shape[1]
        merged = jnp.where(write_mask.reshape(shape), merged, old)
    return jax.lax.dynamic_update_slice(cache, merged, start)


def chunk_attention(q, k_chunk, v_chunk, k_cache, v_cache, offset, *, scale):
    """Two-source chunked-prefill attention (jnp twin of the Pallas
    `kernels.prefill_attention` kernel — see its docstring for the mask
    derivation). q: (B,C,H,Dk); k_chunk/v_chunk: (B,C,KV,D); caches:
    one head-major layer (B,KV,CL,D) in their PRE-chunk state, read as a
    (B,CL,KV,D) view like `decode_attention`'s; offset: scalar absolute
    position of the chunk's first token.

    Query i (absolute position qp = offset+i) attends to (1) cache slots j
    holding absolute position p_j = offset-1 - ((offset-1-j) mod CL) with
    p_j >= 0 and qp - p_j < CL (ring addressing; degenerates to j < offset
    on a full-length cache), and (2) the chunk's own keys causally.

    The einsums take f32 operands: exact for bf16 inputs (the products and
    the f32 sums of a bf16 dot), and XLA's CPU backend refuses some bf16
    dots with an f32 result."""
    B, C, H, Dk = q.shape
    KV, CL = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    rep = H // KV
    f32, vdt, cdt = jnp.float32, v_cache.dtype, v_chunk.dtype
    k_cache = jnp.swapaxes(k_cache, 1, 2).astype(f32)
    v_cache = jnp.swapaxes(v_cache, 1, 2).astype(f32)
    k_chunk, v_chunk = k_chunk.astype(f32), v_chunk.astype(f32)
    qr = q.reshape(B, C, KV, rep, Dk).astype(f32)
    qp = offset + jnp.arange(C)                                   # (C,)
    j = jnp.arange(CL)
    p_j = (offset - 1) - jnp.mod(offset - 1 - j, CL)              # (CL,)
    valid = (p_j[None] >= 0) & (qp[:, None] - p_j[None] < CL)     # (C,CL)
    s_cache = jnp.einsum("bqgrd,bkgd->bgrqk", qr, k_cache,
                         preferred_element_type=jnp.float32) * scale
    s_cache = jnp.where(valid[None, None, None], s_cache, NEG_INF)
    s_chunk = jnp.einsum("bqgrd,bkgd->bgrqk", qr, k_chunk,
                         preferred_element_type=jnp.float32) * scale
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]     # (C,C)
    s_chunk = jnp.where(causal[None, None, None], s_chunk, NEG_INF)
    p = jax.nn.softmax(jnp.concatenate([s_cache, s_chunk], axis=-1), axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p[..., :CL].astype(vdt).astype(f32),
                     v_cache, preferred_element_type=jnp.float32)
    out += jnp.einsum("bgrqk,bkgd->bqgrd", p[..., CL:].astype(cdt).astype(f32),
                      v_chunk, preferred_element_type=jnp.float32)
    return out.reshape(B, C, H, Dv).astype(q.dtype)


def prefill_block_k(cache_len: int) -> int:
    """prefill_attention cache-block size for a given cache length —
    shared with the engine's offset_hint bucketing so the two layers
    cannot desync (mirror of `decode_block_k`)."""
    return min(128, cache_len)


def _use_prefill_kernel(cfg: ModelConfig, C: int, CL: int) -> bool:
    return cfg.use_pallas and C <= CL and CL % prefill_block_k(CL) == 0


def _chunk_attention_any(q, k_chunk, v_chunk, k_cache, v_cache, offset,
                         cfg: ModelConfig, scale: float,
                         offset_hint: Optional[int] = None):
    """Route chunk-vs-cache attention through the Pallas prefill kernel
    when shapes fit, else the jnp twin. Caches: one head-major layer
    (B,KV,CL,D). offset_hint (static, >= min(offset, CL)) shrinks the
    kernel's cache-block grid — far cache blocks are never launched for
    early chunks."""
    C, CL = q.shape[1], k_cache.shape[2]
    if _use_prefill_kernel(cfg, C, CL):
        from repro.kernels import ops as kops
        return kops.prefill_attention(q, k_chunk, v_chunk, k_cache, v_cache,
                                      offset, scale=scale,
                                      block_k=prefill_block_k(CL),
                                      offset_hint=offset_hint,
                                      interpret=cfg.pallas_interpret)
    k_cache, v_cache = settled(k_cache, v_cache)
    return chunk_attention(q, k_chunk, v_chunk, k_cache, v_cache, offset,
                           scale=scale)


def gqa_prefill_chunk(p, x, positions, cache_k, cache_v, layer, offset,
                      write_mask, cfg: ModelConfig,
                      offset_hint: Optional[int] = None, block_tables=None):
    """Layer `layer` of GQA over a C-token prompt chunk. x: (B,C,d).
    Attends the chunk against the cache prefix plus itself
    (attend-then-write: on a ring cache the chunk's writes evict exactly
    the slots leaving the window), then writes the chunk's K/V at
    [offset mod CL, ...) of the stacked head-major cache (L,B,KV,CL,D),
    masked by write_mask (B,) or (B,C). With `block_tables` the caches are
    stacked page pools (L,NP,PS,KV,D): attend against the gathered view,
    write into pages (the engine keeps chunk | page_size, so the chunk
    lands in one block). Returns y (B,C,d), (cache_k, cache_v)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q, k = _maybe_qk_norm(cfg, p, q, k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    pool_k, pool_v = cache_layer(cache_k, layer), cache_layer(cache_v, layer)
    if block_tables is None:
        view_k, view_v = pool_k, pool_v
    else:
        view_k = paged_gather_heads(pool_k, block_tables)
        view_v = paged_gather_heads(pool_v, block_tables)
    CL = view_k.shape[2]
    y = _chunk_attention_any(q, k, v, view_k, view_v, offset, cfg,
                             1.0 / np.sqrt(cfg.d_head),
                             offset_hint=offset_hint)
    off_w = jnp.mod(offset, CL)
    if block_tables is None:
        cache_k = write_cache_chunk(cache_k, jnp.swapaxes(k, 1, 2), layer,
                                    off_w, KV_SEQ, write_mask)
        cache_v = write_cache_chunk(cache_v, jnp.swapaxes(v, 1, 2), layer,
                                    off_w, KV_SEQ, write_mask)
    else:
        cache_k = put_cache_layer(cache_k, write_cache_chunk_paged(
            pool_k, k, off_w, write_mask, block_tables), layer)
        cache_v = put_cache_layer(cache_v, write_cache_chunk_paged(
            pool_v, v, off_w, write_mask, block_tables), layer)
    y = jnp.einsum("bshk,hkd->bsd", y, p["wo"])
    return y, (cache_k, cache_v)


def mla_prefill_chunk(p, x, positions, cache_ckv, cache_krope, layer, offset,
                      write_mask, cfg: ModelConfig,
                      offset_hint: Optional[int] = None,
                      block_tables=None):
    """One absorbed-MLA layer over a C-token prompt chunk: scores in latent
    space against the compressed cache (same math as mla_decode, C queries).
    Routed through the shared prefill-attention primitive by treating the
    latent as a single KV head with the rope part concatenated onto the key
    dim (score = q_latent·c_kv + q_rope·k_rope) and the latent itself as
    the value. Latent caches are stacked (L,B,CL,r), or stacked page pools
    with `block_tables`. Returns y (B,C,d), (cache_ckv, cache_krope)."""
    B, C, _ = x.shape
    pool_ckv = cache_layer(cache_ckv, layer)
    pool_krope = cache_layer(cache_krope, layer)
    if block_tables is None:
        view_ckv, view_krope = pool_ckv, pool_krope
    else:
        view_ckv = paged_gather(pool_ckv, block_tables)
        view_krope = paged_gather(pool_krope, block_tables)
    CL = view_ckv.shape[1]
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = jnp.einsum("bsd,dr->bsr", x, p["wq_a"])
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q, p["wq_b"])   # (B,C,H,nope+rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., cfg.kv_lora_rank:], positions, cfg.rope_theta)

    # absorb W_uk into q: (B,C,H,nope) x (r,H,nope) -> (B,C,H,r)
    q_latent = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wk_b"])
    q_cat = jnp.concatenate([q_latent, q_rope], axis=-1)     # (B,C,H,r+rope)
    kh_cat = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None]
    kc_cat = jnp.concatenate([view_ckv, view_krope], axis=-1)[:, None]
    o_latent = _chunk_attention_any(
        q_cat, kh_cat, c_kv[:, :, None], kc_cat, view_ckv[:, None],
        offset, cfg, 1.0 / np.sqrt(nope + rope),
        offset_hint=offset_hint)                             # (B,C,H,r)

    off_w = jnp.mod(offset, CL)
    if block_tables is None:
        cache_ckv = write_cache_chunk(cache_ckv, c_kv, layer, off_w,
                                      LATENT_SEQ, write_mask)
        cache_krope = write_cache_chunk(cache_krope, k_rope, layer, off_w,
                                        LATENT_SEQ, write_mask)
    else:
        cache_ckv = put_cache_layer(cache_ckv, write_cache_chunk_paged(
            pool_ckv, c_kv, off_w, write_mask, block_tables), layer)
        cache_krope = put_cache_layer(cache_krope, write_cache_chunk_paged(
            pool_krope, k_rope, off_w, write_mask, block_tables), layer)
    o = jnp.einsum("bqhr,rhk->bqhk", o_latent.astype(x.dtype), p["wv_b"])
    y = jnp.einsum("bqhk,hkd->bqd", o, p["wo"])
    return y, (cache_ckv, cache_krope)
