"""Pallas TPU flash chunked-prefill: a C-token prompt chunk attending to
the slot cache plus itself — the generation engine's admission hot path.
It takes one layer of the head-major cache, (B,KV,CL,D).

Chunked-prefill attention has two key sources with different masking:

1. **Cache prefix** — K/V written by *previous* chunks of this prompt,
   read from the (possibly ring-buffer) slot cache. Cache slot ``j``
   holds absolute position ``p_j = offset-1 - ((offset-1-j) mod CL)``
   (the most recent position congruent to ``j`` that precedes the chunk);
   it is valid for a query at absolute position ``qp`` iff ``p_j >= 0``
   (the slot was ever written) and ``qp - p_j < CL`` (inside the sliding
   window — for a full-length cache ``CL`` equals the sequence budget so
   this clips nothing). With ``CL = max_len`` the rule degenerates to the
   familiar ``j < offset``.
2. **The chunk itself** — fresh K/V of this chunk's tokens, causal within
   the chunk (``kp <= qp``; the window constraint is vacuous because the
   host guarantees ``C <= CL``).

Attention therefore runs against the cache *before* the chunk is written
into it: on a ring buffer the chunk's writes overwrite exactly the slots
that fall out of the window, so attend-then-write is what makes chunked
admission equal the sequential decode loop (DESIGN.md §2 equivalence law).

grid = (batch, kv_heads, n_cache_blocks + 1); the trailing axis is
sequential on TPU and streams cache KV blocks HBM->VMEM with online-
softmax state in VMEM scratch, exactly like ``flash_decode``; the final
grid step processes the chunk's own K/V tile and writes the output. All
``rep`` q-heads of a kv head are folded with the chunk axis into one
``(C*rep, d)`` MXU tile. Cache blocks entirely beyond the write frontier
(``ki*block_k >= offset``) skip their dots via ``pl.when`` — the first
chunks of a prompt touch almost none of the cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.common import default_interpret

NEG_INF = -1e30


def _prefill_kernel(off_ref, q_ref, kc_ref, vc_ref, kh_ref, vh_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, scale: float, block_k: int,
                    n_cache_blocks: int, chunk: int, rep: int, cache_len: int):
    ki = pl.program_id(2)
    off = off_ref[0]
    rows = chunk * rep  # row = ci * rep + r  ->  query chunk index ci = row//rep

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _online_update(s, v):
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # ---- cache-prefix blocks: skip blocks entirely past the write frontier
    @pl.when((ki < n_cache_blocks) & (ki * block_k < off))
    def _cache_block():
        q = q_ref[0, 0].astype(jnp.float32)                  # (rows, d)
        k = kc_ref[0, 0].astype(jnp.float32)                 # (bk, d)
        v = vc_ref[0, 0].astype(jnp.float32)                 # (bk, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp = off + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 0) // rep            # abs query pos
        j = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)                   # cache slot
        # absolute position held at slot j (ring addressing); for a
        # full-length cache this reduces to p_j = j valid iff j < offset
        p_j = (off - 1) - jnp.remainder(off - 1 - j, cache_len)
        valid = (p_j >= 0) & (qp - p_j < cache_len)
        _online_update(jnp.where(valid, s, NEG_INF), v)

    # ---- the chunk's own K/V: causal within the chunk, then finalize
    @pl.when(ki == n_cache_blocks)
    def _chunk_block():
        q = q_ref[0, 0].astype(jnp.float32)                  # (rows, d)
        k = kh_ref[0, 0].astype(jnp.float32)                 # (C, d)
        v = vh_ref[0, 0].astype(jnp.float32)                 # (C, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qi = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 0) // rep
        ci = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)
        _online_update(jnp.where(ci <= qi, s, NEG_INF), v)
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def prefill_attention(q, k_chunk, v_chunk, k_cache, v_cache, offset, *,
                      scale: float, block_k: int = 128,
                      offset_hint: int | None = None,
                      interpret: bool | None = None):
    """q: (B,C,H,Dk); k_chunk/v_chunk: (B,C,KV,Dk/Dv); caches: one layer
    of the head-major slot cache, (B,KV,CL,Dk/Dv); offset: scalar int32
    absolute position of the chunk's first token. Returns (B,C,H,Dv).

    The caches must be in their pre-chunk state (attend-then-write, see
    module docstring). Requires C <= CL and CL % block_k == 0. MLA absorbed
    prefill reuses this kernel with KV=1, Dk = kv_lora_rank + qk_rope_dim
    (concatenated latent+rope queries/keys) and Dv = kv_lora_rank.

    offset_hint: optional *static* upper bound on the number of valid
    cache slots, i.e. >= min(offset, CL) — the cache-block grid axis
    shrinks to ceil(hint/block_k) blocks, so blocks past the write
    frontier are never even fetched (the `pl.when` skip alone still paid
    the DMA). The generation engine derives it from the host-side chunk
    offset, rounded up to block_k so jit sees few distinct values; a
    violation silently truncates attention. None keeps the full grid.

    interpret=None resolves to interpret mode off-TPU and compiled mode on
    TPU (callers may force either; see kernels.ops for the jitted wrapper).
    """
    interpret = default_interpret(interpret)
    B, C, H, Dk = q.shape
    KV, CL = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    rep = H // KV
    block_k = min(block_k, CL)
    assert CL % block_k == 0, (CL, block_k)
    assert C <= CL, (C, CL)
    nkb = CL // block_k
    if offset_hint is not None:
        # a first chunk (offset 0) touches no cache blocks at all
        nkb = min(nkb, -(-min(int(offset_hint), CL) // block_k))
    rows = C * rep

    qr = q.reshape(B, C, KV, rep, Dk).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(B, KV, rows, Dk)
    kh = jnp.swapaxes(k_chunk, 1, 2)                    # (B,KV,C,Dk)
    vh = jnp.swapaxes(v_chunk, 1, 2)
    off = jnp.reshape(jnp.asarray(offset, jnp.int32), (1,))

    kernel = functools.partial(
        _prefill_kernel, scale=scale, block_k=block_k, n_cache_blocks=nkb,
        chunk=C, rep=rep, cache_len=CL)
    out = pl.pallas_call(
        kernel,
        grid=(B, KV, nkb + 1),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, ki: (0,),
                         memory_space=pltpu.MemorySpace.SMEM),
            pl.BlockSpec((1, 1, rows, Dk), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, Dk),
                         lambda b, h, ki, _n=max(nkb - 1, 0):
                             (b, h, jnp.minimum(ki, _n), 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, ki, _n=max(nkb - 1, 0):
                             (b, h, jnp.minimum(ki, _n), 0)),
            pl.BlockSpec((1, 1, C, Dk), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, C, Dv), lambda b, h, ki: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, Dv), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, rows, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(off, qr, k_cache, v_cache, kh, vh)
    out = out.reshape(B, KV, C, rep, Dv).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, C, H, Dv)
