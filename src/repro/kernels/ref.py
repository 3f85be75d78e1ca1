"""Pure-jnp oracles for every Pallas kernel (the blocked/naive model-zoo
implementations double as references; re-exported here with the kernels'
calling conventions)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.attention import (
    _naive_causal_attention,
    chunk_attention as _chunk_ref,
    decode_attention as _decode_ref,
)
from repro.models.ssm import ssd_chunked


def flash_attention_ref(q, k, v, *, scale: float, causal: bool = True):
    """q: (B,H,S,Dk); k,v: (B,KV,S,D). Matches kernels.flash_attention."""
    qb = jnp.swapaxes(q, 1, 2)      # (B,S,H,D)
    kb = jnp.swapaxes(k, 1, 2)
    vb = jnp.swapaxes(v, 1, 2)
    if not causal:
        raise NotImplementedError("reference is causal-only")
    out = _naive_causal_attention(qb, kb, vb, scale=scale)
    return jnp.swapaxes(out, 1, 2)


def flash_decode_ref(q, k_cache, v_cache, lengths, *, scale: float):
    """Matches kernels.flash_decode on one layer of the head-major cache,
    (B,KV,CL,D) (lengths == CL means full ring)."""
    return _decode_ref(q, k_cache, v_cache, jnp.asarray(lengths),
                       scale=scale, ring=False)


def prefill_attention_ref(q, k_chunk, v_chunk, k_cache, v_cache, offset, *,
                          scale: float):
    """Matches kernels.prefill_attention (two-source chunk-vs-cache
    attention with ring addressing; caches (B,KV,CL,D) in their pre-chunk
    state)."""
    return _chunk_ref(q, k_chunk, v_chunk, k_cache, v_cache,
                      jnp.asarray(offset, jnp.int32), scale=scale)


def fused_logprob_ref(hidden, head, targets, *, transpose_head: bool = False):
    """Matches kernels.fused_logprob (blockwise linear-cross-entropy), via
    the straightforward full-logits computation — the equivalence oracle
    for value *and* gradient, and the model layer's jnp fallback when the
    Pallas path is off. hidden: (N,D); head: (D,V) or (V,D) with
    transpose_head; targets: (N,) int32. Returns (logprob, lse, entropy),
    each (N,) f32. f32 accumulation like the kernel (the unfused model
    path materializes logits in *model dtype*, so bf16 runs agree with
    this twin more tightly than with that path)."""
    import jax

    eq = "nd,vd->nv" if transpose_head else "nd,dv->nv"
    logits = jnp.einsum(eq, hidden, head,
                        preferred_element_type=jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt_l = jnp.take_along_axis(logits, targets[:, None].astype(jnp.int32),
                                axis=-1)[:, 0]
    p = jnp.exp(logits - lse[:, None])
    entropy = lse - jnp.sum(p * logits, axis=-1)
    return tgt_l - lse, lse, entropy


def ssd_scan_ref(x, dt, A, B, C, *, chunk: int = 64):
    """Matches kernels.ssd_scan: returns (y, final_state (b,h,n,p))."""
    y, state = ssd_chunked(x, dt, A, B, C, chunk)
    return y, jnp.swapaxes(state, -1, -2)
