"""jit'd public wrappers for the Pallas kernels — the only entry points the
model layer calls (DESIGN.md §5 "Kernel catalog" documents each kernel's
grid/block layout, masking rules, and early-exit behavior).

`interpret` defaults to True off-TPU (kernel bodies execute in Python on
CPU for correctness validation) and False on real TPU backends; the model
threads `ModelConfig.pallas_interpret` (set from `EngineConfig.interpret`
by the generation engine) into every call so TPU runs never hit an
interpret-mode kernel by accident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import default_interpret
from repro.kernels.decode_attention import flash_decode as _flash_decode
from repro.kernels.paged_cache import flash_decode_paged as _flash_decode_paged
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.fused_logprob import fused_logprob as _fused_logprob
from repro.kernels.prefill_attention import (
    prefill_attention as _prefill_attention,
)
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan


@functools.partial(jax.jit, static_argnames=("scale", "causal", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """Full-sequence (train / whole-prompt prefill) flash attention.

    q: (B,H,S,Dk); k,v: (B,KV,S,Dk/Dv) with GQA folded via H = KV*rep.
    Returns (B,H,S,Dv). Online-softmax over KV blocks; causal=True skips
    fully-masked blocks above the diagonal. S must divide by both block
    sizes (the model layer falls back to the jnp blocked path otherwise).
    """
    interpret = default_interpret(interpret)
    return _flash_attention(q, k, v, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "block_k",
                                             "max_len_hint", "interpret"))
def flash_decode(q, k_cache, v_cache, lengths, layer=0, *, scale: float,
                 block_k: int = 256, max_len_hint: int | None = None,
                 interpret: bool | None = None):
    """One-token decode attention against the (possibly ring-buffer) slot
    cache — the generation engine's per-step hot loop.

    q: (B,H,Dk); caches: the stacked head-major slot cache (L,B,KV,CL,D),
    read where it lies at layer `layer` (a traced scalar: no layer slice
    is materialized; heads narrower than 128 lanes are read in the
    transposed layout a TPU stores them in, see `reads_by_columns`);
    lengths: (B,) count of valid cache slots per sequence (CL for a warm
    ring buffer). Slots >= lengths[b] are masked, so the
    positional-validity invariant of DESIGN.md §1 holds without ever
    zeroing retired slots. max_len_hint (static, must be
    >= max(lengths)) shrinks the KV grid axis itself — blocks beyond the
    hint are never fetched; per-slot `pl.when` skips handle the rest.
    """
    interpret = default_interpret(interpret)
    return _flash_decode(q, k_cache, v_cache, lengths, layer, scale=scale,
                         block_k=block_k, max_len_hint=max_len_hint,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "max_len_hint",
                                             "interpret"))
def flash_decode_paged(q, k_pool, v_pool, block_tables, lengths, *,
                       scale: float, max_len_hint: int | None = None,
                       interpret: bool | None = None):
    """One-token decode attention straight against the paged KV pool
    (DESIGN.md §9) — no gathered per-slot copy.

    q: (B,H,Dk); pools: (NP,PS,KV,D); block_tables: (B,NB) int32 mapping
    each slot's logical ring block to its physical page (trash page 0 for
    unallocated blocks); lengths: (B,) valid logical length per slot.
    The block table and lengths are scalar-prefetch operands: the KV
    BlockSpec index maps dereference `bt[b, ki]`, so each grid step DMAs
    exactly the page backing logical block ki of row b. The online
    softmax runs page-by-page (block_k = page_size); it matches
    `flash_decode` on the gathered view bitwise only when page_size
    equals that call's block_k, fp32-close otherwise. max_len_hint
    (static, >= max(lengths)) shrinks the page grid axis like
    `flash_decode`'s early exit.
    """
    interpret = default_interpret(interpret)
    return _flash_decode_paged(q, k_pool, v_pool, block_tables, lengths,
                               scale=scale, max_len_hint=max_len_hint,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "block_k",
                                             "offset_hint", "interpret"))
def prefill_attention(q, k_chunk, v_chunk, k_cache, v_cache, offset, *,
                      scale: float, block_k: int = 128,
                      offset_hint: int | None = None,
                      interpret: bool | None = None):
    """Chunked-prefill attention: a C-token prompt chunk (Q) against the
    slot cache prefix plus the chunk's own K/V — the admission hot path.

    q: (B,C,H,Dk); k_chunk/v_chunk: (B,C,KV,D); caches: one layer of the
    head-major slot cache, (B,KV,CL,D), in their PRE-chunk state
    (attend-then-write); offset: scalar absolute
    position of the chunk's first token. Cache slots are masked by the
    ring rule p_j = offset-1 - ((offset-1-j) mod CL), valid iff p_j >= 0
    and qp - p_j < CL — which degenerates to j < offset on a full-length
    cache; intra-chunk attention is causal. MLA absorbed prefill reuses
    the kernel with KV=1 and latent+rope dims concatenated.

    offset_hint (static, >= min(offset, CL)) shrinks the cache-block grid
    axis itself — like `flash_decode`'s `max_len_hint` — so blocks past
    the write frontier are never fetched; the engine buckets the host-side
    chunk offset to block_k so jit sees few distinct values.

    Part of the chunked-prefill equivalence law (DESIGN.md §2): admission
    through this kernel must match the sequential decode loop bit-for-bit
    in fp32 on the resulting cache, and within fp32 tolerance on logits.
    """
    interpret = default_interpret(interpret)
    return _prefill_attention(q, k_chunk, v_chunk, k_cache, v_cache, offset,
                              scale=scale, block_k=block_k,
                              offset_hint=offset_hint, interpret=interpret)


def fused_logprob(hidden, head, targets, *, transpose_head: bool = False,
                  block_n: int | None = None, block_v: int | None = None,
                  interpret: bool | None = None):
    """Fused linear-cross-entropy over the lm head — the trainer's loss
    hot path (DESIGN.md §5-6).

    hidden: (N,D) post-final-norm hidden states; head: (D,V), or (V,D)
    with transpose_head=True (tied-embedding layout, no transposed copy);
    targets: (N,) int32. Returns (logprob, lse, entropy), each (N,) f32.
    Tiles the vocab axis with an online-logsumexp reduction so the
    (N,V) logits are never materialized, and carries a custom VJP that
    recomputes per-block softmax from the saved lse so the logits
    *gradient* is never materialized either (grads reach both hidden and
    head). Unlike the other wrappers this one is not jit-wrapped: it is
    always called from inside the already-jitted `train_step` loss, and
    an extra jit boundary here would only add a dispatch layer.

    block_n/block_v default to MXU-friendly (128, 512) tiles on compiled
    TPU; interpret mode (the CPU validation/co-sim path) defaults to
    coarser (256, 2048) blocks — the interpreter pays per-grid-step python
    dispatch, so fewer/bigger blocks make CPU trainer steps measurably
    faster with identical masking and numerics. Explicit values win.
    """
    interpret = default_interpret(interpret)
    if block_n is None:
        block_n = 256 if interpret else 128
    if block_v is None:
        block_v = 2048 if interpret else 512
    return _fused_logprob(hidden, head, targets,
                          transpose_head=transpose_head, block_n=block_n,
                          block_v=block_v, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 64,
             interpret: bool | None = None):
    """Mamba2 SSD chunked scan: intra-chunk attention-form + inter-chunk
    state recurrence. x: (b,l,h,p); dt: (b,l,h); A: (h,); B,C: (b,l,g,n).
    Returns (y (b,l,h,p), final_state (b,h,p,n) fp32). The recurrence is
    reassociated across chunks, so results match the sequential scan to
    fp32 tolerance (not bitwise) — the equivalence tests account for this.
    """
    interpret = default_interpret(interpret)
    return _ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret)
