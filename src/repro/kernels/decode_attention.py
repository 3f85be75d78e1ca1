"""Pallas TPU flash-decode: one-token GQA attention against a (possibly
ring-buffer) KV cache — the generation engine's hot loop.

TPU adaptation of vLLM's paged-attention CUDA kernel: instead of gather-
paged KV blocks, the cache is a contiguous per-slot ring buffer (static
shapes, see DESIGN.md §1), stored head-major and stacked over layers
(L,B,KV,CL,D); the kernel reads one layer of it in place and streams KV
*blocks* HBM->VMEM along the sequential trailing grid axis with
online-softmax accumulation in VMEM scratch. Invalid slots (>= cache
length) are masked, so one kernel serves both the growing-cache and the
full-ring cases.

The kernel is *length-aware* at two levels:

- **grid-level** — a static `max_len_hint` (the host-mirrored
  `max(lengths)` over the batch, rounded up to `block_k`) shrinks the
  trailing grid axis itself, so blocks beyond the hint are never fetched
  from HBM at all (the `pl.when` variant still paid the DMA);
- **block-level** — the per-slot valid lengths ride in SMEM as a
  scalar-prefetch operand and KV blocks entirely beyond a slot's length
  skip the QK^T / PV dots via `pl.when` — in a
  continuous-batching engine most slots are far from the cache capacity,
  so the common case touches only `ceil(len/block_k)` blocks' worth of
  MXU work instead of `CL/block_k`.

grid = (batch, kv_heads, n_kv_blocks); all `rep` q-heads of a kv head are
processed together as a (rep, d) tile — MXU-friendly and it amortizes the
KV block fetch exactly like GQA intends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.common import default_interpret

NEG_INF = -1e30


def _decode_kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                   l_ref, acc_ref, *, scale: float, block_k: int,
                   n_kv_blocks: int, by_columns: bool):
    del layer_ref  # read by the K/V index maps only
    b, ki = pl.program_id(0), pl.program_id(2)
    n_valid = len_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # length-aware skip: blocks whose first slot is already past this
    # sequence's valid length contribute nothing — don't issue the dots
    @pl.when(ki * block_k < n_valid)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # (rep, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, d) or (d, bk)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, dv) or (dv, bk)
        s = jax.lax.dot_general(q, k, (((1,), (0 if by_columns else 1,)),
                                       ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = (ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], block_k), 1)) < n_valid
        s = jnp.where(valid, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (1 if by_columns else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def reads_by_columns(head_dim: int, cache_len: int) -> bool:
    """Whether `flash_decode` reads the cache's (CL, D) heads as D-by-block
    tiles of their transpose. A TPU lays out a head narrower than its 128
    lanes, over a ring at least that long, with the ring axis minor: the
    transposed view is then the stored buffer itself, where a
    block-by-D read would make XLA relayout the whole layer first."""
    return head_dim < 128 <= cache_len


def flash_decode(q, k_cache, v_cache, lengths, layer=0, *, scale: float,
                 block_k: int = 256, max_len_hint: int | None = None,
                 interpret: bool | None = None):
    """q: (B,H,Dk); caches: the stacked head-major slot cache (L,B,KV,CL,D);
    lengths: (B,) valid cache length per slot (pass CL for a full ring
    buffer); layer: the layer to read. Returns (B,H,Dv).

    The kernel reads layer `layer` of the stacked cache where it lies: the
    layer is a scalar-prefetch operand of the K/V index maps, so no layer
    slice is ever materialized, and a head narrower than 128 lanes is read
    as (D, block_k) tiles of its transpose, the layout it is stored in
    (`reads_by_columns`); the online softmax is the same either way.

    max_len_hint: optional *static* upper bound on max(lengths) — the grid's
    trailing KV axis shrinks to ceil(hint/block_k) blocks, so cache blocks
    beyond the hint are never even fetched. The caller must guarantee
    hint >= max(lengths) (the generation engine derives it from its host
    length mirrors, rounded up to block_k so jit sees few distinct values);
    a violation silently truncates attention. None keeps the full grid.

    interpret=None resolves to interpret mode off-TPU and compiled mode on
    TPU (callers may force either; see kernels.ops for the jitted wrapper).
    """
    interpret = default_interpret(interpret)
    B, H, Dk = q.shape
    KV, CL = k_cache.shape[2], k_cache.shape[3]
    Dv = v_cache.shape[-1]
    rep = H // KV
    block_k = min(block_k, CL)
    assert CL % block_k == 0, (CL, block_k)
    nk = CL // block_k
    if max_len_hint is not None:
        nk = max(1, min(nk, -(-int(max_len_hint) // block_k)))

    qr = q.reshape(B, KV, rep, Dk)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    by_columns = reads_by_columns(Dk, CL)
    if by_columns:
        k_cache = jnp.swapaxes(k_cache, 3, 4)           # (L,B,KV,D,CL)
        v_cache = jnp.swapaxes(v_cache, 3, 4)

    def kv_spec(d):
        if by_columns:
            return pl.BlockSpec((None, 1, 1, d, block_k),
                                lambda b, h, ki, _, lay: (lay[0], b, h, 0, ki))
        return pl.BlockSpec((None, 1, 1, block_k, d),
                            lambda b, h, ki, _, lay: (lay[0], b, h, ki, 0))

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                               n_kv_blocks=nk, by_columns=by_columns)
    # lengths and the layer are scalar-prefetch operands: both sit in SMEM
    # for the kernel's lifetime (a per-row rank-1 SMEM block is not a
    # legal TPU block shape)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, rep, Dk),
                         lambda b, h, ki, lens, lay: (b, h, 0, 0)),
            kv_spec(Dk),
            kv_spec(Dv),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, Dv),
                               lambda b, h, ki, lens, lay: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, Dv), q.dtype),
        interpret=interpret,
    )(lengths, layer, qr, k_cache, v_cache)
    return out.reshape(B, H, Dv)
