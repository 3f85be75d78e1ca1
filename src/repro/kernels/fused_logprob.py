"""Pallas TPU fused linear-cross-entropy: the trainer's lm-head hot path.

The textbook LM loss materializes the full ``(B, S, V)`` logits tensor in
model dtype, then a *second* f32 copy for ``log_softmax`` — at llama3-8B /
128k-vocab scale those two tensors dominate trainer activation memory and
the logits *gradient* (a third ``(B, S, V)`` tensor) dominates backward
HBM traffic. This kernel fuses the lm-head matmul with the cross-entropy
reduction: hidden states ``(N, D)`` and the head matrix stream through the
MXU in vocab *blocks* with an online-logsumexp recurrence, producing only
per-token scalars — the sampled token's logprob, the logsumexp, and the
full-distribution entropy. No logits tensor ever exists.

Forward recurrence per ``(row-block, vocab-block)`` grid step (all f32 in
VMEM scratch, persisting across the sequential trailing vocab axis):

    l       = h @ W[:, v0:v0+bv]                  # (bn, bv) block logits
    m'      = max(m, max_v l)                     # running max
    corr    = exp(m - m')
    s       = s * corr + sum_v exp(l - m')        # running sumexp
    a       = a * corr + sum_v exp(l - m') * l    # entropy numerator
    t      += sum_v 1[v == target] * l            # target logit gather

and at the last vocab block ``lse = m + log s``, ``logprob = t - lse``,
``entropy = lse - a / s`` (since ``H = lse - E_p[l]``).

The custom VJP never materializes the logits gradient either: with row
coefficients ``c0 = g_lse - g_lp + g_ent * (lse - H)`` the per-block
gradient is

    dl = g_lp * 1[v == target] + p * (c0 - g_ent * l),   p = exp(l - lse)

recomputed on the fly from the saved ``lse`` (softmax recompute — the same
trick flash-attention backward uses). Two passes: ``dhidden`` accumulates
``dl @ W_blk^T`` over vocab blocks (vocab trailing/sequential), ``dhead``
accumulates ``h^T @ dl`` over row blocks (rows trailing/sequential), so
each output tile owns exactly one sequential reduction axis. Gradients
flow to both the hidden states and the head weights; f32 accumulation
throughout.

``transpose_head=True`` reads the head as ``(V, D)`` — the tied-embedding
layout — so tied models pass ``params["embed"]`` directly and no
transposed ``(D, V)`` copy is ever materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.common import default_interpret

NEG_INF = -1e30


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _block_logits(h_ref, w_ref, transpose_head: bool):
    """(bn, bv) f32 logits of this vocab block."""
    h = h_ref[...]
    w = w_ref[...]
    if transpose_head:                       # w: (bv, D)
        return _dot(h, w, ((1,), (1,)))
    return _dot(h, w, ((1,), (0,)))          # w: (D, bv)


def _col_ids(vi, block_n: int, block_v: int):
    return vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(tgt_ref, h_ref, w_ref, lp_ref, lse_ref, ent_ref,
                m_ref, s_ref, a_ref, t_ref, *, block_n: int, block_v: int,
                n_v_blocks: int, vocab: int, transpose_head: bool):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        a_ref[...] = jnp.zeros_like(a_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    l = _block_logits(h_ref, w_ref, transpose_head)
    col = _col_ids(vi, block_n, block_v)
    l = jnp.where(col < vocab, l, NEG_INF)   # pad columns never contribute

    m_prev, s_prev = m_ref[...], s_ref[...]
    m_new = jnp.maximum(m_prev, l.max(axis=-1, keepdims=True))
    p = jnp.exp(l - m_new)
    corr = jnp.exp(m_prev - m_new)
    s_ref[...] = s_prev * corr + p.sum(axis=-1, keepdims=True)
    # entropy numerator: sum exp(l - m) * l; masked cols give exp -> 0
    a_ref[...] = a_ref[...] * corr + (p * l).sum(axis=-1, keepdims=True)
    onehot = col == tgt_ref[...]             # tgt: (bn, 1) broadcasts
    t_ref[...] += jnp.where(onehot, l, 0.0).sum(axis=-1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(vi == n_v_blocks - 1)
    def _finalize():
        s = jnp.maximum(s_ref[...], 1e-30)
        lse = m_ref[...] + jnp.log(s)
        lse_ref[...] = lse
        lp_ref[...] = t_ref[...] - lse
        ent_ref[...] = lse - a_ref[...] / s


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _block_dlogits(tgt_ref, lse_ref, c0_ref, glp_ref, gent_ref, h_ref, w_ref,
                   vi, *, block_n: int, block_v: int, vocab: int,
                   transpose_head: bool):
    """Recompute the (bn, bv) logits-gradient block from saved row stats."""
    l = _block_logits(h_ref, w_ref, transpose_head)
    col = _col_ids(vi, block_n, block_v)
    p = jnp.exp(l - lse_ref[...])
    onehot = col == tgt_ref[...]
    dl = glp_ref[...] * onehot.astype(jnp.float32) \
        + p * (c0_ref[...] - gent_ref[...] * l)
    return jnp.where(col < vocab, dl, 0.0)


def _bwd_dh_kernel(tgt_ref, lse_ref, c0_ref, glp_ref, gent_ref, h_ref, w_ref,
                   dh_ref, acc_ref, *, block_n: int, block_v: int,
                   n_v_blocks: int, vocab: int, transpose_head: bool):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dl = _block_dlogits(tgt_ref, lse_ref, c0_ref, glp_ref, gent_ref, h_ref,
                        w_ref, vi, block_n=block_n, block_v=block_v,
                        vocab=vocab, transpose_head=transpose_head)
    w = w_ref[...]
    if transpose_head:                       # (bn, bv) @ (bv, D)
        acc_ref[...] += _dot(dl, w, ((1,), (0,)))
    else:                                    # (bn, bv) x (D, bv) -> (bn, D)
        acc_ref[...] += _dot(dl, w, ((1,), (1,)))

    @pl.when(vi == n_v_blocks - 1)
    def _finalize():
        dh_ref[...] = acc_ref[...].astype(dh_ref.dtype)


def _bwd_dw_kernel(tgt_ref, lse_ref, c0_ref, glp_ref, gent_ref, h_ref, w_ref,
                   dw_ref, acc_ref, *, block_n: int, block_v: int,
                   n_n_blocks: int, vocab: int, transpose_head: bool):
    vi, ni = pl.program_id(0), pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dl = _block_dlogits(tgt_ref, lse_ref, c0_ref, glp_ref, gent_ref, h_ref,
                        w_ref, vi, block_n=block_n, block_v=block_v,
                        vocab=vocab, transpose_head=transpose_head)
    h = h_ref[...]
    if transpose_head:                       # dl^T @ h -> (bv, D)
        acc_ref[...] += _dot(dl, h, ((0,), (0,)))
    else:                                    # h^T @ dl -> (D, bv)
        acc_ref[...] += _dot(h, dl, ((0,), (0,)))

    @pl.when(ni == n_n_blocks - 1)
    def _finalize():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _bwd_dw_chunk_kernel(tgt_ref, lse_ref, c0_ref, glp_ref, gent_ref, h_ref,
                         w_ref, dw_ref, acc_ref, *, block_n: int, block_v: int,
                         n_n_blocks: int, rows_per_chunk: int, vocab: int,
                         transpose_head: bool):
    """Two-level dhead reduction (level 1): the sequential rows axis is cut
    into chunks of `rows_per_chunk` row blocks; the VMEM accumulator resets
    at each chunk boundary and flushes a per-chunk f32 partial to its own
    slice of the (n_chunks, ...) output. Level 2 — summing the partials —
    happens outside the kernel as an ordinary tree reduction, so at very
    large N the hidden re-read per vocab block stops being one monolithic
    length-n_n_blocks dependency chain."""
    vi, ni = pl.program_id(0), pl.program_id(1)

    @pl.when(ni % rows_per_chunk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dl = _block_dlogits(tgt_ref, lse_ref, c0_ref, glp_ref, gent_ref, h_ref,
                        w_ref, vi, block_n=block_n, block_v=block_v,
                        vocab=vocab, transpose_head=transpose_head)
    h = h_ref[...]
    if transpose_head:                       # dl^T @ h -> (bv, D)
        acc_ref[...] += _dot(dl, h, ((0,), (0,)))
    else:                                    # h^T @ dl -> (D, bv)
        acc_ref[...] += _dot(h, dl, ((0,), (0,)))

    last_of_chunk = (ni % rows_per_chunk) == rows_per_chunk - 1

    @pl.when((ni == n_n_blocks - 1) | last_of_chunk)
    def _flush():
        dw_ref[...] = acc_ref[...][None]


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _pad_axis(x, axis: int, to: int):
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _geometry(N: int, D: int, V: int, block_n: int, block_v: int):
    """Static launch geometry. block_n is shrunk to divide the (padded) row
    count; the vocab axis is padded up to a block multiple and masked by
    the true V in-kernel (odd V % block remainders)."""
    bn = max(1, min(block_n, N))
    while N % bn:
        bn -= 1
    bv = max(1, min(block_v, V))
    Vp = -(-V // bv) * bv
    return bn, bv, N // bn, Vp // bv, Vp


def _row_specs(bn):
    """BlockSpecs for the per-row (N, 1) scalar inputs."""
    return pl.BlockSpec((bn, 1), lambda ni, vi: (ni, 0))


def _w_spec(bv, D, transpose_head, flip=False):
    """Head-matrix BlockSpec; flip swaps the (ni, vi) grid-arg order for
    the dhead kernel whose grid is (vocab, rows)."""
    if transpose_head:
        if flip:
            return pl.BlockSpec((bv, D), lambda vi, ni: (vi, 0))
        return pl.BlockSpec((bv, D), lambda ni, vi: (vi, 0))
    if flip:
        return pl.BlockSpec((D, bv), lambda vi, ni: (0, vi))
    return pl.BlockSpec((D, bv), lambda ni, vi: (0, vi))


def _fused_fwd_call(hidden, head, targets, block_n, block_v, transpose_head,
                    interpret):
    N, D = hidden.shape
    V = head.shape[0] if transpose_head else head.shape[1]
    bn, bv, n_n, n_v, Vp = _geometry(N, D, V, block_n, block_v)
    head = _pad_axis(head, 0 if transpose_head else 1, Vp)
    tgt = targets.reshape(N, 1).astype(jnp.int32)

    kernel = functools.partial(
        _fwd_kernel, block_n=bn, block_v=bv, n_v_blocks=n_v, vocab=V,
        transpose_head=transpose_head)
    out = pl.pallas_call(
        kernel,
        grid=(n_n, n_v),
        in_specs=[
            _row_specs(bn),
            pl.BlockSpec((bn, D), lambda ni, vi: (ni, 0)),
            _w_spec(bv, D, transpose_head),
        ],
        out_specs=[_row_specs(bn)] * 3,
        out_shape=[jax.ShapeDtypeStruct((N, 1), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)] * 4,
        interpret=interpret,
    )(tgt, hidden, head)
    lp, lse, ent = (o[:, 0] for o in out)
    return lp, lse, ent


def _fused_bwd_call(hidden, head, targets, lse, c0, g_lp, g_ent,
                    block_n, block_v, transpose_head, interpret,
                    dw_chunks=1):
    N, D = hidden.shape
    V = head.shape[0] if transpose_head else head.shape[1]
    bn, bv, n_n, n_v, Vp = _geometry(N, D, V, block_n, block_v)
    head_p = _pad_axis(head, 0 if transpose_head else 1, Vp)
    rows = [targets.reshape(N, 1).astype(jnp.int32),
            lse.reshape(N, 1), c0.reshape(N, 1),
            g_lp.reshape(N, 1), g_ent.reshape(N, 1)]

    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, block_n=bn, block_v=bv,
                          n_v_blocks=n_v, vocab=V,
                          transpose_head=transpose_head),
        grid=(n_n, n_v),
        in_specs=[_row_specs(bn)] * 5 + [
            pl.BlockSpec((bn, D), lambda ni, vi: (ni, 0)),
            _w_spec(bv, D, transpose_head),
        ],
        out_specs=pl.BlockSpec((bn, D), lambda ni, vi: (ni, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), hidden.dtype),
        scratch_shapes=[pltpu.VMEM((bn, D), jnp.float32)],
        interpret=interpret,
    )(*rows, hidden, head_p)

    dw_shape = (Vp, D) if transpose_head else (D, Vp)
    dw_block = (bv, D) if transpose_head else (D, bv)
    if dw_chunks > 1 and n_n > 1:
        # two-level reduction: per-row-chunk f32 partials + tree sum
        rpc = -(-n_n // dw_chunks)           # row blocks per chunk
        n_chunks = -(-n_n // rpc)
        if transpose_head:
            out_spec = pl.BlockSpec((1,) + dw_block,
                                    lambda vi, ni: (ni // rpc, vi, 0))
        else:
            out_spec = pl.BlockSpec((1,) + dw_block,
                                    lambda vi, ni: (ni // rpc, 0, vi))
        dw_part = pl.pallas_call(
            functools.partial(_bwd_dw_chunk_kernel, block_n=bn, block_v=bv,
                              n_n_blocks=n_n, rows_per_chunk=rpc, vocab=V,
                              transpose_head=transpose_head),
            grid=(n_v, n_n),                 # rows trailing: dw accumulates
            in_specs=[pl.BlockSpec((bn, 1), lambda vi, ni: (ni, 0))] * 5 + [
                pl.BlockSpec((bn, D), lambda vi, ni: (ni, 0)),
                _w_spec(bv, D, transpose_head, flip=True),
            ],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((n_chunks,) + dw_shape,
                                           jnp.float32),
            scratch_shapes=[pltpu.VMEM(dw_block, jnp.float32)],
            interpret=interpret,
        )(*rows, hidden, head_p)
        dw = dw_part.sum(axis=0).astype(head.dtype)
    else:
        dw = pl.pallas_call(
            functools.partial(_bwd_dw_kernel, block_n=bn, block_v=bv,
                              n_n_blocks=n_n, vocab=V,
                              transpose_head=transpose_head),
            grid=(n_v, n_n),                 # rows trailing: dw accumulates
            in_specs=[pl.BlockSpec((bn, 1), lambda vi, ni: (ni, 0))] * 5 + [
                pl.BlockSpec((bn, D), lambda vi, ni: (ni, 0)),
                _w_spec(bv, D, transpose_head, flip=True),
            ],
            out_specs=pl.BlockSpec(
                dw_block, (lambda vi, ni: (vi, 0)) if transpose_head
                else (lambda vi, ni: (0, vi))),
            out_shape=jax.ShapeDtypeStruct(dw_shape, head.dtype),
            scratch_shapes=[pltpu.VMEM(dw_block, jnp.float32)],
            interpret=interpret,
        )(*rows, hidden, head_p)
    if Vp != V:
        dw = dw[:V] if transpose_head else dw[:, :V]
    return dh, dw


# ---------------------------------------------------------------------------
# custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused(static, hidden, head, targets):
    block_n, block_v, transpose_head, interpret, _ = static
    return _fused_fwd_call(hidden, head, targets, block_n, block_v,
                           transpose_head, interpret)


def _fused_fwd(static, hidden, head, targets):
    block_n, block_v, transpose_head, interpret, _ = static
    out = _fused_fwd_call(hidden, head, targets, block_n, block_v,
                          transpose_head, interpret)
    lp, lse, ent = out
    return out, (hidden, head, targets, lse, ent)


def _fused_bwd(static, res, cts):
    block_n, block_v, transpose_head, interpret, dw_chunks = static
    hidden, head, targets, lse, ent = res
    g_lp, g_lse, g_ent = (g.astype(jnp.float32) for g in cts)
    # dl = g_lp * 1[v==t] + p * (c0 - g_ent * l), c0 = g_lse - g_lp
    #    + g_ent * (lse - H)  — see module docstring for the derivation
    c0 = g_lse - g_lp + g_ent * (lse - ent)
    dh, dw = _fused_bwd_call(hidden, head, targets, lse, c0, g_lp, g_ent,
                             block_n, block_v, transpose_head, interpret,
                             dw_chunks=dw_chunks)
    d_tgt = np.zeros(targets.shape, jax.dtypes.float0)
    return dh, dw, d_tgt


_fused.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# blocked jnp twin (compiled fallback — the `blocked_causal_attention` of
# the fused loss): same vocab tiling, online-LSE recurrence and custom-VJP
# recompute as the Pallas kernel, expressed as a lax.scan so XLA compiles
# it on any backend. The model layer uses it when `use_pallas` is off —
# unlike the full-logits oracle in kernels/ref.py it, too, never
# materializes the (N, V) logits or their gradient.
# ---------------------------------------------------------------------------

def _blocked_logits(hidden, head_p, i, bv, transpose_head):
    if transpose_head:
        wb = jax.lax.dynamic_slice_in_dim(head_p, i * bv, bv, axis=0)
        return wb, _dot(hidden, wb, ((1,), (1,)))
    wb = jax.lax.dynamic_slice_in_dim(head_p, i * bv, bv, axis=1)
    return wb, _dot(hidden, wb, ((1,), (0,)))


def _blocked_geometry(head, block_v, transpose_head):
    V = head.shape[0] if transpose_head else head.shape[1]
    bv = max(1, min(block_v, V))
    Vp = -(-V // bv) * bv
    head_p = _pad_axis(head, 0 if transpose_head else 1, Vp)
    return V, bv, Vp // bv, head_p


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _blocked(static, hidden, head, targets):
    block_v, transpose_head = static
    N = hidden.shape[0]
    V, bv, nv, head_p = _blocked_geometry(head, block_v, transpose_head)
    tgt = targets.astype(jnp.int32)

    def body(carry, i):
        m, s, a, tl = carry
        _, l = _blocked_logits(hidden, head_p, i, bv, transpose_head)
        col = i * bv + jnp.arange(bv)
        l = jnp.where(col[None] < V, l, NEG_INF)
        m2 = jnp.maximum(m, l.max(axis=-1))
        p = jnp.exp(l - m2[:, None])
        corr = jnp.exp(m - m2)
        s = s * corr + p.sum(axis=-1)
        a = a * corr + (p * l).sum(axis=-1)
        tl = tl + jnp.where(col[None] == tgt[:, None], l, 0.0).sum(axis=-1)
        return (m2, s, a, tl), None

    init = (jnp.full((N,), NEG_INF, jnp.float32), jnp.zeros((N,)),
            jnp.zeros((N,)), jnp.zeros((N,)))
    (m, s, a, tl), _ = jax.lax.scan(body, init, jnp.arange(nv))
    s = jnp.maximum(s, 1e-30)
    lse = m + jnp.log(s)
    return tl - lse, lse, lse - a / s


def _blocked_fwd(static, hidden, head, targets):
    out = _blocked(static, hidden, head, targets)
    lp, lse, ent = out
    return out, (hidden, head, targets, lse, ent)


def _blocked_bwd(static, res, cts):
    block_v, transpose_head = static
    hidden, head, targets, lse, ent = res
    g_lp, g_lse, g_ent = (g.astype(jnp.float32) for g in cts)
    c0 = g_lse - g_lp + g_ent * (lse - ent)
    N, D = hidden.shape
    V, bv, nv, head_p = _blocked_geometry(head, block_v, transpose_head)
    tgt = targets.astype(jnp.int32)

    def body(dh, i):
        wb, l = _blocked_logits(hidden, head_p, i, bv, transpose_head)
        col = i * bv + jnp.arange(bv)
        p = jnp.exp(l - lse[:, None])
        dl = g_lp[:, None] * (col[None] == tgt[:, None]).astype(jnp.float32) \
            + p * (c0[:, None] - g_ent[:, None] * l)
        dl = jnp.where(col[None] < V, dl, 0.0)
        if transpose_head:           # wb: (bv, D); dw block: (bv, D)
            dwb = _dot(dl, hidden, ((0,), (0,)))
            dh = dh + _dot(dl, wb, ((1,), (0,)))
        else:                        # wb: (D, bv); dw block: (D, bv)
            dwb = _dot(hidden, dl, ((0,), (0,)))
            dh = dh + _dot(dl, wb, ((1,), (1,)))
        return dh, dwb

    dh, dwbs = jax.lax.scan(body, jnp.zeros((N, D)), jnp.arange(nv))
    if transpose_head:               # (nv, bv, D) -> (Vp, D)
        dw = dwbs.reshape(nv * bv, D)[:V]
    else:                            # (nv, D, bv) -> (D, Vp)
        dw = jnp.moveaxis(dwbs, 0, 1).reshape(D, nv * bv)[:, :V]
    d_tgt = np.zeros(targets.shape, jax.dtypes.float0)
    return dh.astype(hidden.dtype), dw.astype(head.dtype), d_tgt


_blocked.defvjp(_blocked_fwd, _blocked_bwd)


def fused_logprob_blocked(hidden, head, targets, *,
                          transpose_head: bool = False, block_v: int = 512):
    """Compiled blockwise linear-cross-entropy — the jnp twin of
    `fused_logprob` (same tiling, online-LSE and VJP-recompute math,
    expressed as a lax.scan). Used by the model layer when the Pallas path
    is off; also never materializes the logits or their gradient."""
    assert hidden.ndim == 2 and head.ndim == 2 and targets.ndim == 1
    return _blocked((int(block_v), bool(transpose_head)),
                    hidden, head, targets)


def fused_logprob(hidden, head, targets, *, transpose_head: bool = False,
                  block_n: int = 128, block_v: int = 512,
                  interpret: bool | None = None, dw_chunks: int = 1):
    """Blockwise linear-cross-entropy over the lm head.

    hidden: (N, D) final hidden states (post final-norm); head: (D, V), or
    (V, D) with ``transpose_head=True`` (tied-embedding layout — pass the
    embedding matrix directly, no transposed copy); targets: (N,) int32
    sampled-token ids. Returns ``(logprob, lse, entropy)``, each (N,) f32:
    the target token's logprob, the logsumexp, and the full-distribution
    entropy per row. Differentiable w.r.t. hidden and head via a custom
    VJP that re-derives each vocab block's softmax from the saved ``lse``
    — neither the logits nor their gradient are ever materialized.

    dw_chunks > 1 splits the backward dhead reduction over the rows axis
    into that many per-chunk f32 partials summed outside the kernel (a
    two-level tree reduction): at very large N the single sequential
    accumulation chain per vocab tile stops gating the re-read of hidden.
    The default (1) keeps the original single-pass accumulator.

    Memory: activations are O(N) scalars + one (bn, D) tile per grid step,
    vs O(N·V) logits (twice: model dtype + f32) for the unfused path.
    """
    interpret = default_interpret(interpret)
    assert hidden.ndim == 2 and head.ndim == 2 and targets.ndim == 1
    return _fused((int(block_n), int(block_v), bool(transpose_head),
                   bool(interpret), int(dw_chunks)), hidden, head, targets)


# ---------------------------------------------------------------------------
# vocab-sharded wrapper (the p_vocab -> "model" mesh axis, DESIGN.md §11)
# ---------------------------------------------------------------------------
#
# Each shard owns a contiguous V/n slice of the head and runs the ordinary
# fused kernel on it, producing *local* (lp_i, lse_i, ent_i). The global
# stats are exact functions of those three scalars per row:
#
#     lse = m + log sum_i exp(lse_i - m),      m = max_i lse_i
#     sum_{v in shard i} exp(l_v) * l_v = exp(lse_i) * (lse_i - ent_i)
#       => entropy = lse - sum_i exp(lse_i - lse) * (lse_i - ent_i)
#     target logit = sum_i owned_i * (lp_i + lse_i)   (one owner per row)
#       => logprob = target logit - lse
#
# so one psum over "model" of three (N,)-vectors replaces any (N, V)
# traffic — the no-materialization property now holds *per shard*, and the
# combine is plain differentiable jnp, so the custom VJP of the local call
# stays intact and grads flow to the local head slice only.

def vocab_shard_count(mesh, axis_name: str, vocab: int) -> int:
    """Usable vocab shards: the size of `axis_name` on `mesh` when it
    exists and divides `vocab`, else 1 (caller falls back to the
    replicated path — the same divisibility-drop contract as
    `sharding.logical_to_spec`)."""
    if mesh is None or axis_name not in mesh.shape:
        return 1
    n = int(mesh.shape[axis_name])
    return n if n > 1 and vocab % n == 0 else 1


def fused_logprob_sharded(hidden, head, targets, *, mesh=None,
                          axis_name: str = "model",
                          transpose_head: bool = False,
                          use_pallas: bool = True,
                          block_n: int | None = None,
                          block_v: int | None = None,
                          interpret: bool | None = None,
                          dw_chunks: int = 1):
    """`fused_logprob` sharded over the vocab axis of `mesh`.

    hidden (N, D) and targets (N,) enter replicated; the head enters split
    along its vocab dimension over `axis_name` (rows when transpose_head,
    columns otherwise — exactly how `sharding.DEFAULT_RULES` places
    `p_vocab`/`p_embed_vocab`). Each shard runs the single-device fused
    path (Pallas kernel or the blocked jnp twin per `use_pallas`) on its
    V/n slice with targets clipped into the slice; the cross-shard combine
    is three psums over (N,) vectors (see header comment). Falls back to
    the unsharded call when `mesh` is None, the axis is absent/size-1, or
    V does not divide — so callers can route unconditionally.

    Value and grads match the single-device path to fp32 tolerance (the
    shard cut only reassociates the vocab reduction, like a different
    block_v would)."""
    from jax.sharding import PartitionSpec as P

    interpret = default_interpret(interpret)
    if block_n is None:
        block_n = 256 if interpret else 128
    if block_v is None:
        block_v = 2048 if interpret else 512
    assert hidden.ndim == 2 and head.ndim == 2 and targets.ndim == 1
    V = head.shape[0] if transpose_head else head.shape[1]
    n = vocab_shard_count(mesh, axis_name, V)
    if n <= 1:
        if use_pallas:
            return fused_logprob(hidden, head, targets,
                                 transpose_head=transpose_head,
                                 block_n=block_n, block_v=block_v,
                                 interpret=interpret, dw_chunks=dw_chunks)
        return fused_logprob_blocked(hidden, head, targets,
                                     transpose_head=transpose_head,
                                     block_v=block_v)

    v_local = V // n

    def shard_fn(h, w, t):
        off = jax.lax.axis_index(axis_name).astype(jnp.int32) * v_local
        t_local = jnp.clip(t.astype(jnp.int32) - off, 0, v_local - 1)
        if use_pallas:
            lp_i, lse_i, ent_i = _fused(
                (int(block_n), int(block_v), bool(transpose_head),
                 bool(interpret), int(dw_chunks)), h, w, t_local)
        else:
            lp_i, lse_i, ent_i = _blocked(
                (int(block_v), bool(transpose_head)), h, w, t_local)
        owned = (t >= off) & (t < off + v_local)
        t_logit = lp_i + lse_i               # local logit of the clipped id
        # stable max of the shard lse's; pmax has no autodiff rule, so the
        # max rides an all_gather of the stopped values (m is a constant —
        # any shared offset gives the same lse, see the log-sum-exp form)
        m = jax.lax.all_gather(
            jax.lax.stop_gradient(lse_i), axis_name).max(axis=0)
        lse = m + jnp.log(jax.lax.psum(jnp.exp(lse_i - m), axis_name))
        ent = lse - jax.lax.psum(
            jnp.exp(lse_i - lse) * (lse_i - ent_i), axis_name)
        # non-owner rows gathered a clipped (wrong) id: the where() both
        # drops their contribution and zeroes their cotangent into lp_i
        tgt = jax.lax.psum(jnp.where(owned, t_logit, 0.0), axis_name)
        return tgt - lse, lse, ent

    w_spec = P(axis_name, None) if transpose_head else P(None, axis_name)
    return jax.shard_map(shard_fn, mesh=mesh,
                         in_specs=(P(), w_spec, P()),
                         out_specs=(P(), P(), P()),
                         check_vma=False)(hidden, head, targets)
