"""Pallas TPU kernel for the Mamba2 SSD chunked scan. [arXiv:2405.21060]

TPU adaptation: the chunk dimension is the sequential trailing grid axis;
the (P, N) recurrent state lives in VMEM scratch and is carried across
chunks — the HBM traffic is exactly one pass over x/dt/B/C plus the y
writeback, and all three chunk-local contractions (C@B^T, score@x, C@state)
are MXU matmuls. Chunk length Q and head dim P should be multiples of 8/128
for lane alignment (Q=64..128 fits VMEM comfortably at N=128).

grid = (batch, heads, n_chunks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.common import default_interpret


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, fstate_ref,
                state_ref, *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0].astype(jnp.float32)            # (Q,P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)          # (Q,)
    b = b_ref[0, :, 0].astype(jnp.float32)            # (Q,N)
    c = c_ref[0, :, 0].astype(jnp.float32)            # (Q,N)
    A = a_ref[0]                                      # scalar (negative)

    dA = dt * A                                       # (Q,)
    A_cum = jnp.cumsum(dA)                            # (Q,)
    xd = x * dt[:, None]                              # (Q,P)

    # intra-chunk: L[i,j] = exp(A_cum[i] - A_cum[j]) for i >= j
    seg = A_cum[:, None] - A_cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    Lmat = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * Lmat
    y = jax.lax.dot_general(scores, xd, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q,P)

    # inter-chunk: contribution of the carried state
    state = state_ref[...]                            # (N,P)
    y += jnp.exp(A_cum)[:, None] * jax.lax.dot_general(
        c, state, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # state update: state' = exp(sum dA) * state + B^T @ (decay * xd)
    decay = jnp.exp(A_cum[-1] - A_cum)                # (Q,)
    state_ref[...] = jnp.exp(A_cum[-1]) * state + jax.lax.dot_general(
        b, xd * decay[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        fstate_ref[0, 0] = state_ref[...]


def ssd_scan(x, dt, A, B, C, *, chunk: int = 64,
             interpret: bool | None = None):
    """x: (b,l,h,p); dt: (b,l,h) (softplus'd); A: (h,) negative;
    B,C: (b,l,g,n). Returns (y (b,l,h,p), final_state (b,h,n,p))
    (no D skip / gating — see ops.py). interpret=None: auto by backend."""
    interpret = default_interpret(interpret)
    bsz, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    return pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1,), lambda b_, h_, c_: (h_,),
                         memory_space=pltpu.MemorySpace.SMEM),
            pl.BlockSpec((1, chunk, 1, p), lambda b_, h_, c_: (b_, c_, h_, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b_, h_, c_: (b_, c_, h_)),
            pl.BlockSpec((1, chunk, 1, n), lambda b_, h_, c_, _r=rep: (b_, c_, h_ // _r, 0)),
            pl.BlockSpec((1, chunk, 1, n), lambda b_, h_, c_, _r=rep: (b_, c_, h_ // _r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda b_, h_, c_: (b_, c_, h_, 0)),
            pl.BlockSpec((1, 1, n, p), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, l, h, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(A.astype(jnp.float32), x, dt, B, C)
