"""Pallas kernel microbenchmarks (interpret mode on CPU: numbers measure the
reference execution, not TPU performance — the derived column reports the
analytic FLOPs so TPU projections use the roofline, not these timings)."""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from benchmarks.common import time_call
from repro.kernels import ops

Row = Tuple[str, float, str]
KEY = jax.random.PRNGKey(0)


def kernel_benchmarks() -> List[Row]:
    rows: List[Row] = []

    B, H, KV, S, D = 1, 8, 2, 256, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, KV, S, D))
    v = jax.random.normal(ks[2], (B, KV, S, D))
    us, _ = time_call(ops.flash_attention, q, k, v, scale=D ** -0.5,
                      block_q=128, block_k=128, iters=3)
    flops = 4 * B * H * S * S * D
    rows.append(("kernel/flash_attention_256", us, f"flops={flops:.0f}"))

    CL = 512
    kc = jax.random.normal(ks[1], (1, B, KV, CL, D))   # one layer's stack
    vc = jax.random.normal(ks[2], (1, B, KV, CL, D))
    qd = jax.random.normal(ks[0], (B, H, D))
    us, _ = time_call(ops.flash_decode, qd, kc, vc,
                      jnp.full((B,), CL), scale=D ** -0.5, iters=3)
    rows.append(("kernel/flash_decode_512", us,
                 f"flops={4 * B * H * CL * D:.0f}"))

    b, l, h, p, g, n = 1, 256, 4, 32, 1, 32
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    Bm = jax.random.normal(ks[1], (b, l, g, n))
    Cm = jax.random.normal(ks[2], (b, l, g, n))
    us, _ = time_call(ops.ssd_scan, x, dt, A, Bm, Cm, chunk=64, iters=3)
    rows.append(("kernel/ssd_scan_256", us,
                 f"flops~{2 * b * l * h * p * n * 3:.0f}"))
    return rows
