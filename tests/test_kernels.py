"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,H,KV,S,D", [
    (1, 4, 4, 128, 64),    # MHA
    (2, 8, 2, 256, 64),    # GQA 4:1
    (1, 8, 1, 128, 128),   # MQA
    (2, 4, 4, 192, 32),    # S not a multiple of 128 -> smaller blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, KV, S, D, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, D), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, D), dtype)
    bq = 64 if S % 64 == 0 else S
    out = ops.flash_attention(q, k, v, scale=D ** -0.5, block_q=bq, block_k=bq)
    expected = ref.flash_attention_ref(q, k, v, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,H,KV,CL,D,block", [
    (2, 8, 2, 128, 64, 32),
    (1, 4, 4, 256, 64, 64),
    (3, 8, 1, 64, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(B, H, KV, CL, D, block, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kc = jax.random.normal(ks[1], (B, KV, CL, D), dtype)
    vc = jax.random.normal(ks[2], (B, KV, CL, D), dtype)
    lengths = jnp.arange(1, B + 1) * (CL // (B + 1)) + 1
    out = ops.flash_decode(q, kc[None], vc[None], lengths, scale=D ** -0.5,
                           block_k=block)
    expected = ref.flash_decode_ref(q, kc, vc, lengths, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), **_tol(dtype))


def test_flash_decode_full_ring():
    """lengths == CL must attend to every slot (ring-buffer mode)."""
    B, H, KV, CL, D = 1, 4, 2, 64, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kc = jax.random.normal(ks[1], (B, KV, CL, D))
    vc = jax.random.normal(ks[2], (B, KV, CL, D))
    out = ops.flash_decode(q, kc[None], vc[None], jnp.full((B,), CL),
                           scale=D ** -0.5, block_k=32)
    expected = ref.flash_decode_ref(q, kc, vc, jnp.full((B,), CL),
                                    scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_max_len_hint():
    """A static hint >= max(lengths) shrinks the KV grid without changing
    the result (grid-level early exit)."""
    B, H, KV, CL, D, block = 2, 4, 2, 256, 32, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kc = jax.random.normal(ks[1], (1, B, KV, CL, D))
    vc = jax.random.normal(ks[2], (1, B, KV, CL, D))
    lengths = jnp.asarray([37, 70])
    full = ops.flash_decode(q, kc, vc, lengths, scale=D ** -0.5, block_k=block)
    for hint in (70, 96, 255):   # any hint >= max(lengths) is exact
        out = ops.flash_decode(q, kc, vc, lengths, scale=D ** -0.5,
                               block_k=block, max_len_hint=hint)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                                   atol=2e-5, rtol=2e-5, err_msg=str(hint))


@pytest.mark.parametrize("B,H,KV,C,CL,D,off,block", [
    (2, 4, 2, 16, 128, 32, 0, 64),     # first chunk: empty cache
    (2, 4, 2, 16, 128, 32, 48, 64),    # mid-prompt, full-length cache
    (1, 8, 1, 8, 64, 64, 64, 32),      # MQA, ring exactly full
    (1, 4, 4, 8, 32, 16, 72, 16),      # MHA, ring wrapped twice
    (2, 8, 2, 4, 32, 64, 36, 32),      # chunk straddling the ring window
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefill_attention_sweep(B, H, KV, C, CL, D, off, block, dtype):
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, C, H, D), dtype)
    kh = jax.random.normal(ks[1], (B, C, KV, D), dtype)
    vh = jax.random.normal(ks[2], (B, C, KV, D), dtype)
    kc = jax.random.normal(ks[3], (B, KV, CL, D), dtype)
    vc = jax.random.normal(ks[4], (B, KV, CL, D), dtype)
    out = ops.prefill_attention(q, kh, vh, kc, vc, jnp.int32(off),
                                scale=D ** -0.5, block_k=block)
    expected = ref.prefill_attention_ref(q, kh, vh, kc, vc, off,
                                         scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), **_tol(dtype))


def test_prefill_attention_matches_sequential_window():
    """Independent oracle: build the ring cache by sequential writes of an
    absolute K/V history, then check every chunk query attends exactly the
    sliding window [qp-CL+1, qp] of that history — the invariant that makes
    chunked admission equal the per-token decode loop on ring caches."""
    B, H, KV, D, CL, C = 1, 4, 2, 16, 8, 4
    rep = H // KV
    for off in (0, 4, 8, 12, 20):
        S = off + C
        ks = jax.random.split(jax.random.fold_in(KEY, off), 3)
        kfull = jax.random.normal(ks[0], (B, S, KV, D))
        vfull = jax.random.normal(ks[1], (B, S, KV, D))
        q = jax.random.normal(ks[2], (B, C, H, D))
        kc = jnp.zeros((B, KV, CL, D))
        vc = jnp.zeros((B, KV, CL, D))
        for p in range(off):            # the sequential decode loop's writes
            kc = kc.at[:, :, p % CL].set(kfull[:, p])
            vc = vc.at[:, :, p % CL].set(vfull[:, p])
        out = ops.prefill_attention(q, kfull[:, off:], vfull[:, off:],
                                    kc, vc, jnp.int32(off), scale=D ** -0.5,
                                    block_k=CL)
        exp = np.zeros((B, C, H, D), np.float32)
        for i in range(C):
            qp = off + i
            lo = max(0, qp - CL + 1)
            keys = np.asarray(kfull[:, lo:qp + 1])
            vals = np.asarray(vfull[:, lo:qp + 1])
            qr = np.asarray(q[:, i]).reshape(B, KV, rep, D)
            s = np.einsum("bgrd,bkgd->bgrk", qr, keys) * D ** -0.5
            pw = np.exp(s - s.max(-1, keepdims=True))
            pw /= pw.sum(-1, keepdims=True)
            exp[:, i] = np.einsum("bgrk,bkgd->bgrd", pw, vals).reshape(B, H, D)
        np.testing.assert_allclose(np.asarray(out, np.float32), exp,
                                   atol=2e-5, rtol=2e-5, err_msg=f"off={off}")


def test_prefill_attention_offset_hint():
    """A static offset_hint >= min(offset, CL) shrinks the cache-block
    grid without changing the result (grid-level early exit, the prefill
    mirror of flash_decode's max_len_hint). offset=0 launches no cache
    blocks at all."""
    B, H, KV, C, CL, D, block = 1, 4, 2, 8, 256, 32, 32
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, C, H, D))
    kh = jax.random.normal(ks[1], (B, C, KV, D))
    vh = jax.random.normal(ks[2], (B, C, KV, D))
    kc = jax.random.normal(ks[3], (B, KV, CL, D))
    vc = jax.random.normal(ks[4], (B, KV, CL, D))
    for off in (0, 40, 96, 300):    # 300 > CL: wrapped ring, all slots live
        full = ops.prefill_attention(q, kh, vh, kc, vc, jnp.int32(off),
                                     scale=D ** -0.5, block_k=block)
        lo = min(off, CL)
        for hint in (lo, -(-lo // block) * block, CL):
            out = ops.prefill_attention(q, kh, vh, kc, vc, jnp.int32(off),
                                        scale=D ** -0.5, block_k=block,
                                        offset_hint=hint)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(full), atol=2e-5, rtol=2e-5,
                err_msg=f"off={off} hint={hint}")


@pytest.mark.parametrize("b,l,h,p,g,n,chunk", [
    (1, 64, 2, 16, 1, 8, 16),
    (2, 128, 4, 32, 2, 16, 32),
    (1, 96, 6, 16, 3, 8, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(b, l, h, p, g, n, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, l, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, l, g, n), dtype)
    C = jax.random.normal(ks[4], (b, l, g, n), dtype)
    out, st = ops.ssd_scan(x, dt, A, B, C, chunk=chunk)
    expected, st_ref = ref.ssd_scan_ref(
        x.astype(jnp.float32), dt, A, B.astype(jnp.float32),
        C.astype(jnp.float32), chunk=chunk)
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(st, np.float32),
                               np.asarray(st_ref, np.float32), **tol)


def test_ssd_scan_state_carries_across_chunks():
    """A signal in chunk 0 must influence outputs in the last chunk."""
    b, l, h, p, g, n, chunk = 1, 64, 1, 8, 1, 8, 16
    ks = jax.random.split(KEY, 5)
    x = jnp.zeros((b, l, h, p)).at[0, 3].set(1.0)
    dt = jnp.full((b, l, h), 0.05)
    A = -jnp.ones((h,)) * 0.01  # slow decay
    B = jnp.ones((b, l, g, n))
    C = jnp.ones((b, l, g, n))
    y, _ = ops.ssd_scan(x, dt, A, B, C, chunk=chunk)
    assert float(jnp.abs(y[0, -1]).max()) > 1e-4
