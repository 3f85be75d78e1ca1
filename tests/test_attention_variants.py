"""Deeper attention-variant coverage: MLA absorbed-decode equivalence,
blocked-vs-naive flash equivalence, MoE capacity behaviour, the slot
cache's row writes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need it; CPU image may lack it
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, smoke_config
from repro.models import moe as moe_mod
from repro.models.attention import (
    blocked_causal_attention, _naive_causal_attention, write_cache_rows,
)

KEY = jax.random.PRNGKey(11)


# ---------------------------------------------------------------------------
# blocked flash == naive reference (segment ids, windows)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 16])
def test_blocked_equals_naive(window):
    B, S, H, KV, D = 2, 256, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    blocked = blocked_causal_attention(q, k, v, scale=0.2, window=window,
                                       q_block=64, kv_block=64)
    naive = _naive_causal_attention(q, k, v, scale=0.2, window=window)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(naive),
                               atol=2e-5, rtol=2e-5)


def test_blocked_segment_ids():
    B, S, H, KV, D = 1, 128, 2, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    seg = jnp.concatenate([jnp.ones(64), jnp.full(64, 2)])[None].astype(jnp.int32)
    blocked = blocked_causal_attention(q, k, v, scale=0.25,
                                       segment_ids=seg, q_block=32,
                                       kv_block=32)
    naive = _naive_causal_attention(q, k, v, scale=0.25, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(naive),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# ring-buffer cache writes
# ---------------------------------------------------------------------------

def test_write_cache_scalar_wraps():
    cache = jnp.zeros((2, 2, 4, 3))       # (L, B, CL, ...), ring on axis 2
    new = jnp.ones((2, 1, 3))
    out = write_cache_rows(cache, new, 1, jnp.int32(5), 2)  # 5 % 4 == 1
    assert float(out[1, :, 1].sum()) == 6.0
    assert float(out.sum()) == 6.0


@given(st.lists(st.integers(0, 30), min_size=2, max_size=2))
@settings(max_examples=20, deadline=None)
def test_write_cache_per_slot(idx):
    CL = 8
    cache = jnp.zeros((2, 2, CL, 3))      # (L, B, CL, ...), ring on axis 2
    new = jnp.ones((2, 1, 3))
    out = write_cache_rows(cache, new, 1, jnp.asarray(idx), 2)
    for b in range(2):
        assert float(out[1, b, idx[b] % CL].sum()) == 3.0
    assert float(out.sum()) == 6.0


# ---------------------------------------------------------------------------
# MoE routing invariants
# ---------------------------------------------------------------------------

def _moe_setup(T=64, E=4, k=2, d=16, F=32):
    cfg = dataclasses.replace(
        smoke_config(get_config("granite-moe-1b-a400m")),
        n_experts=E, experts_per_token=k, moe_d_ff=F, d_model=d,
        capacity_factor=2.0)
    ks = jax.random.split(KEY, 4)
    p = {
        "router": jax.random.normal(ks[0], (d, E)),
        "gate": jax.random.normal(ks[1], (E, d, F)) * 0.05,
        "up": jax.random.normal(ks[2], (E, d, F)) * 0.05,
        "down": jax.random.normal(ks[3], (E, F, d)) * 0.05,
    }
    x = jax.random.normal(KEY, (T, d))
    return cfg, p, x


def test_moe_output_finite_and_shaped():
    cfg, p, x = _moe_setup()
    out, aux = moe_mod._moe_local(p, x, cfg, cfg.n_experts, 0, None)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux) >= 1.0 - 1e-5  # Switch aux loss lower bound is 1


def test_moe_capacity_drops_tokens():
    """With capacity_factor -> tiny, most tokens overflow and get zero
    contribution (dropped), so the output norm shrinks."""
    cfg, p, x = _moe_setup()
    lo = dataclasses.replace(cfg, capacity_factor=2.0)
    hi_drop = dataclasses.replace(cfg, capacity_factor=0.01)
    out_full, _ = moe_mod._moe_local(p, x, lo, cfg.n_experts, 0, None)
    out_drop, _ = moe_mod._moe_local(p, x, hi_drop, cfg.n_experts, 0, None)
    assert float(jnp.linalg.norm(out_drop)) < float(jnp.linalg.norm(out_full))


def test_moe_expert_partition_sums_to_whole():
    """Sum of per-shard contributions (disjoint expert ranges) must equal
    the all-experts-local result — the shard_map psum invariant."""
    cfg, p, x = _moe_setup(E=4)
    full, _ = moe_mod._moe_local(p, x, cfg, 4, 0, None)
    parts = []
    for off in (0, 2):
        pl = {k: (v[off:off + 2] if k != "router" else v)
              for k, v in p.items()}
        part, _ = moe_mod._moe_local(pl, x, cfg, 2, off, None)
        parts.append(part)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(full),
                               atol=1e-5, rtol=1e-5)
