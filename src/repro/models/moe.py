"""Mixture-of-Experts with expert parallelism.

Design (TPU-native, see DESIGN.md §4): experts are sharded over the "model"
mesh axis; tokens are sharded over ("pod","data") and *replicated* along
"model", so each model-column computes only its local experts' contribution
and a single psum over "model" combines them — the same collective pattern
as a tensor-parallel FFN (dispatch stays device-local; no all-to-all).
Dispatch is capacity-bounded and sort-free: k sequential top-1 passes keep
the position-in-expert cumsum at O(T*E) and scatter (T,d) rows per pass —
never materializing a (T,E,C) GShard dispatch tensor or a (T*k,d) gather.

Runs inside shard_map when a mesh context is active, or as plain local code
(single-device smoke tests).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import ParamDef
from repro.sharding import logical_to_spec
from repro.shardctx import current_mesh, current_rules


def moe_defs(cfg: ModelConfig, n_stack: int) -> Dict[str, ParamDef]:
    d, dt = cfg.d_model, cfg.dtype
    E, F = cfg.n_experts, cfg.moe_d_ff
    L, Ll = (n_stack,), ("layers",)
    out_scale = 0.02 / np.sqrt(2 * cfg.n_layers)
    defs = {
        "router": ParamDef(L + (d, E), Ll + ("p_embed", "p_experts"), jnp.float32),
        "gate": ParamDef(L + (E, d, F), Ll + ("p_experts", "p_embed", "p_mlp"), dt),
        "up": ParamDef(L + (E, d, F), Ll + ("p_experts", "p_embed", "p_mlp"), dt),
        "down": ParamDef(L + (E, F, d), Ll + ("p_experts", "p_mlp", "p_embed"), dt, out_scale),
    }
    if cfg.n_shared_experts:
        SF = cfg.moe_d_ff * cfg.n_shared_experts
        defs.update({
            "shared_gate": ParamDef(L + (d, SF), Ll + ("p_embed", "p_mlp"), dt),
            "shared_up": ParamDef(L + (d, SF), Ll + ("p_embed", "p_mlp"), dt),
            "shared_down": ParamDef(L + (SF, d), Ll + ("p_mlp", "p_embed"), dt, out_scale),
        })
    return defs


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(math.ceil(cfg.experts_per_token * n_tokens * cfg.capacity_factor
                      / cfg.n_experts))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def _moe_local(p, x, cfg: ModelConfig, n_local: int, offset,
               expert_axis: Optional[str]):
    """x: (T, d) local tokens; expert weights already local (n_local,...).
    Computes the contribution of experts [offset, offset+n_local) and psums
    over expert_axis if given. Returns (out (T,d), aux_loss scalar)."""
    T, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    C = _capacity(T, cfg)

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                      # (T,E)

    # --- load-balance auxiliary loss (Switch-style) ---
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))

    # --- top-k routing as k sequential top-1 passes ---
    masked = probs
    dests, weights = [], []
    counts = jnp.zeros((E,), jnp.int32)
    for _ in range(k):
        w = masked.max(axis=-1)                                  # (T,)
        e = masked.argmax(axis=-1)                               # (T,)
        masked = masked * (1.0 - jax.nn.one_hot(e, E, dtype=jnp.float32))
        onehot = jax.nn.one_hot(e, E, dtype=jnp.int32)
        pos = counts[e] + (jnp.cumsum(onehot, axis=0) - onehot)[jnp.arange(T), e]
        counts = counts + onehot.sum(axis=0)
        local = (e >= offset) & (e < offset + n_local) & (pos < C)
        dests.append(jnp.where(local, (e - offset) * C + pos, n_local * C))
        weights.append(w)

    # --- dispatch: scatter (T,d) rows per pass into (n_local*C [+ovf], d) ---
    buf = jnp.zeros((n_local * C + 1, d), x.dtype)
    for dest in dests:
        buf = buf.at[dest].add(x, mode="drop")
    eb = buf[:n_local * C].reshape(n_local, C, d)

    # --- expert FFN (SwiGLU), batched over local experts ---
    g = jnp.einsum("ecd,edf->ecf", eb, p["gate"])
    u = jnp.einsum("ecd,edf->ecf", eb, p["up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    eo = jnp.einsum("ecf,efd->ecd", h, p["down"])                # (n_local,C,d)

    # --- combine: gather back per pass, router-weighted ---
    flat = jnp.concatenate([eo.reshape(n_local * C, d),
                            jnp.zeros((1, d), x.dtype)])
    out = jnp.zeros((T, d), x.dtype)
    for dest, w in zip(dests, weights):
        out = out + flat[dest] * w[:, None].astype(x.dtype)
    if expert_axis is not None:
        out = jax.lax.psum(out, expert_axis)
    return out, aux


def moe_apply(p, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,d) global. Returns (out, aux_loss)."""
    B, S, d = x.shape
    mesh = current_mesh()
    E = cfg.n_experts
    xf = x.reshape(B * S, d)

    # token sharding falls back to replicated automatically when B*S is not
    # divisible (logical_to_spec drops the axis), so expert-parallel shard_map
    # only requires the expert count to divide the model axis
    use_ep = (mesh is not None and "model" in mesh.axis_names
              and E % mesh.shape["model"] == 0)
    routed = {k: p[k] for k in ("router", "gate", "up", "down")}
    if use_ep:
        rules = current_rules()
        x_spec = logical_to_spec(("batch", "embed"), xf.shape, mesh, rules)
        ep = mesh.shape["model"]
        n_local = E // ep
        w_specs = {
            "router": P(None, None),
            "gate": P("model", None, None),
            "up": P("model", None, None),
            "down": P("model", None, None),
        }

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(w_specs, x_spec),
            out_specs=(x_spec, P()),
            check_vma=False)
        def run(pl, xl):
            idx = jax.lax.axis_index("model")
            out, aux = _moe_local(pl, xl, cfg, n_local, idx * n_local, "model")
            axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
            if axes:
                aux = jax.lax.pmean(aux, axes)
            return out, aux

        out, aux = run(routed, xf)
    else:
        out, aux = _moe_local(routed, xf, cfg, E, 0, None)

    out = out.reshape(B, S, d)
    if cfg.n_shared_experts:
        g = jnp.einsum("bsd,df->bsf", x, p["shared_gate"])
        u = jnp.einsum("bsd,df->bsf", x, p["shared_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        out = out + jnp.einsum("bsf,fd->bsd", h, p["shared_down"])
    return out, aux
