"""The composable decoder LM: one definition covering all 10 assigned
architectures (dense GQA, MLA+MoE, pure-SSM, hybrid, VLM/audio prefix).

Layers with identical structure are stacked and scanned (`lax.scan`), which
keeps the HLO size O(1) in depth — essential for compiling 61-layer models
on the 512-device dry-run mesh. Heterogeneous stacks (DeepSeek's 3 dense +
58 MoE layers) become consecutive scan *groups*.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, effective_cache_len
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import ParamDef, build_params, ffn_defs, rms_norm, swiglu
from repro.shardctx import constrain


# ---------------------------------------------------------------------------
# parameter definitions
# ---------------------------------------------------------------------------

def layer_groups(cfg: ModelConfig) -> List[Tuple[str, int]]:
    """[(ffn_kind, n_layers)] — ffn_kind in {dense, moe, none}."""
    if cfg.n_experts:
        if cfg.n_dense_layers:
            return [("dense", cfg.n_dense_layers),
                    ("moe", cfg.n_layers - cfg.n_dense_layers)]
        return [("moe", cfg.n_layers)]
    if cfg.d_ff == 0:
        return [("none", cfg.n_layers)]
    return [("dense", cfg.n_layers)]


def _group_defs(cfg: ModelConfig, kind: str, count: int) -> Dict[str, Any]:
    d, dt = cfg.d_model, cfg.dtype
    g: Dict[str, Any] = {
        "norm1": ParamDef((count, d), ("layers", "p_embed"), dt, -1.0),
    }
    if cfg.has_attention:
        g["attn"] = attn.attention_defs(cfg, count)
    if cfg.has_ssm:
        g["ssm"] = ssm_mod.ssm_defs(cfg, count)
    if cfg.hybrid_parallel:
        # Hymba: per-branch output norms fused by averaging [arXiv:2411.13676]
        g["hyb_norm_a"] = ParamDef((count, d), ("layers", "p_embed"), dt, -1.0)
        g["hyb_norm_s"] = ParamDef((count, d), ("layers", "p_embed"), dt, -1.0)
    if kind == "dense":
        ff = cfg.dense_d_ff if (cfg.n_experts and cfg.dense_d_ff) else cfg.d_ff
        g["norm2"] = ParamDef((count, d), ("layers", "p_embed"), dt, -1.0)
        g["ffn"] = ffn_defs(d, ff, count, dt)
    elif kind == "moe":
        g["norm2"] = ParamDef((count, d), ("layers", "p_embed"), dt, -1.0)
        g["moe"] = moe_mod.moe_defs(cfg, count)
    return g


def param_defs(cfg: ModelConfig) -> Dict[str, Any]:
    d, dt, V = cfg.d_model, cfg.dtype, cfg.vocab_size
    defs: Dict[str, Any] = {
        "embed": ParamDef((V, d), ("p_embed_vocab", "p_embed"), dt),
        "final_norm": ParamDef((d,), ("p_embed",), dt, -1.0),
        "groups": [
            _group_defs(cfg, kind, count) for kind, count in layer_groups(cfg)
        ],
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, V), ("p_embed", "p_vocab"), dt)
    if cfg.use_value_head:
        defs["value_head"] = ParamDef((d, 1), ("p_embed", None), jnp.float32, 0.0)
    if cfg.modality in ("vision", "audio"):
        # learned projector from the (stubbed) frontend embedding space
        defs["mm_proj"] = ParamDef((d, d), ("p_embed", None), dt)
    if cfg.use_mtp:
        defs["mtp"] = {
            "proj": ParamDef((2 * d, d), ("p_embed", "p_embed"), dt),
            "norm_h": ParamDef((d,), ("p_embed",), dt, -1.0),
            "norm_e": ParamDef((d,), ("p_embed",), dt, -1.0),
            "layer": _group_defs(cfg, "dense", 1),
        }
    return defs


def init_params(cfg: ModelConfig, key=None, abstract: bool = False):
    """Annotated param tree (Annotated leaves carry logical axes)."""
    return build_params(param_defs(cfg), key=key, abstract=abstract)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _layer_forward(cfg: ModelConfig, kind: str, h, lp, positions, segment_ids,
                   return_kv: bool):
    """One decoder layer; h: (B,S,d). Returns (h, aux, kv_for_cache)."""
    aux = jnp.zeros((), jnp.float32)
    x = rms_norm(h, lp["norm1"], cfg.norm_eps)
    kv = None
    if cfg.hybrid_parallel:
        a, kv_a = attn.gqa_forward(lp["attn"], x, positions, cfg,
                                   segment_ids, return_kv=True)
        s, st = ssm_mod.ssm_forward(lp["ssm"], x, cfg, return_state=True)
        mix = 0.5 * (rms_norm(a, lp["hyb_norm_a"], cfg.norm_eps)
                     + rms_norm(s, lp["hyb_norm_s"], cfg.norm_eps))
        h = h + mix
        kv = {"k": jnp.swapaxes(kv_a[0], 1, 2),
              "v": jnp.swapaxes(kv_a[1], 1, 2), "conv": st[0], "ssd": st[1]}
    elif cfg.arch_type == "ssm":
        s, st = ssm_mod.ssm_forward(lp["ssm"], x, cfg, return_state=True)
        h = h + s
        kv = {"conv": st[0], "ssd": st[1]}
    else:
        fwd = attn.mla_forward if cfg.use_mla else attn.gqa_forward
        a, kv_a = fwd(lp["attn"], x, positions, cfg, segment_ids, return_kv=True)
        h = h + a
        if cfg.use_mla:
            kv = {"c_kv": kv_a[0], "k_rope": kv_a[1]}
        else:  # the slot cache's head-major layout (B,KV,S,D)
            kv = {"k": jnp.swapaxes(kv_a[0], 1, 2),
                  "v": jnp.swapaxes(kv_a[1], 1, 2)}
    h = constrain(h, ("batch", "seq", "embed"))

    if kind == "dense":
        x = rms_norm(h, lp["norm2"], cfg.norm_eps)
        f = lp["ffn"]
        h = h + swiglu(x, f["gate"], f["up"], f["down"])
    elif kind == "moe":
        x = rms_norm(h, lp["norm2"], cfg.norm_eps)
        mo, aux = moe_mod.moe_apply(lp["moe"], x, cfg)
        h = h + mo
    h = constrain(h, ("batch", "seq", "embed"))
    return h, aux, (kv if return_kv else None)


def forward(params, tokens, positions, cfg: ModelConfig, *,
            segment_ids=None, prefix_embeds=None, return_cache: bool = False,
            return_hidden: bool = False, loss_targets=None):
    """Full-sequence forward.

    tokens: (B,S) int32; positions: (B,S) int32.
    Returns dict(logits, values?, aux_loss, cache?, hidden?).
    The multimodal prefix (if any) is prepended; its rows are stripped from
    logits/values so downstream shapes match `tokens`.

    loss_targets: optional (B,S) int32 next-token targets (position t holds
    the token logits[t] should score, i.e. tokens[t+1]; the last column is
    a dead pad). With `cfg.fused_loss` set, the head matmul + cross-entropy
    fuse into the blockwise kernel (`kernels.fused_logprob`): no logits are
    materialized and the output carries `token_logprobs` / `lse` /
    `entropy` instead, each (B,S) f32 aligned with `tokens` the way
    `algo.token_logprobs` aligns them (entry t describes the distribution
    that scored token t; entry 0 is a zero pad). The MTP head rides the
    same fused call (per-draft stats `mtp_token_logprobs` / `mtp_lse` /
    `mtp_entropy` instead of `mtp_logits`); value head and the MoE aux
    loss are unchanged.
    """
    B, S = tokens.shape
    h = jnp.take(params["embed"], tokens, axis=0)
    n_prefix = 0
    if prefix_embeds is not None:
        n_prefix = prefix_embeds.shape[1]
        pe = jnp.einsum("bpd,de->bpe", prefix_embeds.astype(cfg.dtype),
                        params["mm_proj"])
        h = jnp.concatenate([pe, h], axis=1)
        positions = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(n_prefix, dtype=positions.dtype)[None],
                              (B, n_prefix)),
             positions + n_prefix], axis=1)
        if segment_ids is not None:
            segment_ids = jnp.concatenate(
                [jnp.zeros((B, n_prefix), segment_ids.dtype), segment_ids], axis=1)
    h = constrain(h, ("batch", "seq", "embed"))

    total_aux = jnp.zeros((), jnp.float32)
    caches = []
    for gi, (kind, count) in enumerate(layer_groups(cfg)):
        gp = params["groups"][gi]

        def scan_body(carry, lp, _kind=kind):
            hh, aux_acc = carry
            hh, aux, kv = _layer_forward(cfg, _kind, hh, lp, positions,
                                         segment_ids, return_cache)
            return (hh, aux_acc + aux), kv

        if cfg.remat:
            # activation checkpointing: save only the per-layer residual
            # stream; recompute attention/FFN internals in the backward pass
            scan_body = jax.checkpoint(
                scan_body,
                policy=jax.checkpoint_policies.nothing_saveable)
        (h, total_aux), kvs = jax.lax.scan(scan_body, (h, total_aux), gp,
                                           unroll=True if cfg.scan_unroll else 1)
        if return_cache:
            caches.append(kvs)

    hidden = h
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    out = {"aux_loss": total_aux, "n_prefix": n_prefix}
    fused = cfg.fused_loss and loss_targets is not None
    if fused:
        out.update(_fused_loss_stats(params, cfg, h[:, n_prefix:],
                                     loss_targets))
    else:
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", h, head)
        logits = constrain(logits, ("batch", "seq", "vocab"))
        out["logits"] = logits[:, n_prefix:]
    if cfg.use_value_head:
        values = jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32),
                            params["value_head"])[..., 0]
        out["values"] = values[:, n_prefix:]
    if cfg.use_mtp:
        if fused:
            out.update(_mtp_fused_stats(params, cfg, hidden, tokens,
                                        positions, n_prefix))
        else:
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
            out["mtp_logits"] = _mtp_forward(params, cfg, hidden, tokens,
                                             positions, n_prefix, head)
    if return_cache:
        out["cache"] = _stack_group_caches(cfg, caches)
    if return_hidden:
        out["hidden"] = hidden[:, n_prefix:]
    return out


def _fused_head_stats(params, cfg: ModelConfig, hs, tgt):
    """Shared fused lm-head routing for the loss and MTP stats: hs (N,D)
    rows against the lm head, targets (N,) int32. Returns (lp, lse, ent).

    Tied embeddings pass `params["embed"]` in its native (V,D) layout
    (`transpose_head`) so no transposed head copy is materialized. When a
    mesh is active (`shardctx.sharding_context`) and the head's vocab
    logical axis maps to a mesh axis, the call routes through
    `fused_logprob_sharded`: each shard runs the ordinary fused path on
    its V/n head slice and the global stats come from three (N,) psums —
    the (N,V)-free property then holds per shard (DESIGN.md §11). The
    sharded wrapper itself falls back to the single-device call when the
    axis is absent, size 1, or does not divide V, so routing here is
    unconditional on mesh presence only."""
    from repro.shardctx import current_mesh, current_rules
    if cfg.tie_embeddings:
        head, transpose_head = params["embed"], True
        logical = "p_embed_vocab"
    else:
        head, transpose_head = params["lm_head"], False
        logical = "p_vocab"
    mesh = current_mesh()
    if mesh is not None:
        from repro.sharding import DEFAULT_RULES
        rules = dict(DEFAULT_RULES, **(current_rules() or {}))
        axis = rules.get(logical)
        if isinstance(axis, str):
            from repro.kernels.fused_logprob import fused_logprob_sharded
            return fused_logprob_sharded(
                hs, head, tgt, mesh=mesh, axis_name=axis,
                transpose_head=transpose_head, use_pallas=cfg.use_pallas,
                interpret=cfg.pallas_interpret)
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        return kops.fused_logprob(
            hs, head, tgt, transpose_head=transpose_head,
            interpret=cfg.pallas_interpret)
    from repro.kernels.fused_logprob import fused_logprob_blocked
    return fused_logprob_blocked(hs, head, tgt,
                                 transpose_head=transpose_head)


def _fused_loss_stats(params, cfg: ModelConfig, h, loss_targets):
    """Fused lm-head + cross-entropy (DESIGN.md §6): per-token stats of the
    sampled tokens without materializing (B,S,V) logits.

    h: (B,S,D) post-final-norm hidden states (multimodal prefix already
    stripped); loss_targets: (B,S) with targets[t] = tokens[t+1] (last
    column dead). Returns token_logprobs / lse / entropy, each (B,S) f32
    shifted to the `algo.token_logprobs` alignment: entry t describes the
    distribution that scored token t (entry 0 is a zero pad, masked by
    loss_mask downstream — prompts start at position >= 1).

    The Pallas kernel runs when `use_pallas` is set (interpret plumbed
    like every other kernel); otherwise the compiled blockwise jnp twin
    `fused_logprob_blocked` — same tiling and VJP-recompute math as a
    lax.scan, so the no-materialization property holds on every backend
    (the full-logits oracle lives in kernels/ref.py, tests only). Under an
    active mesh the head call is vocab-sharded — see `_fused_head_stats`.
    """
    B, S, D = h.shape
    hs = h.reshape(B * S, D)
    tgt = loss_targets.reshape(B * S).astype(jnp.int32)
    lp, lse, ent = _fused_head_stats(params, cfg, hs, tgt)

    def shift(x):  # (B,S) stats of position t -> aligned with token t+1
        return jnp.pad(x.reshape(B, S)[:, :-1], ((0, 0), (1, 0)))

    return {"token_logprobs": shift(lp), "lse": shift(lse),
            "entropy": shift(ent)}


def _mtp_hidden(params, cfg, hidden, tokens, positions, n_prefix):
    """DeepSeek-V3 MTP trunk: [norm(h_t); norm(emb_{t+1})] -> proj -> one
    extra layer -> final norm. Returns the pre-head hidden (B, S-1, D);
    row t carries the draft prediction of token t+2."""
    mp = params["mtp"]
    h = hidden[:, n_prefix:]
    h_t = rms_norm(h[:, :-1], mp["norm_h"], cfg.norm_eps)
    e_next = rms_norm(jnp.take(params["embed"], tokens[:, 1:], axis=0),
                      mp["norm_e"], cfg.norm_eps)
    x = jnp.einsum("bse,ed->bsd", jnp.concatenate([h_t, e_next], axis=-1),
                   mp["proj"])
    lp = jax.tree.map(lambda a: a[0], mp["layer"])  # single stacked layer
    x, _, _ = _layer_forward(cfg, "dense", x, lp, positions[:, 1:], None, False)
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def _mtp_forward(params, cfg, hidden, tokens, positions, n_prefix, head):
    """MTP logits oracle: (B, S-1, V) for targets t+2. Only used when the
    fused loss is off — the fused path goes through `_mtp_fused_stats`."""
    x = _mtp_hidden(params, cfg, hidden, tokens, positions, n_prefix)
    return jnp.einsum("bsd,dv->bsv", x, head)


def _mtp_fused_stats(params, cfg, hidden, tokens, positions, n_prefix):
    """Fused-loss coverage for the MTP head: per-draft targets (row t of
    the MTP trunk predicts token t+2) through the same fused lm-head call
    as the main loss, so the draft head stops materializing its own
    (B, S-1, V) logits. Returns mtp_token_logprobs / mtp_lse /
    mtp_entropy, each (B, S-1) f32 in MTP row alignment (entry t scores
    token t+2; the last row is a dead pad, like the main loss targets'
    last column)."""
    x = _mtp_hidden(params, cfg, hidden, tokens, positions, n_prefix)
    B, Sm1, D = x.shape
    tgt = jnp.concatenate([tokens[:, 2:], tokens[:, -1:]], axis=1)
    lp, lse, ent = _fused_head_stats(params, cfg, x.reshape(B * Sm1, D),
                                     tgt.reshape(B * Sm1).astype(jnp.int32))
    return {"mtp_token_logprobs": lp.reshape(B, Sm1),
            "mtp_lse": lse.reshape(B, Sm1),
            "mtp_entropy": ent.reshape(B, Sm1)}


def _stack_group_caches(cfg: ModelConfig, caches: List[Dict[str, Any]]):
    """Concat per-group scan outputs into the unified (L, ...) cache tree,
    sharded per CACHE_LOGICAL (without this, a prefill cache whose kv_heads
    don't divide the TP axis is replicated across it — 425 GB/dev for
    musicgen's 32k MHA prefill; see EXPERIMENTS.md §Perf)."""
    from repro.configs.base import CACHE_LOGICAL
    keys = caches[0].keys()
    return {
        k: constrain(jnp.concatenate([c[k] for c in caches], axis=0),
                     CACHE_LOGICAL[k])
        for k in keys
    }


# ---------------------------------------------------------------------------
# decode (one token against the cache)
# ---------------------------------------------------------------------------

def _cached_layer_step(cfg: ModelConfig, kind: str, h, lp, attn_fn, ssm_fn):
    """Shared layer wiring for the cache-carrying paths (decode_step and
    prefill_chunk): norm1 -> attention/SSM branch(es) -> residual -> FFN.

    attn_fn(attn_params, x) / ssm_fn(ssm_params, x) run the path-specific
    primitive and return (branch_out, new_cache_entries)."""
    x = rms_norm(h, lp["norm1"], cfg.norm_eps)
    if cfg.hybrid_parallel:
        a, ncs_a = attn_fn(lp["attn"], x)
        s, ncs_s = ssm_fn(lp["ssm"], x)
        mix = 0.5 * (rms_norm(a, lp["hyb_norm_a"], cfg.norm_eps)
                     + rms_norm(s, lp["hyb_norm_s"], cfg.norm_eps))
        h = h + mix
        ncs = {**ncs_a, **ncs_s}
    elif cfg.arch_type == "ssm":
        s, ncs = ssm_fn(lp["ssm"], x)
        h = h + s
    else:
        a, ncs = attn_fn(lp["attn"], x)
        h = h + a

    if kind == "dense":
        x = rms_norm(h, lp["norm2"], cfg.norm_eps)
        f = lp["ffn"]
        h = h + swiglu(x, f["gate"], f["up"], f["down"])
    elif kind == "moe":
        x = rms_norm(h, lp["norm2"], cfg.norm_eps)
        mo, _ = moe_mod.moe_apply(lp["moe"], x, cfg)
        h = h + mo
    return h, ncs


def _ssm_layer(cache, layer, fn):
    """Run `fn(conv, ssd) -> (y, (conv, ssd))` on layer `layer` of the
    stacked recurrent state; returns y and the state with the layer
    written back."""
    y, (conv, ssd) = fn(attn.cache_layer(cache["conv"], layer),
                        attn.cache_layer(cache["ssd"], layer))
    return y, {"conv": attn.put_cache_layer(cache["conv"], conv, layer),
               "ssd": attn.put_cache_layer(cache["ssd"], ssd, layer)}


def _scan_layers(cfg: ModelConfig, params, h, cache, body):
    """Run every layer group's scan with the whole stacked (L,...) cache as
    the carry: `body(kind, h, cache, lp, layer) -> (h, cache)` reads and
    writes its layer of the stack (layer is the traced absolute index), so
    no layer slice goes in as a scan input or comes out as an output."""
    offset = 0
    for gi, (kind, count) in enumerate(layer_groups(cfg)):
        def scan_body(carry, inp, _kind=kind):
            lp, layer = inp
            return body(_kind, *carry, lp, layer), None

        layers = jnp.arange(offset, offset + count, dtype=jnp.int32)
        (h, cache), _ = jax.lax.scan(scan_body, (h, cache),
                                     (params["groups"][gi], layers),
                                     unroll=True if cfg.scan_unroll else 1)
        offset += count
    return h, cache


def decode_step(params, tokens, positions, cache, cache_index,
                cfg: ModelConfig, *, ring: Optional[bool] = None,
                kv_len_hint: Optional[int] = None, block_tables=None,
                paged_kernel: bool = False):
    """tokens: (B,1); cache: stacked (L,...) tree; cache_index: scalar or (B,).
    Returns (logits (B,1,V), values (B,1)?, new_cache).

    The stacked cache is the layer loop's carry: each layer writes one new
    row per slot into it in place and the attention reads its layer where
    it lies (DESIGN.md §1); with the cache donated, the step touches
    nothing else of it.

    kv_len_hint: optional static upper bound on the valid cache length
    across the batch; forwarded to the flash-decode kernel to shrink its
    KV grid (the generation engine derives it from its host-side length
    mirrors). Must satisfy kv_len_hint >= max over the batch of
    min(cache_index+1, CL); None disables the grid-level early exit.

    block_tables: (B,NB) int32 when the attention cache leaves are page
    pools (L,NP,PS,...) instead of slot arrays (DESIGN.md §9); SSM leaves
    keep the slot layout either way. paged_kernel routes GQA decode
    through the scalar-prefetch paged kernel instead of gather-then-
    flash_decode."""
    if ring is None:
        # ring addressing applies only to attention caches, and is on
        # exactly when the sliding-window variant allocated a ring buffer
        # (effective_cache_len < full sequence); SSM state has no cache.
        has_kv = "k" in cache or "c_kv" in cache
        ring = has_kv and cfg.attention_variant == "sliding_window"
    h = jnp.take(params["embed"], tokens, axis=0)
    h = constrain(h, ("batch", "seq", "embed"))

    def body(kind, h, cache, lp, layer):
        def attn_fn(pa, x):
            if cfg.use_mla:
                a, (nck, nkr) = attn.mla_decode(
                    pa, x, positions, cache["c_kv"], cache["k_rope"], layer,
                    cache_index, cfg, ring, block_tables=block_tables,
                    paged_kernel=paged_kernel)
                return a, {"c_kv": nck, "k_rope": nkr}
            a, (nk, nv) = attn.gqa_decode(
                pa, x, positions, cache["k"], cache["v"], layer, cache_index,
                cfg, ring, kv_len_hint=kv_len_hint,
                block_tables=block_tables, paged_kernel=paged_kernel)
            return a, {"k": nk, "v": nv}

        def ssm_fn(ps, x):
            return _ssm_layer(cache, layer, functools.partial(
                ssm_mod.ssm_decode, ps, x, cfg=cfg))

        h, ncs = _cached_layer_step(cfg, kind, h, lp, attn_fn, ssm_fn)
        return h, {**cache, **ncs}

    h, new_cache = _scan_layers(cfg, params, h, dict(cache), body)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, head)
    out = {"logits": logits, "cache": new_cache}
    if cfg.use_value_head:
        out["values"] = jnp.einsum(
            "bsd,dv->bsv", h.astype(jnp.float32), params["value_head"])[..., 0]
    return out


# ---------------------------------------------------------------------------
# chunked prefill (batched prompt admission against the slot cache)
# ---------------------------------------------------------------------------

def _merge_state(new, old, mask):
    """Keep `old` rows where mask is False. mask: (B,)."""
    m = mask.reshape((-1,) + (1,) * (old.ndim - 1))
    return jnp.where(m, new.astype(old.dtype), old)


def prefill_chunk(params, tokens, prompt_len, offset, admit_mask, cache,
                  cfg: ModelConfig, *, chunk: int,
                  offset_hint: Optional[int] = None, block_tables=None):
    """One fixed-size chunk of chunked-prefill admission (DESIGN.md §2).

    Runs `chunk` prompt tokens (positions [offset, offset+chunk)) of every
    slot through the full layer stack and writes their K/V (MLA latent /
    SSM state) straight into the stacked slot cache in place, so
    admitting a prompt of length P costs ceil((P-1)/chunk) batched forwards
    instead of P-1 one-token decode steps. Chunk attention runs through the
    Pallas prefill kernel (`kernels.prefill_attention`) when shapes fit,
    and supports ring-buffer (sliding-window) caches: writes land at
    `position mod CL` and masking follows the ring rule (see
    `attention.chunk_attention`).

    **Equivalence law** (enforced by `tests/test_prefill.py`): chunked
    admission must match the sequential decode loop *bit-for-bit in fp32*
    on the attention caches and `n_cached` — every K/V value written here
    is the same projection of the same token at the same position the
    legacy token-at-a-time loop would have written — and within fp32
    tolerance on SSD state and logits (the chunked scan and the online
    softmax reassociate their reductions). At ~greedy temperature the two
    admission paths must produce identical completions.

    tokens: (B,T) slot token buffer; prompt_len: (B,); offset: scalar chunk
    start — the host guarantees offset + chunk <= T, offset % chunk == 0
    and chunk | CL (ring writes stay contiguous); offset_hint: optional
    *static* upper bound on the valid cache-slot count (>= min(offset,
    CL)), bucketed host-side to the prefill kernel's block size — shrinks
    the Pallas kernel's cache-block grid so early chunks never launch
    blocks past the write frontier; admit_mask: (B,) bool,
    True for slots admitted this refill (other rows participate in compute
    for static shapes but their cache/state is untouched). Attention-cache
    writes are additionally masked to positions < prompt_len-1 per row: a
    full-length cache would merely hold dead garbage beyond that (masked
    by n_cached), but once a ring wraps, garbage at high positions would
    alias into low slots that count-based decode masking treats as valid.
    The SSD recurrence gets the same mask via dt=0 no-ops. No logits are
    computed: the first completion token is sampled by the normal decode
    step at n_cached = prompt_len-1.

    block_tables: (B,NB) int32 when attention leaves are page pools
    (DESIGN.md §9). The engine then additionally guarantees chunk |
    page_size, so every chunk write lands inside one logical block; the
    chunk attends against the gathered per-slot view, which keeps the
    equivalence law above intact bit-for-bit versus the slot cache.

    Returns the updated cache tree.
    """
    B, T = tokens.shape
    offset = jnp.asarray(offset, jnp.int32)
    toks = jax.lax.dynamic_slice_in_dim(tokens, offset, chunk, axis=1)
    positions = jnp.broadcast_to(
        (offset + jnp.arange(chunk, dtype=jnp.int32))[None], (B, chunk))
    # tokens folded into recurrent state: absolute position < prompt_len-1
    pos_valid = positions < (prompt_len[:, None] - 1)             # (B,C)
    tok_mask = pos_valid.astype(jnp.float32)
    # attention-cache writes: admitted rows, valid prompt positions only
    kv_write_mask = admit_mask[:, None] & pos_valid               # (B,C)

    h = jnp.take(params["embed"], toks, axis=0)
    h = constrain(h, ("batch", "seq", "embed"))

    def body(kind, h, cache, lp, layer):
        def attn_fn(pa, x):
            if cfg.use_mla:
                a, (nck, nkr) = attn.mla_prefill_chunk(
                    pa, x, positions, cache["c_kv"], cache["k_rope"], layer,
                    offset, kv_write_mask, cfg, offset_hint=offset_hint,
                    block_tables=block_tables)
                return a, {"c_kv": nck, "k_rope": nkr}
            a, (nk, nv) = attn.gqa_prefill_chunk(
                pa, x, positions, cache["k"], cache["v"], layer, offset,
                kv_write_mask, cfg, offset_hint=offset_hint,
                block_tables=block_tables)
            return a, {"k": nk, "v": nv}

        def ssm_fn(ps, x):
            def run(conv, ssd):
                s, (ncv, nss) = ssm_mod.ssm_forward(
                    ps, x, cfg, return_state=True, initial_state=(conv, ssd),
                    token_mask=tok_mask)
                # only admitted rows may advance recurrent state
                return s, (_merge_state(ncv, conv, admit_mask),
                           _merge_state(nss, ssd, admit_mask))

            return _ssm_layer(cache, layer, run)

        h, ncs = _cached_layer_step(cfg, kind, h, lp, attn_fn, ssm_fn)
        return h, {**cache, **ncs}

    return _scan_layers(cfg, params, h, dict(cache), body)[1]
