"""Config system: model configs, input shapes, and ShapeDtypeStruct specs.

Every assigned architecture is a `ModelConfig` instance in its own module
(`repro.configs.<arch>`), citing its source. `input_specs()` builds the
ShapeDtypeStruct stand-ins used by the multi-pod dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    # attention options
    use_qk_norm: bool = False
    rope_theta: float = 10000.0
    attention_variant: str = "full"  # full | sliding_window (decode ring buffer)
    sliding_window: int = 8192
    # MLA (DeepSeek-V3 style multi-head latent attention)
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # MoE
    n_experts: int = 0
    experts_per_token: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0
    n_dense_layers: int = 0  # leading dense layers (DeepSeek-V3 uses 3)
    dense_d_ff: int = 0  # d_ff of those leading dense layers
    capacity_factor: float = 2.0
    router_aux_coef: float = 0.001
    # SSM (Mamba2 / SSD)
    ssm_state: int = 0
    ssm_n_groups: int = 1
    ssm_chunk: int = 64
    ssm_head_dim: int = 64
    d_conv: int = 4
    expand: int = 2
    # hybrid (Hymba): parallel attention + SSM heads in every layer
    hybrid_parallel: bool = False
    # multimodal prefix (stubbed frontend provides embeddings)
    modality: str = "text"  # text | vision | audio
    n_prefix_tokens: int = 0
    # DeepSeek multi-token prediction head
    use_mtp: bool = False
    mtp_depth: int = 1
    # numerics
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # value head for RL (paper Eq. 4 baseline)
    use_value_head: bool = True
    # activation checkpointing over the layer scan (training memory)
    remat: bool = False
    # fully unroll layer scans (roofline calibration: XLA cost_analysis
    # counts a scan body once, so calibration compiles unroll at L=1,2)
    scan_unroll: bool = False
    # route attention/SSD through the Pallas TPU kernels (interpret mode on
    # CPU); falls back to the jnp path when a shape doesn't fit the kernel
    use_pallas: bool = False
    # fused linear-cross-entropy trainer loss (DESIGN.md §6): when the
    # trainer passes loss targets, `forward` skips the (B,S,V) logits
    # materialization and returns per-token logprob/lse/entropy from the
    # blockwise Pallas kernel (jnp twin when use_pallas is off). Inference
    # paths (decode/prefill) are unaffected.
    fused_loss: bool = False
    # Pallas interpret mode: None = auto (interpret off-TPU, compiled on
    # TPU); True/False forces it. Plumbed into every kernel call so TPU
    # runs never hit an interpret-mode kernel by accident.
    pallas_interpret: Optional[bool] = None
    source: str = ""

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def is_attention_free(self) -> bool:
        return self.arch_type == "ssm"

    @property
    def has_attention(self) -> bool:
        return self.arch_type != "ssm"

    @property
    def has_ssm(self) -> bool:
        return self.arch_type in ("ssm", "hybrid")

    @property
    def supports_long_decode(self) -> bool:
        """Every arch supports long_500k: SSM/hybrid natively (O(1) state);
        attention archs via the sliding-window ring-buffer cache."""
        return True

    def param_count(self, active_only: bool = False) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6*N*D)."""
        d, L = self.d_model, self.n_layers
        n = 0
        n += self.vocab_size * d  # embed
        if not self.tie_embeddings:
            n += self.vocab_size * d  # lm head
        for li in range(L):
            n += 2 * d  # 2 norms
            # --- attention ---
            if self.has_attention:
                if self.use_mla:
                    n += d * self.q_lora_rank + self.q_lora_rank * self.n_heads * (
                        self.qk_nope_dim + self.qk_rope_dim)
                    n += d * (self.kv_lora_rank + self.qk_rope_dim)
                    n += self.kv_lora_rank * self.n_heads * (self.qk_nope_dim + self.v_head_dim)
                    n += self.n_heads * self.v_head_dim * d
                else:
                    n += d * self.n_heads * self.d_head  # q
                    n += 2 * d * self.n_kv_heads * self.d_head  # k,v
                    n += self.n_heads * self.d_head * d  # o
            # --- ssm branch ---
            if self.has_ssm:
                di = self.d_inner
                n += d * (2 * di + 2 * self.ssm_n_groups * self.ssm_state + self.n_ssm_heads)
                n += self.d_conv * (di + 2 * self.ssm_n_groups * self.ssm_state)
                n += 2 * self.n_ssm_heads  # A_log, D
                n += di * d  # out proj
            # --- ffn ---
            moe_layer = self.n_experts > 0 and li >= self.n_dense_layers
            if moe_layer:
                e_ff = self.moe_d_ff
                per_expert = 3 * d * e_ff
                n += d * self.n_experts  # router
                if active_only:
                    n += self.experts_per_token * per_expert
                else:
                    n += self.n_experts * per_expert
                n += self.n_shared_experts * per_expert
            elif self.d_ff > 0:
                ff = self.dense_d_ff if (self.n_experts > 0 and self.dense_d_ff) else self.d_ff
                n += 3 * d * ff  # SwiGLU gate/up/down
        return n


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """Gray-failure self-healing knobs (DESIGN.md §10): the HealthMonitor
    watchdog over the actor pool plus the trainer's numerical-robustness
    policy. Defaults are conservative enough that a healthy run with the
    monitor enabled is bit-identical to one without it — detection only
    *observes* until a threshold trips."""
    enabled: bool = True
    # watchdog sweep cadence (flashes of simulated time)
    interval: float = 20.0
    # hang detection: heartbeat deadline = max(hang_grace,
    # hang_factor * EWMA inter-tick gap) per engine
    hang_grace: float = 120.0
    hang_factor: float = 8.0
    # straggler detection: speed-normalized EWMA tick cost vs the pool
    # minimum; must exceed the factor for `patience` consecutive sweeps
    straggler_factor: float = 2.5
    straggler_patience: int = 2
    # poison-prompt circuit breaker: a prompt salvaged from this many
    # failed/hung engines is quarantined instead of requeued
    quarantine_after: int = 3
    # a detected hang is escalated to fail/salvage/requeue; unless the
    # fault plan carries its own restart_after, the wedged engine is
    # restarted this long after detection (None = leave it down)
    hang_restart_after: Optional[float] = 60.0
    # trainer robustness: auto-rollback to the newest intact checkpoint
    # after this many consecutive guarded-bad steps (0 = never)
    bad_step_rollback: int = 3
    # EWMA loss-spike divergence detector: |loss| > factor * EWMA(|loss|)
    # marks the step bad (0.0 = disabled; it is off by default because a
    # young policy's loss is legitimately spiky)
    loss_spike_factor: float = 0.0
    # rotated trainer_step_*.npz checkpoints kept for rollback targets
    ckpt_keep: int = 3


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def effective_cache_len(cfg: ModelConfig, seq_len: int) -> int:
    """Ring-buffer cache length actually allocated for a decode shape."""
    if not cfg.has_attention:
        return 0
    if cfg.use_mla:
        return seq_len  # compressed latent cache is cheap; keep full length
    if cfg.attention_variant == "sliding_window" or seq_len > 65536:
        # long-context decode uses the sliding-window ring buffer
        return min(seq_len, cfg.sliding_window)
    return seq_len


def for_shape(cfg: ModelConfig, shape: ShapeSpec) -> ModelConfig:
    """Specialize a config for an input shape (attention variant for 500k)."""
    if shape.name == "long_500k" and cfg.has_attention and not cfg.use_mla:
        return dataclasses.replace(cfg, attention_variant="sliding_window")
    return cfg


def kv_cache_specs(cfg: ModelConfig, batch: int, cache_len: int) -> Dict[str, Any]:
    """ShapeDtypeStructs for the decode-state pytree (stacked over layers).

    GQA K/V are head-major, (L, B, KV, CL, D): the layout the decode and
    prefill kernels read, so no step swaps a layer into it. MLA latents
    (L, B, CL, r) have no head axis. `CACHE_LOGICAL` names the axes and
    `cache_seq_axis` finds the ring axis of each leaf."""
    L = cfg.n_layers
    s: Dict[str, Any] = {}
    if cfg.has_attention:
        cl = effective_cache_len(cfg, cache_len)
        if cfg.use_mla:
            s["c_kv"] = jax.ShapeDtypeStruct((L, batch, cl, cfg.kv_lora_rank), cfg.dtype)
            s["k_rope"] = jax.ShapeDtypeStruct((L, batch, cl, cfg.qk_rope_dim), cfg.dtype)
        else:
            s["k"] = jax.ShapeDtypeStruct((L, batch, cfg.n_kv_heads, cl, cfg.d_head), cfg.dtype)
            s["v"] = jax.ShapeDtypeStruct((L, batch, cfg.n_kv_heads, cl, cfg.d_head), cfg.dtype)
    if cfg.has_ssm:
        s["conv"] = jax.ShapeDtypeStruct(
            (L, batch, cfg.d_conv - 1,
             cfg.d_inner + 2 * cfg.ssm_n_groups * cfg.ssm_state), cfg.dtype)
        s["ssd"] = jax.ShapeDtypeStruct(
            (L, batch, cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
    return s


def paged_layout(cfg: ModelConfig, cache_len: int,
                 page_size: int) -> tuple:
    """(page_size, n_blocks) for a paged attention cache of logical length
    `cache_len`. page_size is reduced until it divides the cache length so
    every logical ring position maps to exactly one (block, offset)."""
    cl = effective_cache_len(cfg, cache_len)
    if cl == 0:
        return 0, 0
    ps = max(1, min(int(page_size), cl))
    while cl % ps:
        ps -= 1
    return ps, cl // ps


def paged_cache_specs(cfg: ModelConfig, batch: int, cache_len: int,
                      n_pages: int, page_size: int) -> Dict[str, Any]:
    """ShapeDtypeStructs for the paged decode-state pytree (DESIGN.md §9).

    Attention leaves become page *pools* shaped (L, n_pages, page_size,
    ...): one physical page spans all layers of all attention leaves, so a
    single host-side integer per logical block addresses every leaf. SSM
    leaves are O(1) per slot — no paging win — and keep the slot layout
    from `kv_cache_specs`. The (batch, n_blocks) block table itself lives
    host-side (numpy) and rides into jit as an ordinary traced arg.
    """
    L = cfg.n_layers
    s: Dict[str, Any] = {}
    if cfg.has_attention:
        ps, _ = paged_layout(cfg, cache_len, page_size)
        if cfg.use_mla:
            s["c_kv"] = jax.ShapeDtypeStruct((L, n_pages, ps, cfg.kv_lora_rank), cfg.dtype)
            s["k_rope"] = jax.ShapeDtypeStruct((L, n_pages, ps, cfg.qk_rope_dim), cfg.dtype)
        else:
            s["k"] = jax.ShapeDtypeStruct((L, n_pages, ps, cfg.n_kv_heads, cfg.d_head), cfg.dtype)
            s["v"] = jax.ShapeDtypeStruct((L, n_pages, ps, cfg.n_kv_heads, cfg.d_head), cfg.dtype)
    if cfg.has_ssm:
        ssm = kv_cache_specs(dataclasses.replace(cfg, arch_type="ssm"),
                             batch, cache_len)
        s.update(ssm)
    return s


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a step function.

    train  -> RL train batch (tokens, mask, behavior logprobs, rewards, ...)
    prefill-> prompt tokens
    decode -> one-token step against a KV cache of shape.seq_len
    """
    B, S = shape.global_batch, shape.seq_len
    i32, f32 = jnp.int32, jnp.float32
    sd = jax.ShapeDtypeStruct
    specs: Dict[str, Any]
    if shape.kind == "train":
        # matches repro.data.packing.pack output (online sequence packing)
        specs = {
            "tokens": sd((B, S), i32),
            "loss_mask": sd((B, S), f32),
            "behavior_logprobs": sd((B, S), f32),
            "rewards": sd((B, S), f32),  # per-token broadcast of sequence reward
            "positions": sd((B, S), i32),
            "segment_ids": sd((B, S), i32),
        }
    elif shape.kind == "prefill":
        specs = {
            "tokens": sd((B, S), i32),
            "positions": sd((B, S), i32),
        }
    else:  # decode: one new token, cache of length seq_len
        specs = {
            "tokens": sd((B, 1), i32),
            "positions": sd((B, 1), i32),
            "cache": kv_cache_specs(cfg, B, S),
            "cache_index": sd((), i32),
        }
    if cfg.modality in ("vision", "audio") and cfg.n_prefix_tokens:
        # stubbed frontend: precomputed patch/frame embeddings
        specs["prefix_embeds"] = sd((B, cfg.n_prefix_tokens, cfg.d_model), cfg.dtype)
    return specs


CACHE_LOGICAL = {
    "k": ("layers", "batch", "kv_heads", "cache_seq", None),
    "v": ("layers", "batch", "kv_heads", "cache_seq", None),
    "c_kv": ("layers", "batch", "cache_seq", None),
    "k_rope": ("layers", "batch", "cache_seq", None),
    "conv": ("layers", "batch", None, "mlp"),
    "ssd": ("layers", "batch", "heads", None, None),
}


def cache_seq_axis(key: str) -> int:
    """Ring (cache position) axis of a stacked slot-cache leaf."""
    return CACHE_LOGICAL[key].index("cache_seq")


def input_logical(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Logical axis names for every input spec (same keys as input_specs)."""
    two = ("batch", "seq")
    if shape.kind == "train":
        log: Dict[str, Any] = {k: two for k in (
            "tokens", "loss_mask", "behavior_logprobs", "rewards",
            "positions", "segment_ids")}
    elif shape.kind == "prefill":
        log = {"tokens": two, "positions": two}
    else:
        log = {
            "tokens": ("batch", None),
            "positions": ("batch", None),
            "cache": {k: CACHE_LOGICAL[k]
                      for k in kv_cache_specs(cfg, 1, 8)},
            "cache_index": (),
        }
    if cfg.modality in ("vision", "audio") and cfg.n_prefix_tokens:
        log["prefix_embeds"] = ("batch", None, None)
    return log


def smoke_config(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family variant: 2 layers, d_model<=512, <=4 experts."""
    d = min(cfg.d_model, 256)
    n_heads = min(cfg.n_heads, 4)
    n_kv = max(1, min(cfg.n_kv_heads, n_heads))
    if n_heads % n_kv:
        n_kv = 1
    repl: Dict[str, Any] = dict(
        n_layers=2,
        d_model=d,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        d_head=32,
        d_ff=min(cfg.d_ff, 512) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 512),
        sliding_window=64,
        ssm_head_dim=32 if cfg.has_ssm else cfg.ssm_head_dim,
        ssm_state=min(cfg.ssm_state, 16) if cfg.has_ssm else 0,
        ssm_chunk=16 if cfg.has_ssm else cfg.ssm_chunk,
        dtype=jnp.float32,
    )
    if cfg.use_mla:
        repl.update(q_lora_rank=64, kv_lora_rank=32, qk_nope_dim=32,
                    qk_rope_dim=16, v_head_dim=32)
    if cfg.n_experts:
        repl.update(n_experts=min(cfg.n_experts, 4),
                    experts_per_token=min(cfg.experts_per_token, 2),
                    moe_d_ff=64, n_dense_layers=min(cfg.n_dense_layers, 1),
                    dense_d_ff=128 if cfg.dense_d_ff else 0)
    if cfg.n_prefix_tokens:
        repl.update(n_prefix_tokens=8)
    return dataclasses.replace(cfg, **repl)
