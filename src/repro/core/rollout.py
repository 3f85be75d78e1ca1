"""Continuous-batching generation engine with in-flight weight updates —
the Actor process of PipelineRL (Algorithm 2), TPU/JAX-native.

vLLM's dynamic paged batching becomes a *slot array*: H static slots, each
with its own write index into a preallocated KV cache. Finished sequences
retire and their slot is refilled with a new prompt in the same jitted step
function (no dynamic shapes). The in-flight weight update is a host-side
pointer swap of the behavior weights μ — the KV cache (and SSM state) of
in-progress sequences is retained *stale*, exactly the paper's mechanism
(§5.1 shows this is safe; `recompute_kv=True` reproduces their ablation).

Per-token bookkeeping records the behavior logprob (mixed-policy μ of
Eq. 8) and the weight version each token was sampled under (token lag).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (CACHE_LOGICAL, ModelConfig, cache_seq_axis,
                                effective_cache_len, kv_cache_specs,
                                paged_cache_specs, paged_layout)
from repro.data.math_task import MathTask, Problem
from repro.data.packing import Rollout
from repro.kernels.paged_cache import BlockTables, OutOfPages, PageAllocator
from repro.models import attention as attn
from repro.models import model as M
from repro.spans import named, span


@dataclasses.dataclass
class EngineConfig:
    n_slots: int = 16            # H, the generation batch size
    max_len: int = 64            # prompt + completion budget per sequence
    temperature: float = 1.0
    eos_id: int = 2
    pad_id: int = 0
    # chunked-prefill admission (DESIGN.md §2): newly admitted prompts run
    # through batched `prefill_chunk`-token forwards that write K/V (and
    # SSM state) straight into the slot cache — ceil((P-1)/chunk) model
    # invocations per prompt instead of P-1 one-token decode steps. 0
    # falls back to the legacy token-at-a-time forcing loop. The effective
    # chunk is reduced to the largest common divisor of max_len and the
    # attention cache length, so chunk windows never cross the cache end
    # and ring-buffer (sliding-window) writes stay contiguous — ring
    # caches take the chunked path like everything else.
    prefill_chunk: int = 16
    # Pallas interpret-mode override threaded into every kernel the engine
    # compiles (None = auto: interpret off-TPU, compiled on TPU)
    interpret: Optional[bool] = None
    # admission policy for prompts longer than max_len-2: "reject" drops
    # the prompt and counts it in `prompts_rejected` (the task reward is
    # computed against the FULL problem, so silently truncating the
    # prompt scores the policy on a question it never saw); "truncate"
    # keeps the legacy clip-and-admit behavior, counted in
    # `prompts_truncated`.
    long_prompt: str = "reject"
    # --- paged KV cache (DESIGN.md §9) ---------------------------------
    # "slots": one contiguous max_len stripe per slot (the differential
    # oracle). "paged": attention leaves become page pools addressed
    # through a ref-counted block table — short requests stop reserving
    # max_len of cache, a GRPO group's prompt is prefilled once and
    # forked copy-on-write, and admission is costed in pages.
    cache: str = "slots"
    # logical tokens per page (reduced until it divides the cache length)
    page_size: int = 16
    # physical pages in the pool, including the reserved trash page 0.
    # 0 = auto: n_slots * blocks_per_slot + 1, i.e. exactly the slot-array
    # footprint (no eviction pressure); smaller values trade capacity for
    # memory and rely on page-exhaustion preemption.
    n_pages: int = 0
    # prefill a GRPO group's identical prompt once and fork the rest over
    # shared pages (paged mode with chunked prefill only)
    prefix_sharing: bool = True
    # paged decode read path: "gather" runs the unchanged attention on the
    # gathered per-slot view (bit-identical to the slot engine); "kernel"
    # opts into the scalar-prefetch paged flash-decode kernel (no gather;
    # page-sized softmax blocks, so fp32-close rather than bitwise unless
    # page_size == decode_block_k)
    paged_attention: str = "gather"


# backstop for refill's reject-retry loop: after this many rejections in
# one refill call the engine stops pulling for the tick (turns a source
# that yields only overlong prompts from a hang into slow, counted
# progress — real sources either fit or drain)
_MAX_REJECTS_PER_REFILL = 1024


def _zero_cache(cfg: ModelConfig, n_slots: int, max_len: int):
    specs = kv_cache_specs(cfg, n_slots, max_len)
    return {k: jnp.zeros(v.shape, v.dtype) for k, v in specs.items()}


def _zero_paged_cache(cfg: ModelConfig, n_slots: int, max_len: int,
                      n_pages: int, page_size: int):
    specs = paged_cache_specs(cfg, n_slots, max_len, n_pages, page_size)
    return {k: jnp.zeros(v.shape, v.dtype) for k, v in specs.items()}


def _paged_ring_view(cache, block_tables):
    """Gather pool leaves (L,NP,PS,...) into slot-layout views through the
    block table: (L,H,CL,...), K/V head-major (L,H,KV,CL,D); SSM leaves
    (already per-slot) pass through untouched."""
    out = dict(cache)
    for k in ("k", "v", "c_kv", "k_rope"):
        if k in out:
            v = jnp.take(out[k], block_tables, axis=1)    # (L,H,NB,PS,...)
            v = v.reshape((v.shape[0], v.shape[1], v.shape[2] * v.shape[3])
                          + v.shape[4:])
            out[k] = jnp.moveaxis(v, 2, cache_seq_axis(k))
    return out


def _admit_impl(st: Dict[str, Any], new_tokens, new_plen, new_ncached,
                admit_mask, cfg: ModelConfig):
    """Device-side admission: scatter fresh prompt rows into engine state.

    Replaces the old host round trip (five full-state np copies per
    admission) — the only host->device traffic is the (H,T) prompt buffer
    and three (H,) vectors; everything else is donated and updated in
    place. admit_mask: (H,) bool, True where a new prompt enters.
    """
    m = admit_mask
    tokens = jnp.where(m[:, None], new_tokens, st["tokens"])
    lp = jnp.where(m[:, None], 0.0, st["lp"])
    n_cached = jnp.where(m, new_ncached, st["n_cached"])
    prompt_len = jnp.where(m, new_plen, st["prompt_len"])
    active = st["active"] | m
    cache = dict(st["cache"])
    # zero recurrent state of refilled slots (attention cache is masked by
    # cache_index, but SSM state carries over unless cleared)
    if "ssd" in cache:
        keep = (~m).astype(cache["ssd"].dtype)[None, :, None, None, None]
        cache["ssd"] = cache["ssd"] * keep
        keep_c = (~m).astype(cache["conv"].dtype)[None, :, None, None]
        cache["conv"] = cache["conv"] * keep_c
    return dict(st, tokens=tokens, lp=lp, n_cached=n_cached,
                prompt_len=prompt_len, active=active, cache=cache)


def _prefill_impl(params, st: Dict[str, Any], offset, admit_mask,
                  block_tables, cfg: ModelConfig, chunk: int,
                  offset_hint: Optional[int] = None):
    """One chunked-prefill step over the slot state (cache update only).

    offset_hint (static): host-side bound on the valid cache-slot count,
    bucketed to the prefill kernel's block size; shrinks the kernel's
    cache-block grid (grid-level early exit, like decode's kv_len_hint).
    block_tables: (H,NB) int32 in paged mode, None for the slot array."""
    cache = M.prefill_chunk(params, st["tokens"], st["prompt_len"], offset,
                            admit_mask, st["cache"], cfg, chunk=chunk,
                            offset_hint=offset_hint,
                            block_tables=block_tables)
    return dict(st, cache=cache)


def _engine_step(params, st: Dict[str, Any], block_tables,
                 cfg: ModelConfig, ec: EngineConfig,
                 kv_len_hint: Optional[int] = None):
    """One token for every active slot. st: tokens (H,T), n_cached (H,),
    prompt_len (H,), active (H,) bool, cache, lp (H,T), key.

    kv_len_hint (static): host-mirrored bound on the valid cache length,
    bucketed to the flash-decode block size so jit sees few values; shrinks
    the decode kernel's KV grid (grid-level early exit).

    block_tables: (H,NB) int32 in paged mode (None for the slot array).
    The host guarantees, before every step, that each active slot's write
    block is backed by an exclusively-owned page (lazy alloc + COW), and
    that inactive slots' rows are all trash-page zeros so their
    static-shape stale writes land harmlessly."""
    H, T = st["tokens"].shape
    idx = jnp.arange(H)
    cur_tok = st["tokens"][idx, st["n_cached"]][:, None]          # (H,1)
    positions = st["n_cached"][:, None]                           # (H,1)
    out = M.decode_step(params, cur_tok, positions, st["cache"],
                        st["n_cached"], cfg, ring=False,
                        kv_len_hint=kv_len_hint,
                        block_tables=block_tables,
                        paged_kernel=ec.paged_attention == "kernel")
    logits = out["logits"][:, 0] / jnp.maximum(ec.temperature, 1e-6)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)

    key, sub = jax.random.split(st["key"])
    sampled = jax.random.categorical(sub, logp, axis=-1)          # (H,)

    next_idx = st["n_cached"] + 1
    in_prompt = next_idx < st["prompt_len"]
    forced = st["tokens"][idx, jnp.minimum(next_idx, T - 1)]
    next_tok = jnp.where(in_prompt, forced, sampled).astype(jnp.int32)
    tok_lp = jnp.take_along_axis(logp, next_tok[:, None], axis=-1)[:, 0]
    tok_lp = jnp.where(in_prompt, 0.0, tok_lp)

    active = st["active"]
    write = active & (next_idx < T)
    tokens = st["tokens"].at[idx, jnp.minimum(next_idx, T - 1)].set(
        jnp.where(write, next_tok, st["tokens"][idx, jnp.minimum(next_idx, T - 1)]))
    lp = st["lp"].at[idx, jnp.minimum(next_idx, T - 1)].set(
        jnp.where(write, tok_lp, st["lp"][idx, jnp.minimum(next_idx, T - 1)]))

    finished = active & ~in_prompt & (
        (next_tok == ec.eos_id) | (next_idx >= T - 1))
    n_cached = jnp.where(active, next_idx, st["n_cached"])
    new_active = active & ~finished

    new_st = dict(st, tokens=tokens, lp=lp, key=key,
                  n_cached=n_cached, active=new_active, cache=out["cache"])
    return new_st, finished


def decode_program(cfg: ModelConfig, ec: EngineConfig):
    """The engine's jitted decode step (`jit_engine_decode`). The state is
    donated, so the slot cache is updated in place from one step to the
    next."""
    return jax.jit(
        named(functools.partial(_engine_step, cfg=cfg, ec=ec),
              "engine_decode"), static_argnames=("kv_len_hint",),
        donate_argnums=(1,))


class GenerationEngine:
    """H-slot continuous-batching engine (Algorithm 2, Actor).

    `jit_donor`: another engine whose compiled step/admit/prefill
    callables are reused when cfg+ec match — an actor pool of identical
    engines (core.events.ActorStage) compiles the hot functions once
    instead of once per engine."""

    def __init__(self, cfg: ModelConfig, params, ec: EngineConfig,
                 prompt_source: Callable[[], Problem], seed: int = 0,
                 jit_donor: Optional["GenerationEngine"] = None,
                 mesh=None, rules=None):
        if ec.interpret is not None:
            cfg = dataclasses.replace(cfg, pallas_interpret=ec.interpret)
        self.cfg, self.ec = cfg, ec
        # --- real-mesh placement (DESIGN.md §11): when `mesh` is given the
        # engine owns a device set — params live in the generation layout
        # from `tree_shardings`, the KV cache follows CACHE_LOGICAL, and
        # every jitted call runs under `sharding_context` so the model's
        # `constrain` annotations become real sharding constraints.
        self.mesh, self.rules = mesh, rules
        self._param_shardings = None
        self._pshard_leaves: Optional[List[Any]] = None
        if mesh is not None:
            from repro.sharding import tree_shardings
            ann = M.init_params(cfg, abstract=True)
            self._param_shardings = tree_shardings(ann, mesh, rules)
            self._pshard_leaves = jax.tree_util.tree_leaves(
                self._param_shardings)
            params = jax.device_put(params, self._param_shardings)
        self.params = params      # behavior weights μ
        self.version = 0          # trainer version of μ
        self.prompt_source = prompt_source
        H, T = ec.n_slots, ec.max_len
        # --- paged KV cache (DESIGN.md §9): page pool + block tables ----
        # attention-free archs have nothing to page; they run the slot
        # state machine under either setting (admission costs 0 pages)
        self._paged = ec.cache == "paged" and cfg.has_attention
        if ec.cache not in ("slots", "paged"):
            raise ValueError(f"EngineConfig.cache: {ec.cache!r}")
        self.allocator: Optional[PageAllocator] = None
        self.tables: Optional[BlockTables] = None
        self._bt_jax = None                 # device copy of the block table
        self._bt_dirty = False
        self._deferred: "collections.deque[Problem]" = collections.deque()
        if self._paged:
            ps, nb = paged_layout(cfg, T, ec.page_size)
            n_pages = ec.n_pages or H * nb + 1
            if n_pages - 1 < nb:
                # a lone sequence must be able to fill its table even after
                # preempting everyone else, or eviction cannot terminate
                raise ValueError(
                    f"n_pages={n_pages} cannot back one full sequence "
                    f"({nb} blocks + trash page)")
            self.allocator = PageAllocator(n_pages, ps)
            self.tables = BlockTables(H, nb, self.allocator)
            self._bt_jax = jnp.zeros((H, nb), jnp.int32)
            cache = _zero_paged_cache(cfg, H, T, n_pages, ps)
        else:
            cache = _zero_cache(cfg, H, T)
        self.state: Dict[str, Any] = {
            "tokens": jnp.zeros((H, T), jnp.int32),
            "lp": jnp.zeros((H, T), jnp.float32),
            "n_cached": jnp.zeros((H,), jnp.int32),
            "prompt_len": jnp.ones((H,), jnp.int32),
            "active": jnp.zeros((H,), bool),
            "cache": cache,
            "key": jax.random.PRNGKey(seed),
        }
        if mesh is not None:
            self.state = jax.device_put(self.state, self._state_shardings())
        # host-side bookkeeping
        self.problems: List[Optional[Problem]] = [None] * H
        self.ver_buf = np.zeros((H, T), np.int32)
        self.started_at = np.zeros(H, np.float64)
        self.tokens_generated = 0
        # host mirrors of the scheduling scalars — the step/refill hot loop
        # never reads engine state back from device except `finished`
        self._host_active = np.zeros(H, bool)
        self._host_ncached = np.zeros(H, np.int64)
        self._host_prompt_len = np.ones(H, np.int64)
        # attention cache length (None for attention-free archs); a ring
        # buffer when < T (sliding-window long-context decode)
        self._cache_len: Optional[int] = None
        if cfg.has_attention:
            self._cache_len = effective_cache_len(cfg, T)
            if self._paged:
                assert self._cache_len == (self.tables.n_blocks
                                           * self.allocator.page_size)
        # the decode-length hint only matters when gqa_decode actually
        # takes the flash-decode kernel path; computing it otherwise would
        # re-trace the jitted step once per hint bucket for no benefit
        self._use_decode_hint = (self._cache_len is not None
                                 and attn.uses_flash_decode(
                                     cfg, self._cache_len))
        # chunked prefill: the effective chunk must divide T (chunk windows
        # never cross the token buffer end) and the cache length (modular
        # ring writes stay contiguous — DESIGN.md §2 chunk geometry); in
        # paged mode it must also divide the page size, so every chunk
        # write lands inside exactly one logical block
        chunk = max(int(ec.prefill_chunk), 0)
        if chunk:
            cl = self._cache_len or T
            ps = self.allocator.page_size if self._paged else cl
            chunk = min(chunk, T, cl, ps)
            while T % chunk or cl % chunk or ps % chunk:
                chunk -= 1
        self.prefill_chunk_size = chunk
        self.prefill_invocations = 0       # chunked-prefill model calls
        self.prefill_tokens = 0            # prompt tokens admitted via prefill
        self.last_admit_prefill_tokens = 0
        # paged-mode accounting (all stay 0 for the slot array)
        self.prompt_prefills = 0           # rows actually prefilled (leaders)
        self.prefix_forks = 0              # rows admitted by COW fork
        self.last_admit_pages = 0          # pages allocated by last refill
        self.slots_preempted = 0           # page-exhaustion evictions
        self.pages_copied = 0              # COW page copies materialized
        # long-prompt admission accounting (EngineConfig.long_prompt)
        self.prompts_rejected = 0
        self.prompts_truncated = 0
        # notified with the dropped Problem on every rejection (the Server
        # uses it to fail the owning request instead of losing it)
        self.on_prompt_rejected: Optional[Callable[[Problem], None]] = None
        # streamed in-flight weight broadcast (DESIGN.md §7): shadow param
        # buffer filled chunk-by-chunk between decode steps
        self._wstream: Optional[Dict[str, Any]] = None
        # §10 integrity gate accounting: damaged transmissions rejected
        # by the per-chunk checksum, and assembled streams rejected by
        # the pre-swap digest verify (both must stay 0 on healthy links)
        self.wchunks_rejected = 0
        self.wstreams_torn = 0
        self.last_stream_installed = True
        if (jit_donor is not None and jit_donor.cfg == cfg
                and jit_donor.ec == ec
                and getattr(jit_donor, "mesh", None) == mesh
                and getattr(jit_donor, "rules", None) == rules):
            self._step = jit_donor._step
            self._recompute = jit_donor._recompute
            self._admit = jit_donor._admit
            if chunk:
                self._prefill = jit_donor._prefill
                self._use_prefill_hint = jit_donor._use_prefill_hint
            return
        # named programs: `jit_engine_decode` etc. in HLO and in traces.
        # Decode, admission and prefill donate the state: the slot cache
        # is updated in place (nothing reads a donated state afterwards)
        self._step = decode_program(cfg, ec)
        rc = (self._recompute_impl_paged if self._paged
              else self._recompute_impl)
        self._recompute = jax.jit(named(functools.partial(rc, cfg=cfg),
                                        "engine_recompute"))
        self._admit = jax.jit(
            named(functools.partial(_admit_impl, cfg=cfg), "engine_admit"),
            donate_argnums=(0,))
        if chunk:
            self._prefill = jax.jit(
                named(functools.partial(_prefill_impl, cfg=cfg, chunk=chunk),
                      "engine_prefill"),
                donate_argnums=(1,), static_argnames=("offset_hint",))
            # hint buckets only matter when the Pallas prefill kernel runs
            # (each bucket is one extra compile of the chunk forward)
            self._use_prefill_hint = (self._cache_len is not None
                                      and attn._use_prefill_kernel(
                                          cfg, chunk, self._cache_len))

    # ----- device placement (DESIGN.md §11 real-mesh runtime) ----------
    def _state_shardings(self):
        """Engine-state placement: slot-cache leaves follow CACHE_LOGICAL
        through the rules engine (cache_seq / kv_heads sharding); paged
        pool leaves and the scheduling vectors stay replicated — GSPMD
        keeps the jitted step semantics-identical either way."""
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.sharding import logical_to_spec
        rep = NamedSharding(self.mesh, PartitionSpec())
        sh: Dict[str, Any] = {k: rep for k in self.state if k != "cache"}
        cache = {}
        for k, v in self.state["cache"].items():
            if self._paged or k not in CACHE_LOGICAL:
                cache[k] = rep
            else:
                cache[k] = NamedSharding(self.mesh, logical_to_spec(
                    CACHE_LOGICAL[k], v.shape, self.mesh, self.rules))
        sh["cache"] = cache
        return sh

    def _ctx(self):
        """Ambient sharding context for every jitted call — a no-op for
        mesh-less engines, so the simulated pool is untouched."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.shardctx import sharding_context
        return sharding_context(self.mesh, self.rules)

    # ----- weights -----------------------------------------------------
    def set_weights(self, params, version: int, recompute_kv: bool = False,
                    _placed: bool = False):
        """In-flight weight update: swap μ, keep the (stale) KV cache.
        recompute_kv=True reproduces the paper's §5.1 ablation (recompute
        the cache of in-progress sequences under the new weights). An
        atomic swap supersedes any in-progress weight stream.

        On a mesh engine the swap is an *executed* transfer: the incoming
        tree is resharded onto this engine's placement and waited for, so
        the `engine.swap` span measures it (`_placed=True` skips the copy
        when the caller already delivered device-resident buffers, e.g.
        the final swap of an executed chunk stream)."""
        from repro.core.events import tree_bytes
        with span("engine.swap", version=int(version),
                  nbytes=tree_bytes(params)):
            self._wstream = None
            if self.mesh is not None and not _placed:
                params = jax.device_put(params, self._param_shardings)
                jax.block_until_ready(params)
            self.params = params
            self.version = version
            if recompute_kv:
                if self._paged:
                    # unshare every shared block first: the recompute scatter
                    # overwrites all positions of every referenced page, which
                    # must not clobber a page other forks still read — and a
                    # page referenced twice in one scatter would be written
                    # nondeterministically
                    self._unshare_all()
                    self._sync_tables()
                    with self._ctx():
                        self.state["cache"] = self._recompute(
                            params, self.state, self._bt_jax)
                else:
                    with self._ctx():
                        self.state["cache"] = self._recompute(params,
                                                              self.state)

    def begin_weight_stream(self, params, version: int, n_chunks: int = 8,
                            recompute_kv: bool = False,
                            expect_digest: Optional[int] = None,
                            chunk_leaves: Optional[List[List[Any]]] = None
                            ) -> List[int]:
        """Streamed in-flight broadcast (DESIGN.md §7): stage the new
        param tree into a shadow buffer chunk-by-chunk between decode
        steps via `stream_weight_chunk`; μ (and `self.version`) stay on
        the old weights until the final chunk lands, then pointer-swap —
        so per-token `weight_versions` stamps stay exact across the whole
        transfer. A second `begin` abandons the unfinished shadow buffer.
        `expect_digest` arms the §10 integrity gate: the assembled stream
        must reproduce it before the swap is allowed. `chunk_leaves[k]`,
        when given, holds the k-th span's leaves already resharded onto
        this engine's devices (a WeightBroadcaster execution backend ran
        the transfer) — installs consume those buffers instead of the
        sender's. Returns the per-chunk byte sizes (for interconnect
        costing)."""
        from repro.core.events import chunk_spans, span_bytes
        leaves, treedef = jax.tree_util.tree_flatten(params)
        spans = chunk_spans(leaves, n_chunks)
        sizes = span_bytes(leaves, spans)
        self._wstream = {
            "treedef": treedef, "leaves": leaves, "spans": spans,
            "sizes": sizes, "shadow": [None] * len(leaves), "next": 0,
            "version": version, "recompute": recompute_kv,
            "expect": expect_digest, "tokens": [],
            "chunk_leaves": chunk_leaves,
        }
        return sizes

    def stream_weight_chunk(self, token: Optional[int] = None) -> bool:
        """Install the next chunk into the shadow buffer; on the last
        chunk, assemble the tree and pointer-swap it in (returns True).
        No-op (False) when no stream is active.

        Integrity gate (DESIGN.md §10): when the transmission carries a
        checksum `token`, it must match the token this engine computes
        from its own span table — a damaged chunk is rejected before it
        touches the shadow buffer (`wchunks_rejected`) and the sender's
        backoff machinery retransmits it. Before the pointer swap the
        whole shadow buffer is verified (every span filled + accumulated
        digest matches the publication digest), so a torn stream can
        never install (`wstreams_torn`); `last_stream_installed` tells
        the stage whether the final chunk actually swapped weights."""
        from repro.core.events import chunk_token, stream_digest
        ws = self._wstream
        if ws is None:
            return False
        k = ws["next"]
        if token is not None:
            if token != chunk_token(ws["version"], k, ws["sizes"][k]):
                self.wchunks_rejected += 1
                return False
        with span("engine.install", version=int(ws["version"]), chunk=k,
                  nbytes=ws["sizes"][k], swapped=0) as counts:
            lo, hi = ws["spans"][k]
            if ws.get("chunk_leaves") is not None:
                # executor-resharded span: the buffers already live on this
                # engine's devices (k-indexed, so a retransmit after a
                # rejected chunk naturally reuses the right span)
                ws["shadow"][lo:hi] = list(ws["chunk_leaves"][k])
            elif self.mesh is not None:
                # in-engine executed transfer: reshard the span onto this
                # engine's placement and wait for it, so the `engine.install`
                # span measures it (DESIGN.md §11)
                placed = jax.device_put(ws["leaves"][lo:hi],
                                        self._pshard_leaves[lo:hi])
                jax.block_until_ready(placed)
                ws["shadow"][lo:hi] = placed
            else:
                ws["shadow"][lo:hi] = ws["leaves"][lo:hi]
            ws["tokens"].append(chunk_token(ws["version"], k, ws["sizes"][k]))
            ws["next"] += 1
            if ws["next"] < len(ws["spans"]):
                return False
            torn = any(x is None for x in ws["shadow"]) or (
                ws["expect"] is not None
                and stream_digest(ws["tokens"]) != ws["expect"])
            if torn:
                self.wstreams_torn += 1
                self.last_stream_installed = False
                self._wstream = None
                return True
            params = jax.tree_util.tree_unflatten(ws["treedef"], ws["shadow"])
            version, recompute = ws["version"], ws["recompute"]
            self.last_stream_installed = True
            counts["swapped"] = 1
            self.set_weights(params, version, recompute_kv=recompute,
                             _placed=True)
            return True

    @property
    def stream_active(self) -> bool:
        return self._wstream is not None

    # ----- crash semantics (DESIGN.md §8 failure model) -----------------
    def reset_slots(self) -> int:
        """Kill every in-flight sequence — engine-process crash semantics.
        All slots go inactive and their token/KV contents are abandoned
        (safe: admission overwrites tokens and prefill rewrites every
        cache position a later decode step may read, exactly as on normal
        slot reuse); any half-filled weight-stream shadow buffer is
        dropped (the restart's catch-up sync supersedes it). In paged
        mode every page reference — including shared prefix pages, whose
        refcounts drop once per holding slot — returns to the pool;
        prompts deferred by page pressure are dropped with the slots (a
        salvage path that wants them calls `drain_deferred()` first).
        Returns the number of live slots killed, i.e. the rollouts
        lost."""
        n = int(self._host_active.sum())
        H = self.ec.n_slots
        self._host_active[:] = False
        self._host_ncached[:] = 0
        self._host_prompt_len[:] = 1
        self.problems = [None] * H
        self._wstream = None
        self._deferred.clear()
        if self._paged:
            for s in range(H):
                self.tables.release_row(s)
            assert self.allocator.live_pages == 0, "pages leaked on reset"
            self._bt_dirty = True
            self._sync_tables()
        self.state = dict(
            self.state,
            n_cached=jnp.zeros((H,), jnp.int32),
            prompt_len=jnp.ones((H,), jnp.int32),
            active=jnp.zeros((H,), bool))
        return n

    def drain_deferred(self) -> List[Problem]:
        """Hand back prompts parked by page-exhaustion deferral/preemption
        (salvage path: they re-enter the pool through the router like the
        live slots' prompts)."""
        out = list(self._deferred)
        self._deferred.clear()
        return out

    def kill_slot(self, s: int) -> Optional[Problem]:
        """Kill ONE live slot without crashing the engine (DESIGN.md §10
        quarantine path): the slot's rollout-in-progress is abandoned
        exactly as in `reset_slots` — tokens/KV left for reuse, pages
        (shared refs included) returned — and its prompt is handed back
        so the caller can quarantine or requeue it. Returns None for an
        inactive slot."""
        s = int(s)
        if not self._host_active[s]:
            return None
        prob = self.problems[s]
        self._host_active[s] = False
        self._host_ncached[s] = 0
        self._host_prompt_len[s] = 1
        self.problems[s] = None
        if self._paged:
            self.tables.release_row(s)
            self._bt_dirty = True
            self._sync_tables()
        self.state = dict(
            self.state,
            n_cached=self.state["n_cached"].at[s].set(0),
            prompt_len=self.state["prompt_len"].at[s].set(1),
            active=self.state["active"].at[s].set(False))
        return prob

    # ----- paged-cache machinery (DESIGN.md §9) -------------------------
    @property
    def free_pages(self) -> int:
        """Free pages in the pool (a large sentinel for the slot array /
        attention-free engines, whose admission is slot-bounded only)."""
        if not self._paged:
            return 1 << 30
        return self.allocator.free_pages

    def pages_needed(self, prompt_len: int) -> int:
        """Pages a prompt of `prompt_len` needs through admission and its
        first decode write (its logical footprint is capped by the ring
        length)."""
        if not self._paged:
            return 0
        cl = self._cache_len
        return self.tables.blocks_for(min(max(int(prompt_len), 1), cl))

    def can_admit(self, prompt_len: int) -> bool:
        """Page-costed admission check (serving/router gate): True when a
        free slot exists AND the pool can back the prompt without evicting
        in-flight work. Slot-array engines only check slots."""
        if not (~self._host_active).any():
            return False
        return self.free_pages >= self.pages_needed(prompt_len)

    def _sync_tables(self) -> None:
        if self._paged and self._bt_dirty:
            self._bt_jax = jnp.asarray(self.tables.table)
            self._bt_dirty = False

    def _unshare_all(self) -> None:
        """Break every COW share: after this, each live page is referenced
        by exactly one table entry (recompute_kv's full-scatter needs
        exclusive pages; no device copy — the scatter overwrites every
        position of every referenced page)."""
        tb, alloc = self.tables, self.allocator
        for s in range(self.ec.n_slots):
            for j in range(tb.n_blocks):
                p = int(tb.table[s, j])
                if p and alloc.refcount[p] > 1:
                    q = alloc.alloc()
                    alloc.refcount[p] -= 1
                    tb.table[s, j] = q
                    self._bt_dirty = True

    def _evict_one(self, requester: int) -> bool:
        """Preempt the least-progressed active slot (ties: higher index)
        to free its pages; its prompt re-enters through `_deferred` at the
        front. Returns False when no victim exists."""
        victims = [s for s in np.where(self._host_active)[0]
                   if s != requester]
        if not victims:
            return False
        progress = {s: int(self._host_ncached[s] - self._host_prompt_len[s])
                    for s in victims}
        victim = max(victims, key=lambda s: (-progress[s], s))
        self.tables.release_row(victim)
        self._bt_dirty = True
        self._host_active[victim] = False
        prob = self.problems[victim]
        self.problems[victim] = None
        if prob is not None:
            self._deferred.appendleft(prob)
        self.slots_preempted += 1
        # the jitted step reads `active` from device state — push the kill
        self.state = dict(self.state,
                          active=jnp.asarray(self._host_active))
        return True

    def _ensure_block(self, s: int, j: int,
                      copies: List[Tuple[int, int]]) -> None:
        """Host side of the lazy alloc/COW discipline for one (slot,
        block): allocate or copy-on-write, evicting under page pressure.
        Termination: n_pages-1 >= n_blocks (checked at init) and the
        requester holds < n_blocks pages when an alloc is needed, so
        after evicting every other slot a free page must exist."""
        while True:
            before = int(self.tables.table[s, j])
            try:
                pair = self.tables.ensure_writable(s, j)
            except OutOfPages:
                if not self._evict_one(s):
                    raise
                continue
            if pair is not None:
                copies.append(pair)
                self.pages_copied += 1
            if int(self.tables.table[s, j]) != before:
                self._bt_dirty = True
            return

    def _prepare_pages_for_step(self) -> None:
        """Before every decode step: make each active slot's write block
        (ring position n_cached mod CL) exclusively owned — lazy alloc at
        block entry, COW at a fork's divergence block — and materialize
        the COW copies on device. Establishes the invariant the jitted
        step relies on: no write ever lands on a page with refcount > 1."""
        if not self._paged:
            return
        ps = self.allocator.page_size
        cl = self._cache_len
        copies: List[Tuple[int, int]] = []
        for s in np.where(self._host_active)[0]:
            if not self._host_active[s]:
                continue  # evicted mid-loop by an earlier slot's alloc
            j = (int(self._host_ncached[s]) % cl) // ps
            self._ensure_block(int(s), j, copies)
        if copies:
            src = np.array([c[0] for c in copies])
            dst = np.array([c[1] for c in copies])
            cache = dict(self.state["cache"])
            for k in ("k", "v", "c_kv", "k_rope"):
                if k in cache:
                    cache[k] = cache[k].at[:, dst].set(cache[k][:, src])
            self.state = dict(self.state, cache=cache)
        self._sync_tables()

    def _release_slot_pages(self, s: int) -> None:
        """Rollout finished (or slot abandoned): drop the slot's page
        references — shared prefix pages survive until the last fork
        finishes — and zero its table row so the static-shape stale
        writes of the now-inactive row land on the trash page."""
        if self._paged:
            self.tables.release_row(int(s))
            self._bt_dirty = True

    @staticmethod
    def _recompute_impl_paged(params, st, block_tables, cfg: ModelConfig):
        """Paged twin of `_recompute_impl`: recompute through the slot
        twin's ring-gather, then scatter each row's ring view into its own
        pages. The caller has unshared every block (refcount 1), so no
        page is written twice except the trash page (unallocated entries
        of inactive/short rows — never read)."""
        view = GenerationEngine._recompute_impl(
            params, dict(st, cache=_paged_ring_view(st["cache"],
                                                    block_tables)), cfg)
        new = dict(st["cache"])
        NB = block_tables.shape[1]
        for k in ("k", "v", "c_kv", "k_rope"):
            if k not in new:
                continue
            pool = new[k]                         # (L,NP,PS,...)
            L, NP, PS = pool.shape[:3]
            v = jnp.moveaxis(view[k], cache_seq_axis(k), 2)  # (L,H,CL,...)
            vr = v.reshape((L, v.shape[1], NB, PS) + v.shape[3:])
            new[k] = pool.at[:, block_tables].set(vr.astype(pool.dtype))
        return new

    @staticmethod
    def _recompute_impl(params, st, cfg: ModelConfig):
        H, T = st["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(T)[None], (H, T))
        out = M.forward(params, st["tokens"], positions, cfg, return_cache=True)
        # entries at positions >= n_cached are garbage in both old and new
        # caches (masked by cache_index), so a full overwrite is safe.
        new = dict(st["cache"])
        for k in ("k", "v", "c_kv", "k_rope", "conv", "ssd"):
            if k not in out["cache"]:
                continue
            if k in ("conv", "ssd"):
                continue  # recurrent state recompute not supported here
            full = out["cache"][k]         # full length: T on the ring axis
            if full.shape == new[k].shape:
                new[k] = full.astype(new[k].dtype)
                continue
            # ring cache (CL < T): gather the last CL positions of the
            # full-length recompute into ring order — slot j must hold the
            # most recent position p <= n_cached-1 with p ≡ j (mod CL),
            # exactly what the sequential decode loop would have written
            # (the §3 ablation then works on sliding-window engines too).
            # Rows with n_cached <= CL reduce to p_j = j for live slots;
            # slots beyond a row's frontier clamp to dead positions that
            # count-based decode masking never reads.
            ax = cache_seq_axis(k)
            CL = new[k].shape[ax]
            nc = st["n_cached"][None, :, None]              # (1,H,1)
            j = jnp.arange(CL)[None, None]                  # (1,1,CL)
            p = (nc - 1) - jnp.mod(nc - 1 - j, CL)          # (1,H,CL)
            p = jnp.clip(p, 0, T - 1)
            idx = jnp.moveaxis(p.reshape(p.shape + (1,) * (full.ndim - 3)),
                               2, ax)
            new[k] = jnp.take_along_axis(
                full, jnp.broadcast_to(idx, new[k].shape),
                axis=ax).astype(new[k].dtype)
        return new

    # ----- admission ----------------------------------------------------
    def refill(self, now: float = 0.0) -> int:
        """Fill inactive slots with fresh prompts. The prompt source may
        return None to decline (serving: empty request queue) — those slots
        stay inactive. Returns #admitted.

        Admission is device-side: a jitted, donated `admit` scatters the
        new prompt rows into tokens/n_cached/prompt_len/lp/active (no full
        engine-state round trip through host numpy), then chunked prefill
        writes the prompts' K/V into the slot cache in ceil((P-1)/chunk)
        batched forwards (prefill_chunk=0: legacy token-at-a-time loop).

        Paged mode (DESIGN.md §9): admission is page-costed — a prompt
        only enters when the pool can back its blocks (otherwise it parks
        in `_deferred`, consumed first next refill, and the engine stops
        pulling for the tick); identical prompts admitted in the same
        refill form a GRPO prefix-sharing group: the leader alone runs
        prefill (and alone counts prefill tokens/pages), the rest fork
        its pages copy-on-write and merely copy its recurrent SSM state.
        """
        self.last_admit_prefill_tokens = 0
        self.last_admit_pages = 0
        free = np.where(~self._host_active)[0]
        if free.size == 0:
            return 0
        calls0 = self.prefill_invocations
        with span("engine.admit") as counts:
            n = self._admit_into(free, now)
            counts.update(admitted=n,
                          prefill_tokens=self.last_admit_prefill_tokens,
                          prefill_calls=self.prefill_invocations - calls0)
        return n

    def _admit_into(self, free: np.ndarray, now: float) -> int:
        H, T = self.ec.n_slots, self.ec.max_len
        new_tokens = np.full((H, T), self.ec.pad_id, np.int32)
        new_plen = np.zeros(H, np.int32)
        mask = np.zeros(H, bool)
        admitted = []
        chunk = self.prefill_chunk_size
        allocs0 = self.allocator.total_allocs if self._paged else 0
        # prefix sharing needs the chunked path: forks resume at n_cached
        # = P-1, which the legacy token-forcing loop never reaches
        share = self._paged and chunk > 0 and self.ec.prefix_sharing
        leaders: Dict[Tuple[int, ...], int] = {}
        prefill_mask = np.zeros(H, bool)   # rows that run prefill
        forks: List[Tuple[int, int]] = []  # (fork slot, leader slot)
        # a rejected prompt re-offers its slot immediately (otherwise one
        # overlong request idles a slot for a whole tick while admissible
        # prompts wait); the budget bounds the spin against a pathological
        # source that yields nothing but overlong prompts
        rejects_left = _MAX_REJECTS_PER_REFILL
        out_of_pages = False
        for s in free:
            while True:
                prob = (self._deferred.popleft() if self._deferred
                        else self.prompt_source())
                if prob is None:
                    break
                pl = len(prob.prompt_ids)
                if pl <= T - 2:
                    break
                # no room for even one sampled token + EOS: either clip
                # (legacy, opt-in) or reject-and-count — never silently
                # truncate, the reward scores the full problem
                if self.ec.long_prompt == "truncate":
                    pl = T - 2
                    self.prompts_truncated += 1
                    break
                self.prompts_rejected += 1
                if self.on_prompt_rejected is not None:
                    self.on_prompt_rejected(prob)
                rejects_left -= 1
                if rejects_left <= 0:
                    prob = None
                    break
            if prob is None:
                if rejects_left <= 0:
                    break
                continue
            key = tuple(prob.prompt_ids[:pl]) if share else None
            if share and key in leaders:
                # COW fork: share the leader's pages, prefill nothing
                forks.append((int(s), leaders[key]))
            elif self._paged:
                if self.allocator.free_pages < self.pages_needed(pl):
                    # page-costed admission: park the prompt (front of the
                    # deferral queue) and stop pulling — pages free up as
                    # in-flight rollouts finish
                    self._deferred.appendleft(prob)
                    out_of_pages = True
                    break
                need = (self.tables.blocks_for(
                    min(max(pl - 1, 0), self._cache_len)) if chunk else 0)
                if need:
                    self.tables.alloc_prefix(int(s), need)
                    self._bt_dirty = True
                if share:
                    leaders[key] = int(s)
                prefill_mask[s] = True
            else:
                prefill_mask[s] = True
            admitted.append(s)
            new_tokens[s, :pl] = prob.prompt_ids[:pl]
            new_plen[s] = pl
            mask[s] = True
            self.problems[s] = prob
            self.ver_buf[s] = 0
            self.started_at[s] = now
        del out_of_pages  # loop already stopped; counted via _deferred
        if not admitted:
            return 0
        # chunked path: the cache is prefilled below, so decode resumes at
        # the LAST prompt token (n_cached = P-1); legacy path starts at 0
        # and forces the prompt token by token
        target_nc = (np.maximum(new_plen - 1, 0) if chunk
                     else np.zeros(H, np.int32))
        with self._ctx():
            self.state = self._admit(self.state, jnp.asarray(new_tokens),
                                     jnp.asarray(new_plen),
                                     jnp.asarray(target_nc.astype(np.int32)),
                                     jnp.asarray(mask))
        self._host_active[mask] = True
        self._host_prompt_len[mask] = new_plen[mask]
        self._host_ncached[mask] = target_nc[mask]
        self._sync_tables()
        if chunk:
            # forks never prefill: their cache IS the leader's prefix
            n_pre = (int(new_plen[prefill_mask].max()) - 1
                     if prefill_mask.any() else 0)
            for off in range(0, max(n_pre, 0), chunk):
                # grid-level early exit for the prefill kernel: bound the
                # valid cache-slot count from the host-known chunk offset,
                # rounded up to the kernel block so jit sees at most
                # CL/block distinct static values (DESIGN.md §5)
                hint = None
                if self._use_prefill_hint:
                    cl = self._cache_len
                    blk = attn.prefill_block_k(cl)
                    hint = int(min(cl, -(-min(off, cl) // blk) * blk))
                with self._ctx():
                    self.state = self._prefill(self.params, self.state, off,
                                               jnp.asarray(prefill_mask),
                                               self._bt_jax,
                                               offset_hint=hint)
                self.prefill_invocations += 1
            self.last_admit_prefill_tokens = int(
                np.maximum(new_plen[prefill_mask] - 1, 0).sum())
            self.prefill_tokens += self.last_admit_prefill_tokens
            self.prompt_prefills += int(prefill_mask.sum())
        if forks:
            for f, ldr in forks:
                self.tables.fork_row(f, ldr)
            self._bt_dirty = True
            self._sync_tables()
            self.prefix_forks += len(forks)
            # recurrent SSM state is per-slot (not paged): forks copy the
            # leader's post-prefill conv/ssd rows
            farr = np.array([f for f, _ in forks])
            larr = np.array([ldr for _, ldr in forks])
            cache = dict(self.state["cache"])
            for k in ("conv", "ssd"):
                if k in cache:
                    cache[k] = cache[k].at[:, farr].set(cache[k][:, larr])
            self.state = dict(self.state, cache=cache)
        if self._paged:
            self.last_admit_pages = self.allocator.total_allocs - allocs0
        return len(admitted)

    @property
    def n_active(self) -> int:
        return int(self._host_active.sum())

    # ----- stepping -----------------------------------------------------
    def step(self, task: Optional[MathTask] = None,
             now: float = 0.0) -> List[Rollout]:
        """Generate one token on every active slot; returns rollouts that
        finished this step."""
        with span("engine.decode") as counts:
            if self._paged:
                # host-side COW hook: every active slot's next write lands on
                # an exclusively-owned page (may preempt a slot on OutOfPages,
                # which deactivates it before the mirrors are snapshotted)
                self._prepare_pages_for_step()
            prev_active = self._host_active.copy()
            prev_ncached = self._host_ncached.copy()
            n_active = int(prev_active.sum())
            # kv_inplace: this step writes its K/V rows into the slot
            # cache in place (0: paged pools, or no attention cache)
            counts.update(active=n_active,
                          ctx=int((prev_ncached[prev_active] + 1).sum()),
                          kv_inplace=int(self._cache_len is not None
                                         and not self._paged))
            # grid-level early exit for flash-decode: bound the valid cache
            # length from the host mirrors, rounded up to the kernel's block
            # size so jit sees at most CL/block distinct static values. Only
            # active slots count — an idle slot's stale high count would pin
            # the hint at capacity; inactive rows' (possibly truncated)
            # attention outputs are discarded by the `active` gating anyway.
            hint = None
            if self._use_decode_hint:
                cl = self._cache_len
                blk = attn.decode_block_k(cl)
                cur = (int(self._host_ncached[self._host_active].max()) + 1
                       if self._host_active.any() else 1)
                hint = int(min(cl, -(-cur // blk) * blk))
            with span("engine.decode.dispatch"), self._ctx():
                self.state, finished = self._step(self.params, self.state,
                                                  self._bt_jax,
                                                  kv_len_hint=hint)
            with span("engine.decode.wait"):
                finished = np.asarray(finished)
            # record weight version for tokens written this step — only
            # tokens actually *sampled* under μ; prompt-forced tokens keep
            # version 0 so token-lag stats can't be diluted by the prompt
            # mask convention
            nxt = prev_ncached + 1
            wrote = (prev_active & (nxt < self.ec.max_len)
                     & (nxt >= self._host_prompt_len))
            self.ver_buf[wrote, nxt[wrote]] = self.version
            self.tokens_generated += n_active
            # advance host mirrors (device does n_cached+1 on active slots)
            self._host_ncached[prev_active] += 1
            self._host_active[finished] = False

            if not finished.any():
                return []
            with span("engine.decode.finish",
                      finished=int(finished.sum())):
                return self._finished_rollouts(finished, task, now)

    def _finished_rollouts(self, finished: np.ndarray,
                           task: Optional[MathTask],
                           now: float) -> List[Rollout]:
        """Read back the finished slots' tokens and logprobs, score
        them and build their `Rollout`s."""
        done: List[Rollout] = []
        tokens = np.asarray(self.state["tokens"])
        lp = np.asarray(self.state["lp"])
        for s in np.where(finished)[0]:
            if self._paged:
                # finished slots return their pages (shared-prefix
                # pages only truly free once every fork finishes)
                self._release_slot_pages(int(s))
            L = int(self._host_ncached[s]) + 1  # incl. just-sampled token
            L = min(L, self.ec.max_len)
            prob = self.problems[s]
            pl = int(self._host_prompt_len[s])
            completion = tokens[s, pl:L]
            reward = 0.0
            if task is not None and prob is not None:
                reward = task.reward(prob, completion,
                                     self.ec.max_len - pl)
            done.append(Rollout(
                tokens=tokens[s, :L].copy(),
                prompt_len=pl,
                behavior_logprobs=lp[s, :L].copy(),
                reward=reward,
                weight_versions=self.ver_buf[s, :L].copy(),
                finished_at=now,
                prompt_key=(hash(tuple(prob.prompt_ids)) & 0x7FFFFFFF
                            if prob is not None else 0),
                slot=int(s),
                truncated=bool(tokens[s, L - 1] != self.ec.eos_id),
            ))
        return done

    def oldest_inflight_version(self) -> Optional[int]:
        """Smallest weight-version stamp among sampled tokens of in-flight
        (active, past-prompt) slots — the staleness frontier the periodic-
        asynchrony gate reports. None when nothing sampled is in flight."""
        oldest: Optional[int] = None
        for s in np.where(self._host_active)[0]:
            pl = int(self._host_prompt_len[s])
            nc = int(self._host_ncached[s])
            if nc + 1 <= pl:       # still in prompt: nothing sampled yet
                continue
            v = int(self.ver_buf[s, pl:min(nc + 1, self.ec.max_len)].min())
            oldest = v if oldest is None else min(oldest, v)
        return oldest
