"""Differential tests: paged KV engine vs the slot-array oracle
(DESIGN.md §9).

The paged engine's default read path gathers each slot's pages into the
contiguous ring view and runs the *unchanged* attention on it, so every
rollout — tokens, behavior logprobs, per-token weight versions — must be
BIT-identical to the slot engine under the same seed and prompt stream:
across architectures (GQA / MLA / SSM / hybrid), Pallas on and off,
ragged prompts, ring (sliding-window) caches, mid-stream in-flight weight
updates, and GRPO prefix sharing. The opt-in paged flash-decode kernel
reassociates the softmax per page, so it is bitwise only when page_size
equals the slot kernel's block size (pinned separately).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.configs.tiny import config as tiny_config
from repro.core.events import EventLoop, PoolRouter
from repro.core.pipeline import PipelineConfig, PipelineRL
from repro.core.rollout import EngineConfig, GenerationEngine
from repro.core.serving import Server
from repro.core.trainer import Trainer
from repro.data.math_task import MathTask, Problem
from repro.models import attention as attn
from repro.models import model as M
from repro.sharding import tree_values

TASK = MathTask(max_operand=5, ops="+")


def _arch_setup(arch: str, use_pallas: bool = False):
    if arch == "gqa":
        cfg = tiny_config(vocab_size=TASK.tok.vocab_size, d_model=64,
                          n_layers=2)
    else:
        name = {"mla": "deepseek-v3-671b", "ssm": "mamba2-2.7b",
                "hybrid": "hymba-1.5b"}[arch]
        cfg = dataclasses.replace(smoke_config(get_config(name)),
                                  vocab_size=TASK.tok.vocab_size)
    if use_pallas:
        cfg = dataclasses.replace(cfg, use_pallas=True)
    params = tree_values(M.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, params


def _list_source(problems):
    it = iter(list(problems))
    return lambda: next(it, None)


def _drain(engine, max_steps=300):
    out = []
    for _ in range(max_steps):
        out.extend(engine.step(TASK))
        if engine.n_active == 0:
            break
    return out


def _ragged_probs(lens=(3, 5, 9, 13)):
    return [Problem(list(range(2, 2 + n)), 0) for n in lens]


def _assert_rollouts_bitwise(a_list, b_list, n):
    a_list = sorted(a_list, key=lambda r: r.slot)
    b_list = sorted(b_list, key=lambda r: r.slot)
    assert len(a_list) == len(b_list) == n
    for a, b in zip(a_list, b_list):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert a.prompt_len == b.prompt_len
        np.testing.assert_array_equal(a.behavior_logprobs,
                                      b.behavior_logprobs)
        np.testing.assert_array_equal(a.weight_versions, b.weight_versions)


def _paged_done(engine):
    """Post-drain paged-engine hygiene: every page back in the pool and
    the table/allocator cross-checks clean."""
    if engine.allocator is not None:
        assert engine.allocator.live_pages == 0
        engine.tables.check()


# ---------------------------------------------------------------------------
# bit-identity across architectures, ragged prompts, in-flight update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("arch", ["gqa", "mla", "ssm", "hybrid"])
def test_paged_bitwise_equals_slots(arch, use_pallas):
    """Ragged prompts + a mid-stream atomic weight update: the paged
    engine must replay the slot engine bit-for-bit, including the
    per-token weight-version stamps."""
    cfg, params = _arch_setup(arch, use_pallas)
    p2 = tree_values(M.init_params(cfg, jax.random.PRNGKey(7)))
    probs = _ragged_probs()
    ec = EngineConfig(n_slots=4, max_len=16, prefill_chunk=4,
                      temperature=1e-4)
    eS = GenerationEngine(cfg, params, ec, _list_source(probs), seed=2)
    eP = GenerationEngine(cfg, params,
                          dataclasses.replace(ec, cache="paged", page_size=4),
                          _list_source(probs), seed=2)
    assert eS.refill() == 4 and eP.refill() == 4
    outS, outP = [], []
    for i in range(300):
        if i == 3:   # in-flight update over live, partially-shared caches
            eS.set_weights(p2, 1)
            eP.set_weights(p2, 1)
        outS.extend(eS.step(TASK))
        outP.extend(eP.step(TASK))
        if eS.n_active == 0 and eP.n_active == 0:
            break
    _assert_rollouts_bitwise(outS, outP, 4)
    _paged_done(eP)


@pytest.mark.parametrize("arch", ["gqa", "hybrid"])
def test_paged_ring_cache_bitwise(arch):
    """Sliding-window (ring) caches page like everything else: block j
    holds ring positions [j*PS, (j+1)*PS) and decode wraps through the
    same table."""
    cfg, params = _arch_setup(arch)
    cfg = dataclasses.replace(cfg, sliding_window=8)
    probs = _ragged_probs((4, 6, 11, 13))
    ec = EngineConfig(n_slots=4, max_len=16, prefill_chunk=4,
                      temperature=1e-4)
    eS = GenerationEngine(cfg, params, ec, _list_source(probs), seed=3)
    eP = GenerationEngine(cfg, params,
                          dataclasses.replace(ec, cache="paged", page_size=4),
                          _list_source(probs), seed=3)
    assert eS.refill() == 4 and eP.refill() == 4
    _assert_rollouts_bitwise(_drain(eS), _drain(eP), 4)
    _paged_done(eP)


def test_paged_streamed_update_bitwise():
    """The chunked weight stream (DESIGN.md §7) interleaves with decode;
    version stamps must stay exact on the paged engine too."""
    cfg, params = _arch_setup("gqa")
    p2 = tree_values(M.init_params(cfg, jax.random.PRNGKey(9)))
    probs = _ragged_probs()
    ec = EngineConfig(n_slots=4, max_len=16, prefill_chunk=4,
                      temperature=1e-4)
    engines = []
    for cache in ("slots", "paged"):
        e = GenerationEngine(
            cfg, params,
            dataclasses.replace(ec, cache=cache, page_size=4),
            _list_source(probs), seed=6)
        e.refill()
        e.begin_weight_stream(p2, 1, n_chunks=4)
        engines.append(e)
    outs = [[], []]
    for _ in range(300):
        for e, out in zip(engines, outs):
            e.stream_weight_chunk()
            out.extend(e.step(TASK))
        if all(e.n_active == 0 for e in engines):
            break
    _assert_rollouts_bitwise(outs[0], outs[1], 4)
    _paged_done(engines[1])


@pytest.mark.parametrize("rec", [False, True], ids=["stale", "recompute"])
def test_paged_recompute_kv_bitwise(rec):
    """§5.1 ablation on pages: recompute-under-new-weights scatters the
    ring view back through the block table (after unsharing every COW
    block) and must match the slot engine's recompute exactly."""
    cfg, params = _arch_setup("gqa")
    p2 = tree_values(M.init_params(cfg, jax.random.PRNGKey(11)))
    probs = _ragged_probs()
    ec = EngineConfig(n_slots=4, max_len=16, prefill_chunk=4,
                      temperature=1e-4)
    eS = GenerationEngine(cfg, params, ec, _list_source(probs), seed=4)
    eP = GenerationEngine(cfg, params,
                          dataclasses.replace(ec, cache="paged", page_size=4),
                          _list_source(probs), seed=4)
    eS.refill(), eP.refill()
    outS, outP = [], []
    for i in range(300):
        if i == 3:
            eS.set_weights(p2, 1, recompute_kv=rec)
            eP.set_weights(p2, 1, recompute_kv=rec)
        outS.extend(eS.step(TASK))
        outP.extend(eP.step(TASK))
        if eS.n_active == 0 and eP.n_active == 0:
            break
    _assert_rollouts_bitwise(outS, outP, 4)
    _paged_done(eP)


# ---------------------------------------------------------------------------
# GRPO prefix sharing: prefill-once + COW forks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["gqa", "hybrid", "ssm"])
def test_prefix_sharing_prefills_once_and_stays_bitwise(arch):
    """A G-way group of identical prompts: exactly ONE prefill pass runs
    (counters prove it), the forks share pages copy-on-write, and the
    G rollouts are bit-identical to the slot engine's. Scoped to non-MoE
    archs: capacity-limited MoE dispatch couples batch rows, so leader-
    only prefill takes a different expert route than all-rows prefill."""
    cfg, params = _arch_setup(arch)
    G, pl = 4, 6   # P-1 = 5 splits mid-page for PS=4 -> COW at divergence
    group = [Problem(list(range(3, 3 + pl)), 0) for _ in range(G)]
    ec = EngineConfig(n_slots=G, max_len=16, prefill_chunk=4,
                      temperature=1e-4)
    eS = GenerationEngine(cfg, params, ec, _list_source(group), seed=5)
    eP = GenerationEngine(cfg, params,
                          dataclasses.replace(ec, cache="paged", page_size=4),
                          _list_source(group), seed=5)
    assert eS.refill() == G and eP.refill() == G
    if eP._paged:
        # the whole point: the group's prompt was prefilled exactly once
        assert eP.prompt_prefills == 1
        assert eP.prefix_forks == G - 1
        assert eP.last_admit_prefill_tokens == pl - 1
        assert eS.last_admit_prefill_tokens == G * (pl - 1)
    _assert_rollouts_bitwise(_drain(eS), _drain(eP), G)
    if eP._paged:
        assert eP.pages_copied >= G - 1   # COW actually fired mid-page
    _paged_done(eP)


def test_prefix_sharing_off_prefills_everything():
    cfg, params = _arch_setup("gqa")
    group = [Problem([3, 4, 5, 6, 7, 8], 0) for _ in range(4)]
    ec = EngineConfig(n_slots=4, max_len=16, prefill_chunk=4,
                      cache="paged", page_size=4, prefix_sharing=False,
                      temperature=1e-4)
    e = GenerationEngine(cfg, params, ec, _list_source(group), seed=5)
    assert e.refill() == 4
    assert e.prompt_prefills == 4 and e.prefix_forks == 0


# ---------------------------------------------------------------------------
# the opt-in paged flash-decode kernel
# ---------------------------------------------------------------------------

def test_paged_kernel_bitwise_when_page_equals_block():
    """flash_decode_paged == flash_decode on the gathered view, bitwise,
    when page_size == the slot kernel's block size (same softmax block
    reassociation); the engine-level run must then also be bitwise."""
    from repro.kernels import ops as kops
    from repro.kernels.paged_cache import gather_pages
    rng = np.random.default_rng(0)
    B, H, KV, D, NB = 3, 4, 2, 8, 4
    CL = NB * 4
    blk = attn.decode_block_k(CL)
    PS = blk            # the bitwise-equality condition
    NBe = CL // PS
    n_pages = B * NBe + 1
    pool_k = rng.standard_normal((n_pages, PS, KV, D)).astype(np.float32)
    pool_v = rng.standard_normal((n_pages, PS, KV, D)).astype(np.float32)
    bt = np.arange(1, n_pages).reshape(B, NBe).astype(np.int32)
    lengths = np.array([CL, 5, 9], np.int32)
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    kg = np.swapaxes(gather_pages(pool_k, bt), 1, 2)[None]  # (1,B,KV,CL,D)
    vg = np.swapaxes(gather_pages(pool_v, bt), 1, 2)[None]
    ref = kops.flash_decode(q, kg, vg, lengths, scale=0.5, block_k=blk)
    out = kops.flash_decode_paged(q, pool_k, pool_v, bt, lengths, scale=0.5)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    # and with the grid-shrinking length hint
    out_h = kops.flash_decode_paged(q, pool_k, pool_v, bt, lengths,
                                    scale=0.5, max_len_hint=CL)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_h))


def test_paged_kernel_engine_matches_gather_engine():
    """EngineConfig.paged_attention="kernel" routes decode through the
    scalar-prefetch kernel; tokens match the gather engine at ~greedy
    temperature and logprobs agree to fp32 tolerance."""
    cfg, params = _arch_setup("gqa")
    probs = _ragged_probs()
    ec = EngineConfig(n_slots=4, max_len=16, prefill_chunk=4,
                      cache="paged", page_size=4, temperature=1e-4)
    eG = GenerationEngine(cfg, params, ec, _list_source(probs), seed=2)
    eK = GenerationEngine(cfg, params,
                          dataclasses.replace(ec, paged_attention="kernel"),
                          _list_source(probs), seed=2)
    assert eG.refill() == 4 and eK.refill() == 4
    outG = sorted(_drain(eG), key=lambda r: r.slot)
    outK = sorted(_drain(eK), key=lambda r: r.slot)
    assert len(outG) == len(outK) == 4
    for a, b in zip(outG, outK):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_allclose(a.behavior_logprobs, b.behavior_logprobs,
                                   atol=1e-5)
    _paged_done(eK)


# ---------------------------------------------------------------------------
# page-costed admission, eviction, crash hygiene
# ---------------------------------------------------------------------------

def test_can_admit_and_page_costing():
    cfg, params = _arch_setup("gqa")
    # two DISTINCT 13-token prompts (identical ones would fork for free);
    # cl=16, ps=4 -> 4 blocks/slot; 5 usable pages back one 13-token
    # prompt (4 blocks) but not a second
    probs = [Problem(list(range(2, 15)), 0), Problem(list(range(3, 16)), 0)]
    ec = EngineConfig(n_slots=2, max_len=16, prefill_chunk=4,
                      cache="paged", page_size=4, n_pages=6)
    e = GenerationEngine(cfg, params, ec, _list_source(probs), seed=1)
    assert e.pages_needed(13) == 4
    assert e.can_admit(13)
    assert e.refill() == 1          # second prompt deferred: no pages
    assert len(e._deferred) == 1
    assert not e.can_admit(13)
    assert e.last_admit_pages >= 3  # prefill blocks charged to the refill
    # slot engines cost 0 pages and admit on free slots alone
    eS = GenerationEngine(cfg, params,
                          dataclasses.replace(ec, cache="slots"),
                          _list_source(_ragged_probs((13, 13))), seed=1)
    assert eS.pages_needed(13) == 0 and eS.can_admit(13)
    assert eS.refill() == 2


def test_eviction_under_page_pressure_loses_nothing():
    """A pool far too small for the slot count: admission defers, decode
    preempts the least-progressed slot on page exhaustion, and every
    prompt still completes exactly once — with zero leaked pages."""
    cfg, params = _arch_setup("gqa")
    probs = [TASK.sample() for _ in range(8)]
    ec = EngineConfig(n_slots=4, max_len=16, prefill_chunk=4,
                      cache="paged", page_size=4, n_pages=7,
                      temperature=1e-4)
    e = GenerationEngine(cfg, params, ec, _list_source(probs), seed=5)
    done = []
    for _ in range(400):
        e.refill()
        done.extend(e.step(TASK))
        if e.n_active == 0 and not e._deferred:
            break
    assert len(done) == 8
    assert e.slots_preempted > 0
    _paged_done(e)


def test_reset_slots_releases_shared_pages():
    """Engine kill mid-group: every page reference — including the COW-
    shared prefix, whose refcount drops once per holding fork — returns
    to the pool, and the deferred queue is salvageable first."""
    cfg, params = _arch_setup("gqa")
    group = [Problem([3, 4, 5, 6, 7, 8], 0) for _ in range(4)]
    ec = EngineConfig(n_slots=2, max_len=16, prefill_chunk=4,
                      cache="paged", page_size=4, temperature=1e-4)
    e = GenerationEngine(cfg, params, ec, _list_source(group), seed=1)
    assert e.refill() == 2
    e.step(TASK)
    e._deferred.append(Problem([9, 9], 0))
    assert e.allocator.live_pages > 0
    salvaged = e.drain_deferred()
    assert [p.prompt_ids for p in salvaged] == [[9, 9]]
    lost = e.reset_slots()          # asserts zero leaked pages internally
    assert lost == 2
    assert e.allocator.live_pages == 0
    e.tables.check()
    # the table rows pushed to device are all trash-page zeros
    assert int(np.asarray(e._bt_jax).sum()) == 0


def test_engine_crash_under_faultplan_leaks_no_pages():
    """Fault-injection end to end: a paged engine crashed by the
    FaultPlan mid-decode salvages its prompts (live slots AND page-
    deferred ones) into the router, the pool re-admits them on the
    survivor, and the dead engine holds zero pages."""
    from repro.core.events import FaultPlan
    from repro.core.sim import HardwareModel
    task = TASK
    cfg = tiny_config(vocab_size=task.tok.vocab_size, d_model=64, n_layers=1)
    params = tree_values(M.init_params(cfg, jax.random.PRNGKey(0)))
    ec = EngineConfig(n_slots=8, max_len=16, cache="paged", page_size=4)
    pc = PipelineConfig(batch_size=4, n_opt_steps=4, n_chips=8,
                        train_chips=4, pack_rows=2, pack_seq=48, n_engines=2)
    hw = HardwareModel(h_sat=16, bcast_bytes_per_flash=2e3)
    plan = FaultPlan().engine_crash(at=120.0, engine=1)   # permanent
    p = PipelineRL(cfg, params, task, ec, pc, hw=hw,
                   trainer=Trainer(cfg, params), seed=0, fault_plan=plan)
    p.run()
    ps = p.pool_stats()
    victim = ps["engines"][1]
    assert victim["failures"] == 1 and not victim["alive"]
    assert ps["prompts_salvaged"] > 0
    assert ps["prompts_requeued"] == ps["prompts_salvaged"]
    dead = p.engines[1]
    assert dead.allocator.live_pages == 0
    dead.tables.check()
    # the survivor drained the run; its pages net out to its live slots
    live = p.engines[0]
    held = sum(len(live.tables.owned_pages(s))
               for s in range(ec.n_slots))
    assert live.allocator.live_pages == held
    live.tables.check()


def test_router_declines_pull_when_pages_short():
    cfg, params = _arch_setup("gqa")
    ec = EngineConfig(n_slots=2, max_len=16, prefill_chunk=4,
                      cache="paged", page_size=4, n_pages=6)
    probs = [Problem(list(range(2, 15)), 0), Problem(list(range(3, 16)), 0)]
    router = PoolRouter(_list_source(probs))
    e = GenerationEngine(cfg, params, ec, None, seed=1)
    i = router.add_engine(e)
    e.prompt_source = router.source_for(i)
    assert e.refill() == 1          # first prompt takes all 4 blocks
    assert e.refill() == 0          # router declines: prompt stays pooled
    assert router.declined[i] >= 1
    assert len(router.pending) == 1
    assert len(e._deferred) == 0    # never parked inside the full engine


def test_server_defers_admission_until_pages_free():
    """Serving admission gate: with a pool that backs one request at a
    time, the second request WAITS (counted) instead of failing, and is
    served once the first completes."""
    cfg, params = _arch_setup("gqa")
    ec = EngineConfig(n_slots=2, max_len=16, prefill_chunk=4,
                      cache="paged", page_size=4, n_pages=6,
                      temperature=1e-4)
    srv = Server(cfg, params, ec, seed=0)
    srv.submit(list(range(2, 15)))      # 13 tokens -> all 4 usable pages
    srv.submit(list(range(2, 15)))
    served = []
    for _ in range(120):
        served += srv.step(1.0)
        if len(served) == 2:
            break
    m = srv.metrics()
    assert len(served) == 2
    assert m["admissions_deferred"] > 0
    assert m["requests_lost"] == 0
    assert srv.engine.allocator.live_pages == 0
