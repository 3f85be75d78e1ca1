"""Real-mesh distributed runtime (DESIGN.md §11) on forced host devices.

The whole module needs a multi-device backend; CI provides one with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (true multi-device
GSPMD on CPU). On a plain single-device host every test here skips.

Covers the four §11 contracts:
  - vocab-sharded fused_logprob: value AND grads match the single-device
    path, both at the kernel level and through the model's fused loss
    routing across GQA / MLA+MoE families, tied and untied heads
  - executed streamed broadcast: real per-chunk reshard installs are
    bit-identical to atomic `set_weights`, with the integrity gate
    (chunk crc + stream digest) armed on real device buffers
  - sharded engines: decode on a mesh-placed engine is token-identical
    to the single-device engine (GSPMD partitioning is
    semantics-preserving), and the pipeline splits engines onto disjoint
    device subsets
  - co-sim calibrated twin: a recorded real-mesh trace replayed through
    the EventLoop agrees with measurement within tolerance
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.configs.tiny import config as tiny_config
from repro.data.math_task import MathTask
from repro.models import model as M
from repro.sharding import tree_values

N_DEV = 8
pytestmark = pytest.mark.skipif(
    jax.device_count() < N_DEV,
    reason=f"needs {N_DEV} devices (run under "
           f"XLA_FLAGS=--xla_force_host_platform_device_count={N_DEV})")

KEY = jax.random.PRNGKey(3)


@pytest.fixture(scope="module")
def mesh():
    from jax.sharding import AxisType
    return jax.make_mesh((N_DEV,), ("model",), (AxisType.Auto,))


def _engine_pair_tasks():
    """Two identically-seeded tasks: `MathTask` derives its prompt stream
    from its own RandomState, so paired engines see identical prompts."""
    return MathTask(max_operand=5, ops="+"), MathTask(max_operand=5, ops="+")


# ---------------------------------------------------------------------------
# vocab-sharded fused loss
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("transpose_head", [False, True])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_sharded_fused_logprob_value_and_grads(mesh, transpose_head,
                                               use_pallas):
    """Kernel-level: the shard_map'd per-shard online-logsumexp combine
    must match the single-device blocked twin on values and on gradients
    to hidden and head, through all three outputs."""
    from repro.kernels.fused_logprob import (fused_logprob_blocked,
                                             fused_logprob_sharded)

    N, D, V = 48, 32, 64
    ks = jax.random.split(KEY, 3)
    h = jax.random.normal(ks[0], (N, D), jnp.float32)
    w = jax.random.normal(
        ks[1], (V, D) if transpose_head else (D, V), jnp.float32) * 0.3
    t = jax.random.randint(ks[2], (N,), 0, V)

    def scalar(fn):
        def f(h, w):
            lp, lse, ent = fn(h, w)
            return (lp * 1.3 - 0.7 * lse + 0.11 * ent).sum()
        return f

    v1, g1 = jax.jit(jax.value_and_grad(scalar(
        lambda h, w: fused_logprob_sharded(
            h, w, t, mesh=mesh, transpose_head=transpose_head,
            use_pallas=use_pallas, interpret=use_pallas)),
        argnums=(0, 1)))(h, w)
    v2, g2 = jax.jit(jax.value_and_grad(scalar(
        lambda h, w: fused_logprob_blocked(
            h, w, t, transpose_head=transpose_head)),
        argnums=(0, 1)))(h, w)
    np.testing.assert_allclose(v1, v2, rtol=2e-4, atol=2e-4)
    for a, b, name in zip(g1, g2, ("dhidden", "dhead")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("arch", ["llama3-8b", "deepseek-v3-671b",
                                  "granite-moe-1b-a400m"])
@pytest.mark.parametrize("tied", [False, True])
def test_sharded_fused_loss_through_model(mesh, arch, tied):
    """Model-level routing: under `sharding_context` the fused lm-head
    call is vocab-sharded; loss stats and parameter gradients must match
    the single-device run across GQA / MLA / MoE, tied and untied."""
    from repro.shardctx import sharding_context

    cfg = dataclasses.replace(smoke_config(get_config(arch)),
                              tie_embeddings=tied, use_mtp=False,
                              fused_loss=True)
    params = tree_values(M.init_params(cfg, KEY))
    B, S = 2, 16
    ks = jax.random.split(jax.random.fold_in(KEY, 5), 1)
    tokens = jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))
    tgt = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)

    def loss(p):
        out = M.forward(p, tokens, positions, cfg, loss_targets=tgt)
        return (out["token_logprobs"] - 0.5 * out["lse"]
                + 0.2 * out["entropy"]).sum(), out

    (v_ref, out_ref), g_ref = jax.value_and_grad(loss, has_aux=True)(params)
    with sharding_context(mesh):
        (v_sh, out_sh), g_sh = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(params)
    np.testing.assert_allclose(float(v_sh), float(v_ref),
                               rtol=2e-4, atol=2e-4)
    for k in ("token_logprobs", "lse", "entropy"):
        np.testing.assert_allclose(np.asarray(out_sh[k]),
                                   np.asarray(out_ref[k]),
                                   rtol=2e-4, atol=2e-4, err_msg=k)
    flat_sh = jax.tree_util.tree_leaves_with_path(g_sh)
    flat_ref = jax.tree_util.tree_leaves(g_ref)
    for (path, a), b in zip(flat_sh, flat_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
            err_msg=jax.tree_util.keystr(path))


# ---------------------------------------------------------------------------
# executed streamed broadcast
# ---------------------------------------------------------------------------

def _tiny_engine(mesh, task, seed=1, **kw):
    from repro.core.rollout import EngineConfig, GenerationEngine
    cfg = tiny_config(vocab_size=task.tok.vocab_size, d_model=64,
                      n_layers=1)
    params = tree_values(M.init_params(cfg, jax.random.PRNGKey(0)))
    ec = EngineConfig(n_slots=4, max_len=20)
    return cfg, params, GenerationEngine(cfg, params, ec, task.sample,
                                         seed=seed, mesh=mesh, **kw)


def test_executed_stream_bitwise_with_integrity_gate(mesh):
    """Streamed install on real device buffers: a corrupt chunk token is
    rejected (no partial state change), the retransmit succeeds, and the
    final params are bit-identical to an atomic `set_weights` of the same
    tree. Every chunk leaves a measured transfer in wexec_log."""
    from repro.core.events import (chunk_spans, chunk_token, span_bytes,
                                   stream_digest)
    from repro.core.rollout import EngineConfig, GenerationEngine

    task_a, task_b = _engine_pair_tasks()
    cfg, params, eng = _tiny_engine(mesh, task_b)
    ec = EngineConfig(n_slots=4, max_len=20)
    ref = GenerationEngine(cfg, params, ec, task_a.sample, seed=1)
    params2 = jax.tree.map(lambda x: x + 0.01, params)

    leaves = jax.tree_util.tree_leaves(params2)
    sizes = span_bytes(leaves, chunk_spans(leaves, 4))
    good = [chunk_token(7, k, sizes[k]) for k in range(len(sizes))]
    eng.begin_weight_stream(params2, 7, n_chunks=4,
                            expect_digest=stream_digest(good))
    done, k = False, 0
    while not done:
        tok = good[min(k, len(good) - 1)]
        if k == 1:    # corrupt one token mid-stream
            assert eng.stream_weight_chunk(token=tok ^ 0x5AD0BAD) is False
            assert eng.wchunks_rejected == 1
        done = eng.stream_weight_chunk(token=tok)
        k += 1
    assert eng.last_stream_installed and eng.version == 7
    chunk_recs = [r for r in eng.wexec_log if r["kind"] == "chunk"]
    assert len(chunk_recs) == 4
    assert all(r["seconds"] > 0 for r in chunk_recs)

    ref.set_weights(params2, 7)
    for a, b in zip(jax.tree_util.tree_leaves(eng.params),
                    jax.tree_util.tree_leaves(ref.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_broadcaster_executor_records_real_transfers(mesh):
    """`WeightBroadcaster(executor=MeshBroadcastExecutor())`: a streamed
    publication executes real per-chunk reshards onto the engine's
    devices, records them, and the installed tree is bitwise right."""
    from repro.core.events import ActorStage, EventLoop, WeightBroadcaster
    from repro.core.sim import HardwareModel
    from repro.launch.meshrt import MeshBroadcastExecutor

    _, task_b = _engine_pair_tasks()
    cfg, params, eng = _tiny_engine(mesh, task_b)
    params2 = jax.tree.map(lambda x: x + 0.01, params)
    loop = EventLoop()
    stage = ActorStage(loop, eng, task=task_b, name="a0")
    bc = WeightBroadcaster(HardwareModel(), [stage], mode="streamed",
                           n_chunks=4, executor=MeshBroadcastExecutor())
    bc.publish(params2, 3, now=0.0)
    stage.start(0.0)
    loop.run(until=lambda: stage.updates_applied >= 1)
    assert eng.version == 3
    assert len(bc.exec_records) == 1
    rec = bc.exec_records[0]
    assert len(rec["per_chunk"]) == 4 and rec["nbytes"] > 0
    assert bc.stats()["executed"] == 1
    for a, b in zip(jax.tree_util.tree_leaves(eng.params),
                    jax.tree_util.tree_leaves(params2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# sharded engines
# ---------------------------------------------------------------------------

def test_sharded_engine_decode_token_identical(mesh):
    """GSPMD partitioning is semantics-preserving: the mesh-placed engine
    produces exactly the single-device engine's tokens, step by step."""
    from repro.core.rollout import EngineConfig, GenerationEngine

    task_a, task_b = _engine_pair_tasks()
    cfg, params, eng = _tiny_engine(mesh, task_b)
    ec = EngineConfig(n_slots=4, max_len=20)
    ref = GenerationEngine(cfg, params, ec, task_a.sample, seed=1)
    ref.refill()
    eng.refill()
    for i in range(20):
        ref.step(task_a)
        eng.step(task_b)
        np.testing.assert_array_equal(np.asarray(ref.state["tokens"]),
                                      np.asarray(eng.state["tokens"]),
                                      err_msg=f"step {i}")
        if ref.n_active == 0:
            ref.refill()
        if eng.n_active == 0:
            eng.refill()


def test_engine_submeshes_are_disjoint(mesh):
    from repro.launch.mesh import engine_submeshes

    subs = engine_submeshes(mesh, 2)
    assert len(subs) == 2
    d0 = set(subs[0].devices.reshape(-1))
    d1 = set(subs[1].devices.reshape(-1))
    assert len(d0) == len(d1) == N_DEV // 2
    assert not d0 & d1
    with pytest.raises(ValueError):
        engine_submeshes(mesh, 3)    # 8 devices don't split 3 ways


def test_pipeline_on_mesh_end_to_end(mesh):
    """The crown e2e: mesh trainer + engines on disjoint submeshes +
    executed streamed broadcast, and every engine at the trainer's
    version holds bitwise-identical params."""
    from repro.core.pipeline import PipelineConfig, PipelineRL
    from repro.core.rollout import EngineConfig

    task = MathTask(max_operand=5, ops="+")
    cfg = tiny_config(vocab_size=task.tok.vocab_size, d_model=64,
                      n_layers=1)
    params = tree_values(M.init_params(cfg, jax.random.PRNGKey(0)))
    ec = EngineConfig(n_slots=4, max_len=20)
    pc = PipelineConfig(batch_size=4, n_opt_steps=3, n_engines=2,
                        pack_rows=4, pack_seq=32, broadcast="streamed",
                        broadcast_chunks=4)
    pipe = PipelineRL(cfg, params, task, ec, pc, mesh=mesh)
    assert pipe.trainer.mesh is mesh
    assert all(e.mesh is not None for e in pipe.engines)
    assert not (set(pipe.actors[0].devices) & set(pipe.actors[1].devices))
    pipe.run()
    st = pipe.broadcast_stats()
    assert pipe.trainer.version >= 3
    assert st["executed"] >= 1
    tp = jax.tree_util.tree_leaves(pipe.trainer.params)
    for e in pipe.engines:
        if e.version == pipe.trainer.version:
            for a, b in zip(jax.tree_util.tree_leaves(e.params), tp):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# co-sim calibrated twin + executed weight-update reshard
# ---------------------------------------------------------------------------

def test_cosim_replay_agrees_with_measurement(mesh):
    """Replaying a recorded real-mesh trace through the EventLoop twin
    must reproduce the measured totals: the sim shares per-tick decode
    costs by construction, so the tolerance bounds its pause/lag
    *accounting* drift."""
    from repro.core.rollout import EngineConfig, GenerationEngine
    from repro.launch.meshrt import record_cosim_trace, replay_trace

    task = MathTask(max_operand=5, ops="+")
    cfg = tiny_config(vocab_size=task.tok.vocab_size, d_model=64,
                      n_layers=1)
    params = tree_values(M.init_params(cfg, jax.random.PRNGKey(0)))
    params2 = jax.tree.map(lambda x: x + 0.01, params)
    ec = EngineConfig(n_slots=4, max_len=20)
    eng = GenerationEngine(cfg, params, ec, task.sample, seed=2, mesh=mesh)
    trace = record_cosim_trace(eng, params2, n_ticks=24, publish_every=8,
                               n_chunks=4, task=task)
    rep = replay_trace(trace)
    rel = (abs(rep["sim_total_s"] - rep["measured_total_s"])
           / max(rep["measured_total_s"], 1e-12))
    assert rel < 0.05, rel
    assert rep["updates_sim"] == rep["updates_measured"] == 2
    assert abs(rep["mean_lag_sim"] - rep["mean_lag_measured"]) <= 0.5
    assert rep["sim_pause_per_update"] > 0
    np.testing.assert_allclose(rep["sim_pause_per_update"],
                               rep["measured_pause_per_update"],
                               rtol=0.05)


def test_execute_weight_update_measures_chunks(mesh):
    """The executed trainer→generator reshard: one timed record per
    chunk, chunk bytes summing to the whole tree, and the byte guard
    refusing configs that can't fit."""
    from repro.launch.steps import execute_weight_update

    task = MathTask(max_operand=5, ops="+")
    cfg = tiny_config(vocab_size=task.tok.vocab_size, d_model=64,
                      n_layers=1)
    recs = execute_weight_update(cfg, mesh, n_chunks=4)
    assert len(recs) == 4
    assert all(r["t_exec_s"] > 0 for r in recs)
    ann = M.init_params(cfg, abstract=True)
    total = sum(v.size * v.dtype.itemsize
                for v in jax.tree_util.tree_leaves(tree_values(ann)))
    assert sum(r["nbytes"] for r in recs) == total
    with pytest.raises(ValueError):
        execute_weight_update(cfg, mesh, n_chunks=2, max_bytes=16)
