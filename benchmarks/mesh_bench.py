"""Real-mesh runtime benchmarks (DESIGN.md §11).

Measures, on a 1D mesh over every device of the backend (real chips, or
forced host devices on CPU — the same GSPMD partitioning either way):

  - decode step time: replicated single-device engine vs the same tiny
    config mesh-sharded through `sharding_context` (the absolute numbers
    are CPU-host noise; the point is the sharded program compiles, runs,
    and stays token-identical — parity is asserted in the test suite)
  - executed streamed broadcast: per-chunk reshard+install wall time from
    the engine's `wexec_log` vs the atomic `set_weights` transfer, and
    the measured decode pause per weight update
  - co-sim calibration: `record_cosim_trace` replayed through the
    EventLoop twin — predicted vs measured totals and pause accounting
  - the executed trainer→generator weight-update reshard
    (`execute_weight_update`): measured per-chunk t_exec_s, the runtime
    companion of the dry-run's compiled t_collective_s estimate

Emits ``BENCH_mesh.json``. The group runs in this process on every device
the backend has (one process per chip: a parent that has touched JAX holds
the devices). On CPU, force the device count before JAX starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.run --only mesh
"""
from __future__ import annotations

import json
import os
import time
from typing import List, Tuple

Row = Tuple[str, float, str]

JSON_PATH = "BENCH_mesh.json"
N_CHUNKS = 4


def _median(xs):
    return sorted(xs)[len(xs) // 2]


def _step_time(engine, task, iters=15):
    import jax
    engine.refill()
    times = []
    for i in range(iters + 3):
        if engine.n_active == 0:
            engine.refill()
        t0 = time.perf_counter()
        engine.step(task)
        jax.block_until_ready(engine.state["tokens"])
        if i >= 3:    # first rounds pay compile
            times.append(time.perf_counter() - t0)
    return _median(times)


def mesh_benchmarks() -> List[Row]:
    import jax
    from jax.sharding import AxisType

    from repro.configs.tiny import config as tiny_config
    from repro.core.events import chunk_spans, chunk_token, span_bytes, \
        stream_digest
    from repro.core.rollout import EngineConfig, GenerationEngine
    from repro.data.math_task import MathTask
    from repro.launch.meshrt import record_cosim_trace, replay_trace
    from repro.launch.steps import execute_weight_update
    from repro.models import model as M
    from repro.sharding import tree_values

    n_dev = jax.device_count()
    if n_dev < 2:
        raise RuntimeError(
            "the mesh group needs several devices; on CPU set XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 before starting")
    mesh = jax.make_mesh((n_dev,), ("model",), (AxisType.Auto,))
    backend = jax.default_backend()
    # identically-seeded tasks give each engine the same prompt sequence
    task_a = MathTask(max_operand=5, ops="+")
    task_b = MathTask(max_operand=5, ops="+")
    cfg = tiny_config(vocab_size=task_a.tok.vocab_size, d_model=64,
                      n_layers=1)
    params = tree_values(M.init_params(cfg, jax.random.PRNGKey(0)))
    params2 = jax.tree.map(lambda x: x + 0.01, params)
    ec = EngineConfig(n_slots=4, max_len=24)

    ref = GenerationEngine(cfg, params, ec, task_a.sample, seed=1)
    eng = GenerationEngine(cfg, params, ec, task_b.sample, seed=1, mesh=mesh)
    t_rep = _step_time(ref, task_a)
    t_shard = _step_time(eng, task_b)

    # executed streamed install: integrity gate armed, every chunk a real
    # resharding transfer measured by the engine itself
    leaves = jax.tree_util.tree_leaves(params2)
    sizes = span_bytes(leaves, chunk_spans(leaves, N_CHUNKS))
    toks = [chunk_token(2, k, sizes[k]) for k in range(len(sizes))]
    eng.wexec_log.clear()
    eng.begin_weight_stream(params2, 2, n_chunks=N_CHUNKS,
                            expect_digest=stream_digest(toks))
    for tk in toks:
        eng.stream_weight_chunk(token=tk)
    assert eng.last_stream_installed and eng.version == 2
    chunk_s = [r["seconds"] for r in eng.wexec_log if r["kind"] == "chunk"]
    eng.wexec_log.clear()
    eng.set_weights(params, 3)
    atomic_s = eng.wexec_log[-1]["seconds"]

    # co-sim: record a real decode+install timeline, replay it in the sim
    task_c = MathTask(max_operand=5, ops="+")
    eng2 = GenerationEngine(cfg, params, ec, task_c.sample, seed=2,
                            mesh=mesh)
    trace = record_cosim_trace(eng2, params2, n_ticks=24, publish_every=8,
                               n_chunks=N_CHUNKS, task=task_c)
    rep = replay_trace(trace)
    rel = (abs(rep["sim_total_s"] - rep["measured_total_s"])
           / max(rep["measured_total_s"], 1e-12))

    # executed trainer->generator reshard (the dry-run estimate's twin)
    wu = execute_weight_update(cfg, mesh, n_chunks=N_CHUNKS)

    rows: List[Row] = [
        ("mesh/decode_step_replicated", t_rep * 1e6,
         f"backend={backend};n_dev=1"),
        ("mesh/decode_step_sharded", t_shard * 1e6,
         f"backend={backend};n_dev={n_dev};"
         f"sharded/replicated={t_shard / max(t_rep, 1e-12):.2f}x"),
        ("mesh/broadcast_chunk_install", _median(chunk_s) * 1e6,
         f"n_chunks={N_CHUNKS};max_us={max(chunk_s) * 1e6:.1f};"
         f"sum_us={sum(chunk_s) * 1e6:.1f}"),
        ("mesh/broadcast_atomic", atomic_s * 1e6,
         f"atomic/max_chunk={atomic_s / max(max(chunk_s), 1e-12):.2f}x"),
        ("mesh/pause_per_update_measured",
         rep["measured_pause_per_update"] * 1e6,
         f"sim_us={rep['sim_pause_per_update'] * 1e6:.1f};"
         f"updates={rep['updates_measured']}"),
        ("mesh/cosim_total", rep["measured_total_s"] * 1e6,
         f"sim_us={rep['sim_total_s'] * 1e6:.1f};rel_err={rel:.4f};"
         f"lag_sim={rep['mean_lag_sim']:.2f};"
         f"lag_meas={rep['mean_lag_measured']:.2f}"),
        ("mesh/weight_update_exec", sum(c["t_exec_s"] for c in wu) * 1e6,
         f"n_chunks={len(wu)};"
         f"max_chunk_us={max(c['t_exec_s'] for c in wu) * 1e6:.1f}"),
    ]

    payload = {
        "config": {"n_dev": n_dev, "n_chunks": N_CHUNKS, "backend": backend,
                   "d_model": 64, "n_layers": 1},
        "decode_step_s": {"replicated": t_rep, "sharded": t_shard},
        "broadcast": {"chunk_s": chunk_s, "atomic_s": atomic_s,
                      "chunk_nbytes": [int(s) for s in sizes]},
        "pause_per_update_s": {
            "measured": rep["measured_pause_per_update"],
            "sim": rep["sim_pause_per_update"]},
        "cosim": {"sim_total_s": rep["sim_total_s"],
                  "measured_total_s": rep["measured_total_s"],
                  "rel_total_err": rel,
                  "updates_sim": rep["updates_sim"],
                  "updates_measured": rep["updates_measured"],
                  "mean_lag_sim": rep["mean_lag_sim"],
                  "mean_lag_measured": rep["mean_lag_measured"]},
        "weight_update_exec": wu,
    }
    with open(JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    rows.append(("mesh/json", 0.0, os.path.abspath(JSON_PATH)))
    return rows


if __name__ == "__main__":
    for r in mesh_benchmarks():
        print(f"{r[0]},{r[1]:.1f},{r[2]}")
