"""Program spans (`repro.spans`): nesting, the ring's bound, windows, the
profiler's host plane, what a small pipeline run records, and the names
its jitted programs compile under."""
import gc
import glob
import os
import sys
import time

import jax
import numpy as np
import pytest

from repro import spans
from repro.configs.tiny import config as tiny_config
from repro.core.pipeline import PipelineConfig, PipelineRL
from repro.core.rollout import EngineConfig
from repro.data.math_task import MathTask
from repro.models import model as M
from repro.sharding import tree_values


def _since(t0):
    return spans.records(t0, time.perf_counter() + 1.0)


def test_nesting_sets_parent_and_counts():
    t0 = time.perf_counter()
    with spans.span("outer", a=1):
        with spans.span("inner") as counts:
            counts["n"] = 7
        with spans.span("inner2"):
            pass
    got = _since(t0)
    assert [(r.name, r.parent) for r in got] == [
        ("outer", None), ("inner", "outer"), ("inner2", "outer")]
    outer, inner, inner2 = got
    assert outer.counts == {"a": 1} and inner.counts == {"n": 7}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns \
        <= inner2.start_ns <= inner2.end_ns <= outer.end_ns


def test_a_span_closed_by_an_exception_is_recorded():
    t0 = time.perf_counter()
    with pytest.raises(ValueError):
        with spans.span("outer"):
            with spans.span("failing"):
                raise ValueError("x")
    with spans.span("after"):
        pass
    assert [(r.name, r.parent) for r in _since(t0)] == [
        ("outer", None), ("failing", "outer"), ("after", None)]


def test_records_window_is_half_open_on_the_start():
    t_a = time.perf_counter()
    with spans.span("w.a"):
        pass
    t_mid = time.perf_counter()
    with spans.span("w.b"):
        pass
    t_end = time.perf_counter()
    assert [r.name for r in spans.records(t_a, t_mid)] == ["w.a"]
    assert [r.name for r in spans.records(t_mid, t_end)] == ["w.b"]
    assert [r.name for r in spans.records(t_a, t_end)] == ["w.a", "w.b"]
    assert spans.records(t_end, t_end + 10) == []


def test_ring_keeps_the_newest_records_within_its_bound():
    t0 = time.perf_counter()
    n = spans.CAPACITY + 100
    for i in range(n):
        with spans.span("ring.fill", i=i, nbytes=2 ** 40 + i, version=i,
                        swapped=1):
            pass
    assert len(spans._ring) == spans.CAPACITY
    got = [r for r in _since(t0) if r.name == "ring.fill"]
    assert len(got) == spans.CAPACITY
    assert got[0].counts["i"] == 100 and got[-1].counts["i"] == n - 1
    rec = spans._ring[-1]                 # strings are shared, not counted
    size = (sys.getsizeof(rec) + sum(sys.getsizeof(x) for x in rec[1:3])
            + sum(sys.getsizeof(v) for v in rec[5::2]) + 8)
    assert size <= spans.RECORD_BYTES
    # flat tuples of atomic values: the collector stops tracking them
    gc.collect()
    assert not any(gc.is_tracked(r) for r in spans._ring)


def test_profiler_host_plane_holds_the_span_names(tmp_path):
    from jax.profiler import ProfileData
    x = jax.numpy.ones((8, 8))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("probe.outer", k=3):
            with spans.span("probe.inner") as counts:
                (x @ x).block_until_ready()
                counts["late"] = 1
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    assert {"probe.outer", "probe.inner"} <= names


@pytest.fixture(scope="module")
def small_run():
    task = MathTask(max_operand=5, ops="+")
    cfg = tiny_config(vocab_size=task.tok.vocab_size, d_model=64, n_layers=1)
    params = tree_values(M.init_params(cfg, jax.random.PRNGKey(0)))
    ec = EngineConfig(n_slots=8, max_len=20)
    pc = PipelineConfig(batch_size=4, n_opt_steps=5, n_chips=8, train_chips=4,
                        pack_rows=2, pack_seq=48, broadcast="streamed",
                        broadcast_chunks=4)
    p = PipelineRL(cfg, params, task, ec, pc)
    t0 = time.perf_counter()
    p.run()
    return p, spans.records(t0, time.perf_counter())


def test_pipeline_run_spans_match_its_counters(small_run):
    p, recs = small_run
    by = {}
    for r in recs:
        by.setdefault(r.name, []).append(r)
    eng, actor = p.engines[0], p.actors[0]
    decode = by["engine.decode"]
    assert len(decode) == actor.ticks_completed > 0
    assert sum(r.counts["active"] for r in decode) == eng.tokens_generated
    assert all(r.counts["ctx"] >= r.counts["active"] for r in decode)
    installs = by["engine.install"]
    assert sum(r.counts["swapped"] for r in installs) \
        == actor.streams_completed > 0
    assert len(by["trainer.step"]) == len(p.trainer_stage.log) == 5
    assert sum(r.counts["loss_tokens"] for r in by["trainer.step"]) > 0
    assert len(by["trainer.publish"]) == p.broadcaster.published
    admits = by["engine.admit"]
    assert sum(r.counts["admitted"] for r in admits) >= eng.prompt_prefills
    assert sum(r.counts["prefill_calls"] for r in admits) \
        == eng.prefill_invocations
    assert sum(r.counts["finished"] for r in by["engine.decode.finish"]) \
        >= 5 * 4


def test_pipeline_run_spans_nest_by_layer(small_run):
    _, recs = small_run
    parents = {}
    for r in recs:
        parents.setdefault(r.name, set()).add(r.parent)
    assert parents["actor.tick"] == {None}
    assert parents["engine.decode"] == {"actor.tick"}
    for child in ("dispatch", "wait", "finish"):
        assert parents["engine.decode." + child] == {"engine.decode"}
    for child in ("pack", "step", "sync"):
        assert parents["trainer." + child] == {"trainer.train"}
    # a stream's final swap nests in the chunk install that completes it
    assert parents["engine.swap"] <= {"engine.install"}
    assert parents["engine.install"] == {"actor.tick"}


@pytest.mark.parametrize("cache, inplace", [("slots", 1), ("paged", 0)])
def test_decode_span_counts_kv_inplace(cache, inplace):
    """`engine.decode` counts `kv_inplace`: 1 where the step writes its K/V
    rows into the slot cache in place, 0 on the paged pools."""
    from repro.core.rollout import GenerationEngine
    task = MathTask(max_operand=5, ops="+")
    cfg = tiny_config(vocab_size=task.tok.vocab_size, d_model=64, n_layers=1)
    params = tree_values(M.init_params(cfg, jax.random.PRNGKey(0)))
    eng = GenerationEngine(cfg, params,
                           EngineConfig(n_slots=2, max_len=16, cache=cache),
                           task.sample)
    eng.refill()
    t0 = time.perf_counter()
    for _ in range(3):
        eng.step(task)
    decode = [r for r in _since(t0) if r.name == "engine.decode"]
    assert len(decode) == 3
    assert [r.counts["kv_inplace"] for r in decode] == [inplace] * 3


@pytest.fixture(scope="module")
def programs(small_run):
    """Each jitted program of the engine and trainer, lowered on the
    arguments it runs with."""
    p, _ = small_run
    eng, tr = p.engines[0], p.trainer
    H, T = eng.ec.n_slots, eng.ec.max_len
    i32 = np.int32
    mask = np.zeros(H, bool)
    batch = {"tokens": np.zeros((2, 48), i32),
             "positions": np.zeros((2, 48), i32),
             "segment_ids": np.zeros((2, 48), i32),
             "loss_mask": np.zeros((2, 48), np.float32),
             "behavior_logprobs": np.zeros((2, 48), np.float32),
             "rewards": np.zeros((2, 48), np.float32)}
    staged = tr._stage(batch)
    return {
        "engine_decode": eng._step.lower(eng.params, eng.state, eng._bt_jax,
                                         kv_len_hint=None),
        "engine_admit": eng._admit.lower(
            eng.state, np.zeros((H, T), i32), np.zeros(H, i32),
            np.zeros(H, i32), mask),
        "engine_prefill": eng._prefill.lower(eng.params, eng.state, 0, mask,
                                             eng._bt_jax, offset_hint=None),
        "engine_recompute": eng._recompute.lower(eng.params, eng.state),
        "train_step": tr._step.lower(tr.state, staged, poison=False),
        "trainer_stage_batch": tr._stage.lower(batch),
    }


@pytest.mark.parametrize("name", [
    "engine_decode", "engine_admit", "engine_prefill", "engine_recompute",
    "train_step", "trainer_stage_batch"])
def test_program_compiles_under_its_name(programs, name):
    head = programs[name].as_text().splitlines()[0]
    assert head.startswith(f"module @jit_{name} "), head
