"""One run of one cell: build, warm up, measure a window, check, report.

Everything a cell needs is found by name from files: its entry in
`BENCHMARK.json`, `workloads/<cell>.json` (engine, pipeline, optimizer
settings, an optional mesh, and the limits of the correctness check),
the configuration file the entry names and its architecture module
`arch/<arch>.py`, `traffic/<mix>.json`, and one reader per per-layer
metric in `metrics/<metric>.py`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import json
import os
import shutil
import time
from typing import Any, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

import flops as FL  # noqa: E402
import profile_reduce as PR  # noqa: E402
import traffic_gen  # noqa: E402
import weights as W  # noqa: E402

ROW_KEYS = ("tokens", "positions", "segment_ids", "loss_mask",
            "behavior_logprobs", "rewards", "weight_versions")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    workload: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, bench_path: Optional[str] = None) -> Cell:
    """Find a cell and all its parts from files alone."""
    bench = _json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])

    def mine(m, moves_reported):
        if "workloads" in m:
            return name in m["workloads"]
        return moves_reported(m)

    e2e = [m for m in bench["end_to_end"] if mine(m, lambda m: True)]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if mine(m, lambda m: m["moves"] in names)]
    return Cell(name, int(entry["chips"]), _json(os.path.join(ROOT, cfg["file"])),
                traffic_gen.load_mix(entry["traffic"]),
                _json(os.path.join(HERE, "workloads", name + ".json")),
                e2e, per)


def load_reader(metric: str):
    return W.load_file(os.path.join(HERE, "metrics", metric + ".py"),
                       "metric", metric).read


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(PR.SPAN_PREFIX + name)


class Recorder:
    """Rides on the trainer: keeps what the correctness check reads from
    the first steps (their batches, the first gradient from Adam's state,
    the parameters' change after three steps) and counts trained work."""

    def __init__(self, c: dict, seed: int, n_keep: int, b1: float,
                 mesh=None):
        self.c, self.seed, self.n_keep, self.b1 = c, seed, n_keep, b1
        self.mesh = mesh
        self.steps = 0
        self.batches: List[Dict[str, np.ndarray]] = []
        self.metrics: List[Any] = []
        self.grad_norms: Optional[Dict[str, float]] = None
        self.change_norms: Optional[Dict[str, float]] = None
        self.loss_tokens = 0.0
        self.seq_tokens = 0
        self.ctx_sum = 0
        self.rows = 0
        self.annotate = False

    def before(self, trainer, batch) -> None:
        if self.steps == 1:
            m = W.arch(self.c).from_program_tree(trainer.state.opt.m)
            self.grad_norms = {k: v / (1.0 - self.b1)
                               for k, v in _host(_norm_fns()[0](m)).items()}
        elif self.steps == 3:
            p0 = W.make_weights(self.c, self.seed, self.mesh)
            p3 = W.arch(self.c).from_program_tree(trainer.state.params)
            self.change_norms = _host(_norm_fns()[1](p3, p0))
            del p0
        if len(self.batches) < self.n_keep:
            self.batches.append({k: np.array(batch[k]) for k in ROW_KEYS})
        self.loss_tokens += float(batch["loss_mask"].sum())
        seg = batch["segment_ids"]
        for row in seg:
            lens = np.bincount(row)[1:]
            self.seq_tokens += int(lens.sum())
            self.ctx_sum += FL.causal_context_sum(int(x) for x in lens)
        self.rows += seg.shape[0]

    def after(self, metrics) -> None:
        if len(self.metrics) < 3:
            self.metrics.append(metrics)
        self.steps += 1


@functools.lru_cache(maxsize=None)
def _norm_fns():
    """Jitted per-leaf norms (and norms of differences) of a weight tree."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(t):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                for k, v in t.items()}

    @jax.jit
    def diff_norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            a[k].astype(jnp.float32) - b[k].astype(jnp.float32))))
            for k in a}

    return norms, diff_norms


def _host(d) -> Dict[str, float]:
    import jax
    return {k: float(v) for k, v in jax.device_get(d).items()}


def make_trainer_class(rec: Recorder):
    from repro.core.trainer import Trainer

    class BenchTrainer(Trainer):
        def step(self, batch, poison: bool = False):
            rec.before(self, batch)
            with (annotate("train_step") if rec.annotate
                  else contextlib.nullcontext()):
                m = super().step(batch, poison=poison)
            rec.after(m)
            return m

    return BenchTrainer


@dataclasses.dataclass
class Counters:
    decode_steps: int = 0
    decode_tokens: int = 0
    decode_ctx: int = 0


def instrument(pipe, counters: Counters) -> None:
    """Host spans around the calls into each layer, from the benchmark's
    side, and the decode counts the kernel's and the step's FLOPs need."""
    loop_step = pipe.loop.step

    def event():
        with annotate("event"):
            return loop_step()

    pipe.loop.step = event
    for eng in pipe.engines:
        def decode(task=None, now=0.0, _eng=eng, _step=eng.step):
            act = _eng._host_active
            counters.decode_steps += 1
            counters.decode_tokens += int(act.sum())
            counters.decode_ctx += int((_eng._host_ncached[act] + 1).sum())
            with annotate("decode"):
                return _step(task, now=now)

        def admit(now=0.0, _f=eng.refill):
            with annotate("admit"):
                return _f(now)

        def install(token=None, _f=eng.stream_weight_chunk):
            with annotate("install"):
                return _f(token=token)

        eng.step, eng.refill, eng.stream_weight_chunk = decode, admit, install


def build(cell: Cell, seed: int, interpret: Optional[bool]):
    """The public `PipelineRL` for a cell, with the benchmark's seeded
    weights, prompt source and recording trainer. A workload with a
    `mesh` gets that mesh over its chips: the trainer's state is split
    over it, the engines take disjoint submeshes of it, and publications
    run through the program's `MeshBroadcastExecutor`."""
    import jax
    from repro.core.pipeline import PipelineConfig, PipelineRL
    from repro.core.algo import RLConfig
    from repro.core.rollout import EngineConfig
    from repro.optim.adam import AdamConfig
    from repro.models import model as M
    from repro.sharding import tree_values

    c, wl = cell.config, cell.workload
    arch = W.arch(c)
    cfg = arch.program_config(c, interpret)
    mesh = W.cell_mesh(wl, cell.chips)
    if mesh is not None and cfg.use_pallas:
        raise ValueError(f"{c['name']}: a cell on a mesh needs use_pallas "
                         "false (GSPMD cannot partition a Mosaic kernel)")
    params = arch.to_program_tree(W.make_weights(c, seed, mesh), c)
    want = jax.tree.map(lambda a: (a.shape, a.dtype),
                        tree_values(M.init_params(cfg, abstract=True)))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError("benchmark weights do not match the program's "
                         "parameter tree")
    traffic = traffic_gen.Traffic(cell.mix, seed)
    rec = Recorder(c, seed, max(wl["warmup_steps"], 3), wl["optimizer"]["b1"],
                   mesh)
    trainer = make_trainer_class(rec)(
        cfg, params, rl=RLConfig(**wl["rl"]), adam=AdamConfig(**wl["optimizer"]),
        mesh=mesh)
    ec = EngineConfig(max_len=cell.mix["max_len"],
                      temperature=cell.mix["temperature"],
                      eos_id=traffic_gen.eos_id(cell.mix), interpret=interpret,
                      **wl["engine"])
    pc = PipelineConfig(n_opt_steps=wl["warmup_steps"], **wl["pipeline"])
    pipe = PipelineRL(cfg, params, traffic, ec, pc, trainer=trainer,
                      seed=W.seed31(seed, 2), prompt_source=traffic.source,
                      mesh=mesh)
    return pipe, rec, traffic


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def _snapshot(pipe, rec: Recorder, traffic, counters: Counters) -> dict:
    bs = pipe.broadcast_stats()
    return {
        "loss_tokens": rec.loss_tokens, "steps": rec.steps,
        "seq_tokens": rec.seq_tokens, "ctx_sum": rec.ctx_sum,
        "rows": rec.rows,
        "lag_hist": dict(pipe.trainer_stage.lag_hist),
        "sampled": sum(e.tokens_generated for e in pipe.engines),
        "prefill_tokens": sum(e.prefill_tokens for e in pipe.engines),
        "done": len(traffic.done),
        "executed": bs["executed"], "exec_seconds": bs["exec_seconds"],
        "lost": sum(a.rollouts_lost for a in pipe.actors)
                + sum(e.prompts_rejected for e in pipe.engines),
        **dataclasses.asdict(counters),
    }


def measure(pipe, rec, traffic, counters, seconds: float,
            trace_dir: Optional[str]) -> dict:
    import jax
    before = _snapshot(pipe, rec, traffic, counters)
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with (annotate("window") if trace_dir else contextlib.nullcontext()):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        pipe.loop.run(until=lambda: time.perf_counter() >= deadline)
        jax.block_until_ready(pipe.trainer.state.params)
        t1 = time.perf_counter()
    if trace_dir:
        jax.profiler.stop_trace()
    after = _snapshot(pipe, rec, traffic, counters)
    d = {k: after[k] - before[k] for k in after if k != "lag_hist"}
    hist = {k: v - before["lag_hist"].get(k, 0)
            for k, v in after["lag_hist"].items()}
    d["lag_hist"] = {k: v for k, v in hist.items() if v}
    d["latencies"] = [p.done_at - p.drawn_at
                      for p in traffic.done[before["done"]:]]
    d["t0"], d["t1"], d["window_s"] = t0, t1, t1 - t0
    return d


def end_to_end(d: dict, setup_s: float) -> Dict[str, float]:
    return {
        "trained_tokens_per_s": d["loss_tokens"] / d["window_s"],
        "sampled_tokens_per_s": d["sampled"] / d["window_s"],
        "rollout_p90_s": (float(np.percentile(d["latencies"], 90))
                          if d["latencies"] else float("nan")),
        "setup_s": setup_s,
    }


@dataclasses.dataclass
class Ctx:
    """What a per-layer reader may read."""
    config: dict
    workload: dict
    trace: Optional[PR.Trace]
    window: dict
    peak: dict
    chips: int


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def warm_up(pipe, cell: Cell, max_events: int = 200_000) -> None:
    """Run the pipeline through its first optimizer steps: every decode
    program of the window has compiled once the first wave is done. Then
    wait (boundedly: an install that never lands is the check's to catch)
    for the first publication to be installed."""
    pipe.run(cell.workload["warmup_steps"])
    try:
        pipe.loop.run(until=lambda: all(e.version >= 1 for e in pipe.engines),
                      max_events=max_events)
    except RuntimeError:
        pass


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        interpret: Optional[bool] = None, trace_dir: Optional[str] = None,
        keep_trace: bool = False, log=print) -> dict:
    import jax
    import checks
    from peaks import peaks

    devices = jax.devices()[:cell.chips]
    dev = devices[0]
    peak = peaks(dev.device_kind) if dev.platform == "tpu" else {}
    compiles = {"n": 0}

    def on_duration(event: str, _seconds: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    pipe, rec, traffic = build(cell, seed, interpret)
    counters = Counters()
    if trace:
        instrument(pipe, counters)
        rec.annotate = True
    warm_up(pipe, cell)
    jax.block_until_ready(pipe.trainer.state.params)
    c_setup = compiles["n"]
    setup_s = time.perf_counter() - t_start

    tdir = None
    if trace:
        tdir = trace_dir or os.path.join(ROOT, ".bench_traces", cell.name)
        shutil.rmtree(tdir, ignore_errors=True)
    d = measure(pipe, rec, traffic, counters, seconds, tdir)
    c_window = compiles["n"] - c_setup
    mem = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for x in devices)
    log(f"window: {d['window_s']:.6f} s, compiles in window {c_window}, "
        f"compiles in set-up {c_setup}, optimizer steps {d['steps']}, "
        f"rollouts {d['done']}, sampled tokens {d['sampled']}")

    metrics: Dict[str, float] = {}
    result: Dict[str, Any] = {}
    if not trace:
        e2e = end_to_end(d, setup_s)
        metrics = {m["name"]: e2e[m["name"]] for m in cell.end_to_end}
    else:
        path = PR.find_xplane(tdir)
        tr = PR.load(path, (0.0, 0.0))
        win = [s for s in tr.spans if s.name == PR.SPAN_PREFIX + "window"]
        tr.span = (win[0].start, win[0].end) if win else (
            min(o.start for ops in tr.ops.values() for o in ops),
            max(o.end for ops in tr.ops.values() for o in ops))
        ctx = Ctx(cell.config, cell.workload, tr, d, peak, cell.chips)
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = v
        used = [k for k in tr.devices if tr.ops[k]]
        busy = [PR.busy_seconds(tr.ops[k], tr.span) for k in used]
        allops = [o for k in used for o in tr.ops[k]]
        gaps = [g for k in used
                for g in PR.top_gaps(tr.ops[k], tr.spans, tr.span)]
        result["breakdown"] = {
            "device_ops": PR.top_ops(allops),
            "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10]}
        roles = PR.module_roles(tr)
        for role in PR.ROLES:
            log(f"programs in {role}: "
                f"{sorted({m.name for m in roles[role]})}")
        for role in ("decode", "train_step"):
            names = sorted({PR.op_label(o.name) for o in PR.kernels(tr, role)})
            log(f"kernels in {role} programs: {names}")
        result["busy_s"] = float(np.mean(busy)) if busy else 0.0
        result["trace_window_s"] = tr.span[1] - tr.span[0]
        if not keep_trace:
            shutil.rmtree(tdir, ignore_errors=True)

    # ---- correctness: after the window, the program's state freed ----
    readings = checks.program_readings(pipe, rec, cell, seed)
    attempted, failed = d["done"], d["lost"]
    del pipe, rec
    gc.collect()
    numbers = checks.compare(readings, cell, seed)
    limits = cell.workload.get("limits", {})
    chk = {k: {"value": v, "limit": limits.get(k)} for k, v in numbers.items()}
    correct = checks.verdict(chk)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    if trace:
        device["busy_s"] = result.pop("busy_s")
        device["window_s"] = result.pop("trace_window_s")
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {m["name"]: {"value": metrics[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end + cell.per_layer
                       if m["name"] in metrics},
           "device": device}
    if "breakdown" in result:
        out["breakdown"] = result["breakdown"]
    out["checks"] = chk
    return out
