"""Chip smoke run: PipelineRL end to end on TPU at granite-3-2b widths.

    python chip_smoke.py             # one chip: trainer + one engine
    python chip_smoke.py --chips 4   # four chips: mesh trainer, two engines
                                     # on disjoint 2-chip submeshes

The model is granite-3-2b at its published widths (d_model 2048, 32/8
heads, d_head 64, d_ff 8192, vocab 49155) with random weights from
`--seed`, bf16, Pallas kernels compiled (never interpreted) and the fused
trainer loss. Only depth is cut, to what one chip holds for the trainer
state, its non-donated step outputs, remat temporaries and the engine
together (see `N_LAYERS`).

The one-chip phase drives the public `PipelineRL` with one engine and the
streamed weight broadcast for at least `MIN_STEPS` optimizer steps, until
a trained rollout carries tokens of two weight versions (an in-flight
install landed mid-rollout). It then lowers the engine's decode and
prefill steps and the trainer's step at the shapes that ran and fails
unless each holds its Pallas kernel. The four-chip phase runs the
real-mesh pipeline and checks every engine's installed params bitwise
against the trainer's.

The script refuses to run without a TPU. Every failure raises, so the
exit code is non-zero and no result line is printed. The last line of
stdout on success is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import math
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Depth cut (compiled against a described v5e with 16 GiB of HBM): at 4
# layers the model has 444.6M params, and the train step holds 4.14 GiB
# of state (bf16 params + f32 Adam moments), a second 4.14 GiB for its
# outputs (the state is not donated: engines alias the params) and
# 1.71 GiB of remat temporaries at 4x1024 packs. 8x1024 packs need
# 5.3 GiB of temporaries and remat off needs 10.7 GiB, so both are out.
N_LAYERS = 4
SLOTS = 32
MAX_LEN = 512            # multiple of 256: decode and prefill kernels apply
PACK_ROWS, PACK_SEQ = 4, 1024
BATCH = PACK_ROWS * PACK_SEQ // MAX_LEN   # full-length rollouts per step
MIN_STEPS, MAX_STEPS = 3, 12

DECODE_KERNELS = ("_decode_kernel",)
PREFILL_KERNELS = ("_prefill_kernel",)
LOSS_KERNELS = ("_fwd_kernel", "_bwd_dh_kernel", "_bwd_dw_kernel")


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def smoke_config(n_layers: int = N_LAYERS):
    """granite-3-2b at published widths, cut in depth only."""
    from repro.configs.granite_3_2b import config as granite
    return dataclasses.replace(
        granite(), n_layers=n_layers, use_pallas=True,
        pallas_interpret=False, fused_loss=True, remat=True)


def make_trainer_class():
    from repro.core.trainer import Trainer

    class RecordingTrainer(Trainer):
        """Counts trained rollouts whose sampled tokens span two or more
        weight versions, read from the packed batch's own stamps."""

        mixed_rollouts = 0

        def step(self, batch, poison: bool = False):
            import numpy as np
            seg, ver = batch["segment_ids"], batch["weight_versions"]
            sampled = batch["loss_mask"] > 0
            for row in range(seg.shape[0]):
                for s in np.unique(seg[row][seg[row] > 0]):
                    v = ver[row][(seg[row] == s) & sampled[row]]
                    self.mixed_rollouts += int(np.unique(v).size >= 2)
            return super().step(batch, poison=poison)

    return RecordingTrainer


def kernel_names(text: str) -> collections.Counter:
    """Pallas kernels in lowered TPU text, by kernel name."""
    return collections.Counter(re.findall(r'kernel_name = "(\w+)"', text))


def kernel_line(name: str, text: str, expect) -> str:
    calls = text.count("tpu_custom_call")
    names = kernel_names(text)
    missing = [k for k in expect if not names.get(k)]
    check(calls > 0 and not missing,
          f"{name}: Pallas kernel(s) {missing or list(expect)} absent "
          f"(tpu_custom_call={calls}); the jnp fallback ran")
    return f"{name} tpu_custom_call={calls} " + " ".join(
        f"{k}={v}" for k, v in sorted(names.items()))


def check_losses(pipe) -> list:
    losses = [float(r["loss"]) for r in pipe.log]
    check(len(losses) >= MIN_STEPS, f"only {len(losses)} optimizer steps")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(all(not r["bad_step"] for r in pipe.log), "guard dropped a step")
    return losses


def pipeline_parts(cfg, seed: int, n_engines: int):
    import jax
    from repro.core.pipeline import PipelineConfig
    from repro.core.rollout import EngineConfig
    from repro.data.math_task import MathTask
    from repro.models import model as M
    from repro.sharding import tree_values

    params = tree_values(M.init_params(cfg, jax.random.PRNGKey(seed)))
    task = MathTask(max_operand=1000, ops="+-", seed=seed)
    ec = EngineConfig(n_slots=SLOTS, max_len=MAX_LEN, interpret=False)
    pc = PipelineConfig(batch_size=BATCH, n_opt_steps=MIN_STEPS,
                        n_engines=n_engines, pack_rows=PACK_ROWS,
                        pack_seq=PACK_SEQ, broadcast="streamed")
    return params, task, ec, pc


def one_chip_phase(cfg, seed: int, timers) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.pipeline import PipelineRL
    from repro.data.packing import pack
    from repro.kernels.common import default_interpret

    check(default_interpret(None) is False,
          "default_interpret resolves to interpret mode on the chip")
    params, task, ec, pc = pipeline_parts(cfg, seed, n_engines=1)
    trainer = make_trainer_class()(cfg, params)
    pipe = PipelineRL(cfg, params, task, ec, pc, trainer=trainer, seed=seed)

    c0, t0 = timers["compile"], time.perf_counter()
    steps = MIN_STEPS
    pipe.run(steps)
    while trainer.mixed_rollouts == 0 and steps < MAX_STEPS:
        steps += 1
        pipe.run(steps)
    jax.block_until_ready(trainer.state)
    wall = time.perf_counter() - t0
    losses = check_losses(pipe)
    eng = pipe.engine
    check(eng.tokens_generated > 0, "no tokens generated")
    check(trainer.mixed_rollouts > 0,
          f"no trained rollout spans two weight versions in {steps} steps")
    st = pipe.broadcast_stats()["engines"][0]
    print(f"pipeline: steps={trainer.version} wall_s={wall:.3f} "
          f"compile_s={timers['compile'] - c0:.3f} "
          f"tokens_generated={eng.tokens_generated} "
          f"prefill_invocations={eng.prefill_invocations} "
          f"installs={st['updates_applied']} "
          f"mixed_version_rollouts={trainer.mixed_rollouts} "
          f"engine_version={eng.version}", flush=True)
    print("losses: " + " ".join(f"{x:.6f}" for x in losses), flush=True)

    # the engine's and trainer's own jitted steps, at the shapes that ran
    cl = eng._cache_len
    dec = eng._step.lower(eng.params, eng.state, eng._bt_jax,
                          kv_len_hint=cl).as_text()
    pre = eng._prefill.lower(eng.params, eng.state, 0,
                             jnp.ones((ec.n_slots,), bool), eng._bt_jax,
                             offset_hint=0).as_text()
    batch = {k: v for k, v in pack([], PACK_ROWS, PACK_SEQ).items()
             if k not in ("packing_stats", "weight_versions")}
    tl = trainer._step.lower(trainer.state, batch, poison=False)
    print("kernels: " + "; ".join([
        kernel_line("decode_step", dec, DECODE_KERNELS),
        kernel_line("prefill_step", pre, PREFILL_KERNELS),
        kernel_line("train_step", tl.as_text(), LOSS_KERNELS)]), flush=True)
    ma = tl.compile().memory_analysis()
    print(f"train_step memory: args={ma.argument_size_in_bytes} "
          f"outputs={ma.output_size_in_bytes} "
          f"temps={ma.temp_size_in_bytes} bytes", flush=True)

    # warm steady-state facts on the compiled shapes. Each engine step ends
    # by reading `finished` back to the host, which waits for the device.
    if eng.n_active == 0:
        eng.refill()
    n_dec, t0 = 0, time.perf_counter()
    while n_dec < 32 and eng.n_active:
        eng.step(task)
        n_dec += 1
    dec_s = (time.perf_counter() - t0) / max(n_dec, 1)
    real = pack(list(pipe.queue.buf)[:BATCH], PACK_ROWS, PACK_SEQ)
    real.pop("packing_stats")
    t0 = time.perf_counter()
    for _ in range(3):
        trainer.step(real)
    jax.block_until_ready(trainer.state)
    train_s = (time.perf_counter() - t0) / 3
    print(f"steady: decode_step_s={dec_s:.6f} over {n_dec} steps "
          f"({ec.n_slots} slots); train_step_s={train_s:.6f} over 3 steps "
          f"({PACK_ROWS}x{PACK_SEQ} packed tokens)", flush=True)


def four_chip_phase(cfg, seed: int, devices, timers) -> None:
    import jax
    import numpy as np
    from jax.sharding import AxisType
    from repro.core.pipeline import PipelineRL
    from repro.kernels.fused_logprob import vocab_shard_count

    mesh = jax.make_mesh((4,), ("model",), (AxisType.Auto,),
                         devices=devices[:4])
    # GSPMD cannot partition a Mosaic kernel (the compiler asks for a
    # shard_map around it), so the mesh programs take the jnp paths
    cfg = dataclasses.replace(cfg, use_pallas=False)
    print("kernels: off on the mesh (Mosaic kernels are not auto-"
          "partitioned); jnp attention and the blocked jnp fused loss",
          flush=True)
    params, task, ec, pc = pipeline_parts(cfg, seed, n_engines=2)
    pipe = PipelineRL(cfg, params, task, ec, pc, seed=seed, mesh=mesh)
    subs = [set(e.mesh.devices.reshape(-1)) for e in pipe.engines]
    check(all(e.mesh is not mesh for e in pipe.engines),
          "engines fell back to the trainer's shared mesh")
    check(all(len(s) == 2 for s in subs) and not subs[0] & subs[1],
          f"engine submeshes are not disjoint 2-chip sets: {subs}")
    n_vocab = vocab_shard_count(mesh, "model", cfg.vocab_size)
    branch = ("unsharded branch: V does not divide the 4-way model axis"
              if n_vocab == 1 else f"sharded {n_vocab} ways")
    print(f"mesh: trainer on {mesh.devices.size} chips; engines on "
          f"{[sorted(d.id for d in s) for s in subs]}; fused loss over "
          f"V={cfg.vocab_size} takes its {branch}", flush=True)

    c0, t0 = timers["compile"], time.perf_counter()
    pipe.run(MIN_STEPS)
    # let the newest publication finish streaming into both engines
    pipe.loop.run(until=lambda: all(
        e.version == pipe.trainer.version for e in pipe.engines))
    jax.block_until_ready(pipe.trainer.state)
    wall = time.perf_counter() - t0
    losses = check_losses(pipe)
    st = pipe.broadcast_stats()
    check(st["executed"] >= 1, "no streamed publication was executed")
    tp = jax.tree_util.tree_leaves(pipe.trainer.params)
    for i, e in enumerate(pipe.engines):
        check(e.version == pipe.trainer.version,
              f"engine {i} at v{e.version}, trainer at "
              f"v{pipe.trainer.version}")
        for a, b in zip(jax.tree_util.tree_leaves(e.params), tp):
            check(np.array_equal(np.asarray(a), np.asarray(b)),
                  f"engine {i} params differ from the trainer's")
    print(f"pipeline: steps={pipe.trainer.version} wall_s={wall:.3f} "
          f"compile_s={timers['compile'] - c0:.3f} executed_publications="
          f"{st['executed']} exec_seconds={st['exec_seconds']:.6f} "
          f"engine_versions={[e.version for e in pipe.engines]} "
          f"tokens_generated={[e.tokens_generated for e in pipe.engines]}",
          flush=True)
    print("losses: " + " ".join(f"{x:.6f}" for x in losses), flush=True)
    print(f"params: both engines bitwise equal to the trainer at "
          f"v{pipe.trainer.version}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    timers = {"compile": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event: str, seconds: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            timers["compile"] += seconds

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            timers["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            timers["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    cfg = smoke_config()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"model: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads} d_head={cfg.d_head} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype=bf16", flush=True)
    print(f"depth cut: n_layers 40 -> {cfg.n_layers} "
          f"({cfg.param_count() / 1e6:.1f}M params), remat on, packs "
          f"{PACK_ROWS}x{PACK_SEQ}, engine {SLOTS} slots x {MAX_LEN}, "
          f"{BATCH} rollouts per step", flush=True)
    if args.chips == 4:
        four_chip_phase(cfg, args.seed, devices, timers)
    else:
        one_chip_phase(cfg, args.seed, timers)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')} "
          f"bytes_limit={stats.get('bytes_limit', 'n/a')} "
          f"compile_s_total={timers['compile']:.3f} "
          f"compile_cache_hits={timers['cache_hits']} "
          f"compile_cache_misses={timers['cache_misses']}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
