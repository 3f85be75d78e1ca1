"""Trainer: the π side of PipelineRL (Algorithm 2, Trainer process).

`train_step` is a pure function (pjit-able with the sharding rules); the
`Trainer` class wraps it with weight-version bookkeeping — each optimizer
step bumps `version`, which is what the in-flight weight update ships to
the generation engine.

The step loop is *device-resident* (DESIGN.md §6): packed host batches are
staged onto the device in one jitted transfer (one dispatch for the whole
tree, not one blocking copy per field), and per-step metrics stay on
device — `Trainer.step` returns a `LazyMetrics` view and the host syncs
only when (and if) a value is actually read, in one batched `device_get`
per record instead of one blocking `float()` per metric per step.
"""
from __future__ import annotations

import functools
from collections.abc import Mapping
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.algo import RLConfig, reinforce_loss
from repro.models import model as M
from repro.optim.adam import AdamConfig, AdamState, adam_init, adam_update
from repro.spans import named


class TrainState(NamedTuple):
    params: Any
    opt: AdamState
    version: jax.Array  # == number of optimizer steps taken


def init_train_state(params) -> TrainState:
    return TrainState(params=params, opt=adam_init(params),
                      version=jnp.zeros((), jnp.int32))


def loss_fn(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            rl: RLConfig):
    tokens = batch["tokens"]
    kw: Dict[str, Any] = {}
    if cfg.fused_loss:
        # next-token targets: position t holds tokens[t+1]; the last column
        # is dead (nothing to predict) and masked by loss alignment anyway
        kw["loss_targets"] = jnp.concatenate(
            [tokens[:, 1:], tokens[:, -1:]], axis=1)
    out = M.forward(
        params, tokens, batch["positions"], cfg,
        segment_ids=batch.get("segment_ids"),
        prefix_embeds=batch.get("prefix_embeds"), **kw,
    )
    if "logits" in out:
        outputs = out["logits"]
    else:  # fused path: per-token stats, no (B,S,V) logits exist
        outputs = {"token_logprobs": out["token_logprobs"],
                   "entropy": out["entropy"]}
    loss, metrics = reinforce_loss(outputs, out.get("values"), batch, rl)
    if cfg.n_experts:
        loss = loss + rl.aux_coef * out["aux_loss"]
        metrics["moe_aux"] = out["aux_loss"]
    metrics["loss"] = loss
    return loss, metrics


def train_step(state: TrainState, batch, cfg: ModelConfig, rl: RLConfig,
               adam: AdamConfig, microbatch: int = 1, lr_schedule=None,
               guard: bool = False, poison=None,
               ) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """One optimizer step. microbatch > 1 enables gradient accumulation:
    the global batch is split into `microbatch` chunks processed by a scan,
    dividing activation memory by the same factor (beyond-paper memory
    optimization, see EXPERIMENTS.md §Perf).

    guard=True arms the fused non-finite check (DESIGN.md §10): if the
    global grad norm or the loss is non-finite, the whole update is
    dropped *inside the jitted step* — params/opt/version keep their old
    values via `lax.select`, so a poisoned batch can never write NaN into
    the state — and `metrics["nonfinite"]` reports the skip. The check
    rides on `grad_norm`, which `adam_update` already computes (any
    non-finite gradient leaf makes the global norm non-finite), so the
    healthy path runs the same math and `where(False, old, new)` returns
    `new` bitwise: a guarded healthy run is bit-identical to an
    unguarded one. `poison` (traced bool) replaces the gradients with
    NaN — the §10 `nan_step` fault injection point, inside the step so
    the guard is exercised end to end."""
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    if microbatch <= 1:
        (_, metrics), grads = grad_fn(state.params, batch, cfg, rl)
    else:
        def split(x):
            return x.reshape((microbatch, x.shape[0] // microbatch)
                             + x.shape[1:])

        mb = jax.tree.map(split, batch)
        first = jax.tree.map(lambda x: x[0], mb)
        m_shapes = jax.eval_shape(
            lambda p, c: grad_fn(p, c, cfg, rl)[0][1], state.params, first)
        zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              state.params)
        zero_m = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), m_shapes)

        def acc(carry, chunk):
            g_acc, m_acc = carry
            (_, m), g = grad_fn(state.params, chunk, cfg, rl)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32) / microbatch, g_acc, g)
            m_acc = jax.tree.map(lambda a, b: a + b / microbatch, m_acc, m)
            return (g_acc, m_acc), None

        (grads, metrics), _ = jax.lax.scan(acc, (zero_g, zero_m), mb)
    if guard and poison is not None:
        # fault injection (nan_step): a select, not an add — `g + nan*0`
        # style arithmetic would flip -0.0 grads on the healthy path,
        # while where(False, nan, g) returns g bitwise
        pz = jnp.asarray(poison, bool)
        grads = jax.tree.map(
            lambda g: jnp.where(pz, jnp.full_like(g, jnp.nan), g), grads)
    lr = lr_schedule(state.opt.step) if lr_schedule is not None else None
    new_params, new_opt, gnorm = adam_update(state.params, grads, state.opt,
                                             adam, lr=lr)
    metrics["grad_norm"] = gnorm
    if lr is not None:
        metrics["lr"] = lr
    if guard:
        bad = ~(jnp.isfinite(gnorm) & jnp.isfinite(metrics["loss"]))
        new_params = jax.tree.map(lambda o, n: jnp.where(bad, o, n),
                                  state.params, new_params)
        new_opt = jax.tree.map(lambda o, n: jnp.where(bad, o, n),
                               state.opt, new_opt)
        metrics["nonfinite"] = bad.astype(jnp.float32)
        return TrainState(new_params, new_opt,
                          state.version + jnp.where(bad, 0, 1)), metrics
    return TrainState(new_params, new_opt, state.version + 1), metrics


def make_train_step(cfg: ModelConfig, rl: RLConfig, adam: AdamConfig,
                    donate: bool = True, microbatch: int = 1,
                    lr_schedule=None, guard: bool = False):
    fn = functools.partial(train_step, cfg=cfg, rl=rl, adam=adam,
                           microbatch=microbatch, lr_schedule=lr_schedule,
                           guard=guard)
    return jax.jit(named(fn, "train_step"),
                   donate_argnums=(0,) if donate else ())


class LazyMetrics(Mapping):
    """Device-resident metrics record. Holding one costs no host sync; the
    first key access fetches *all* values in one batched `device_get` and
    caches them as python floats."""

    def __init__(self, dev: Dict[str, jax.Array]):
        self._dev = dev
        self._host: Optional[Dict[str, float]] = None

    def fetch(self) -> Dict[str, float]:
        if self._host is None:
            self._host = {k: float(v)
                          for k, v in jax.device_get(self._dev).items()}
            self._dev = {}
        return self._host

    def peek(self, k: str) -> float:
        """Fetch ONE metric without materializing the record: a single
        tiny scalar transfer, so per-step guard polling (DESIGN.md §10)
        does not force the full batched sync the lazy design avoids."""
        if self._host is not None:
            return self._host[k]
        return float(jax.device_get(self._dev[k]))

    def __getitem__(self, k: str) -> float:
        return self.fetch()[k]

    def __iter__(self) -> Iterator[str]:
        return iter(self._host if self._host is not None else self._dev)

    def __len__(self) -> int:
        return len(self._host if self._host is not None else self._dev)

    def __repr__(self) -> str:
        state = "synced" if self._host is not None else "on-device"
        return f"LazyMetrics({state}: {list(self)})"


# batch fields the train step does not consume (bookkeeping riding along
# in pack() output); dropped before staging so no dead transfers happen
_NON_MODEL_KEYS = ("packing_stats", "weight_versions")
# staleness-contract fields (pack(..., trainer_version=...)): consumed by
# the loss only when a lag mode is armed; dropped otherwise so the "off"
# staging (and therefore the whole step) stays bit-identical to pre-lag
_LAG_KEYS = ("lag", "truncated")


class Trainer:
    """Consumes packed batches, performs optimizer steps, exposes the
    current policy weights + version for in-flight updates."""

    def __init__(self, cfg: ModelConfig, params, rl: RLConfig = RLConfig(),
                 adam: AdamConfig = AdamConfig(), lr_schedule=None,
                 guard: bool = True, mesh=None, rules=None):
        self.cfg, self.rl, self.adam = cfg, rl, adam
        self.state = init_train_state(params)
        self.guard = bool(guard)
        self.nonfinite_steps = 0   # updates dropped by the in-step guard
        # real-mesh placement (DESIGN.md §11): params/opt state live in
        # the FSDP+TP train layout from `state_shardings`; the step runs
        # under `sharding_context` so `constrain` annotations bind, and
        # staged batches land replicated on the mesh (their sharding is
        # decided by GSPMD inside the step)
        self.mesh, self.rules = mesh, rules
        if mesh is not None:
            from repro.launch.steps import abstract_train_state, \
                state_shardings
            ann, _ = abstract_train_state(cfg)
            self.state = jax.device_put(self.state,
                                        state_shardings(ann, mesh, rules))
        # no donation of the state: the generation engine aliases these
        # buffers between in-flight updates (the co-sim shares one device)
        self._step = make_train_step(cfg, rl, adam, donate=False,
                                     lr_schedule=lr_schedule,
                                     guard=self.guard)
        # jitted staging: one dispatch moves the whole packed batch to the
        # device (vs one blocking transfer per field, like PR 1's `_admit`
        # killed the per-array admission copies). The staged copy is
        # trainer-owned, so its buffers free at their last use inside the
        # step; explicit donation would add nothing (XLA donation aliases
        # inputs to *outputs* only, and a consumed batch has no matching
        # output — it would just warn "donated buffers were not usable").
        def trainer_stage_batch(b):
            return b

        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._stage = jax.jit(
                trainer_stage_batch,
                out_shardings=NamedSharding(mesh, PartitionSpec()))
        else:
            self._stage = jax.jit(trainer_stage_batch)
        self.history: List[LazyMetrics] = []

    def _ctx(self):
        if self.mesh is None:
            import contextlib
            return contextlib.nullcontext()
        from repro.shardctx import sharding_context
        return sharding_context(self.mesh, self.rules)

    @property
    def version(self) -> int:
        return int(self.state.version)

    @property
    def params(self):
        return self.state.params

    def step(self, batch, poison: bool = False) -> LazyMetrics:
        """One optimizer step. `batch` may be host numpy (the pack()
        output — staged on device in one jitted transfer) or already
        device-resident (used as-is). Returns a `LazyMetrics` view;
        nothing syncs to host unless a metric value is actually read.
        `poison` (guard mode only) injects NaN gradients inside the step
        — the §10 `nan_step` fault; the guard must catch it."""
        drop = _NON_MODEL_KEYS if self.rl.lag_mode != "off" \
            else _NON_MODEL_KEYS + _LAG_KEYS
        batch = {k: v for k, v in batch.items() if k not in drop}
        with self._ctx():
            if not all(isinstance(v, jax.Array) for v in batch.values()):
                batch = self._stage(batch)
            if self.guard:
                self.state, metrics = self._step(self.state, batch,
                                                 poison=poison)
            else:
                self.state, metrics = self._step(self.state, batch)
        m = LazyMetrics(metrics)
        self.history.append(m)
        return m

    def last_nonfinite(self) -> bool:
        """Guard verdict of the newest step — did the fused non-finite
        check drop the update? One scalar `peek`, not a full sync."""
        if not self.guard or not self.history:
            return False
        bad = self.history[-1].peek("nonfinite") > 0.0
        if bad:
            self.nonfinite_steps += 1
        return bad

    # ---- crash-restart checkpointing (DESIGN.md §8) -------------------
    def save(self, path: str) -> str:
        """Atomic checkpoint of the full TrainState (params + optimizer
        moments + version) — everything a crash-restart needs to resume
        with a bit-identical next optimizer step."""
        from repro.checkpoint import checkpoint
        checkpoint.save(path, self.state)
        return checkpoint._norm(path)

    def restore(self, path: str) -> int:
        """Restore params/opt-state/version from `path`; returns the
        restored version. The compiled step function is untouched (same
        cfg), so the next `step` after a restore is bit-identical to the
        step an uninterrupted run would have taken on the same batch."""
        from repro.checkpoint import checkpoint
        loaded = checkpoint.load(path, self.state)
        self.state = jax.tree.map(jnp.asarray, loaded)
        if self.mesh is not None:
            from repro.launch.steps import abstract_train_state, \
                state_shardings
            ann, _ = abstract_train_state(self.cfg)
            self.state = jax.device_put(
                self.state, state_shardings(ann, self.mesh, self.rules))
        return self.version

    def fetch_metrics(self) -> List[Dict[str, float]]:
        """Materialize the whole history in one batched device_get (the
        on-demand sync point of the device-resident loop)."""
        pending = [m for m in self.history if m._host is None]
        if pending:
            fetched = jax.device_get([m._dev for m in pending])
            for m, h in zip(pending, fetched):
                m._host = {k: float(v) for k, v in h.items()}
                m._dev = {}
        return [m.fetch() for m in self.history]
