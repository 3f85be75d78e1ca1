"""Plain reference of the configuration's decoder and of one PipelineRL
optimizer step.

Straightforward `jax.numpy` in float32 under `default_matmul_precision
("highest")`: no kernels, no cache, no packing tricks, one packed row or
one rollout at a time so that it fits beside nothing else on the chip.
It imports nothing of the program and takes no array the program made:
its weights come from `weights.make_weights` with the run's seed, its
inputs are the prompts and sampled tokens (and the behaviour logprobs the
RL loss is defined on), and its hyperparameters come from the cell's
files. On a cell with a mesh the same code runs with its arrays split
over the mesh (`weights.leaf_sharding`): the float32 weights, gradients
and Adam moments of a deeper model do not fit one chip.

`prec="fp8"` is the control: every matrix product takes its operands
through float8 e4m3 with one scale per tensor, the step below the bf16
the configuration states. A sound comparison has to fail it.

The block itself is the architecture module's `forward` (for "dense":
RMSNorm, rotary embeddings on split halves, grouped-query causal
attention inside each packed segment, SwiGLU, untied or tied head,
optional value head; the multipliers in the file are applied as stated),
built from `mm`, `rms_norm` and `rope` here. Parameters are stored in
the configuration's dtype after each update, as the configuration
states; the arithmetic is float32.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(x):
    """float8 e4m3 with a per-tensor scale; gradients pass straight."""
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / F8_MAX)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def mm(eq: str, a, b, prec: str):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if prec == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale


def rope(x, positions, theta):
    """x: (S, heads, d); rotate the two halves of each head."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward(w, tokens, positions, segment_ids, c: dict, prec: str):
    """One row through the configuration's architecture (`arch/<arch>.py`):
    logits (S, V) (row t scores token t+1) and values (S,) or None."""
    return W.arch(c).forward(w, tokens, positions, segment_ids, c, prec)


def token_logprobs(logits, tokens):
    """Entry t: logprob of token t under the logits of position t-1
    (entry 0 is 0), the program's alignment."""
    lp = jax.nn.log_softmax(logits, axis=-1)
    nxt = jnp.take_along_axis(lp[:-1], tokens[1:, None], axis=-1)[:, 0]
    return jnp.concatenate([jnp.zeros((1,), jnp.float32), nxt])


def row_loss(w, row: Dict[str, jax.Array], n_total, c: dict, rl: dict,
             prec: str):
    """This row's share of the truncated-IS REINFORCE loss with the value
    baseline (paper Eq. 5): sums over the row divided by the batch's
    loss-bearing token count."""
    logits, values = forward(w, row["tokens"], row["positions"],
                             row["segment_ids"], c, prec)
    cur = token_logprobs(logits, row["tokens"])
    mask, rew = row["loss_mask"], row["rewards"]
    ratio = jnp.exp(jnp.where(mask > 0, cur - row["behavior_logprobs"], 0.0))
    clamped = jax.lax.stop_gradient(jnp.minimum(ratio, rl["is_clamp"]))
    base = values if values is not None else jnp.zeros_like(rew)
    adv = jax.lax.stop_gradient(rew - base)
    pg = -jnp.sum(clamped * adv * cur * mask) / n_total
    loss = pg
    if values is not None:
        loss = loss + rl["value_coef"] * jnp.sum(
            jnp.square(rew - values) * mask) / n_total
    return loss


@functools.lru_cache(maxsize=None)
def _row_grad(spec: str, rl_spec: tuple, prec: str):
    c, rl = json.loads(spec), dict(rl_spec)
    return jax.jit(jax.value_and_grad(
        lambda w, row, n: row_loss(w, row, n, c, rl, prec)))


@functools.lru_cache(maxsize=None)
def _logprob_fn(spec: str, prec: str):
    c = json.loads(spec)

    @jax.jit
    def f(w, tokens, positions, segment_ids):
        logits, _ = forward(w, tokens, positions, segment_ids, c, prec)
        return token_logprobs(logits, tokens)

    return f


def _frozen(d: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in d.items()
                        if isinstance(v, (int, float, str, bool))))


ROW_KEYS = ("tokens", "positions", "segment_ids", "loss_mask",
            "behavior_logprobs", "rewards")


def leaf_norms(tree) -> Dict[str, float]:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def train_steps(c: dict, seed: int, batches: Sequence[Dict[str, np.ndarray]],
                opt: dict, rl: dict, prec: str = "f32",
                keep_rows: Optional[Sequence[int]] = None,
                mesh=None) -> dict:
    """Follow the program's first len(batches) optimizer steps from the
    seed's weights. Returns each step's loss, the per-leaf norms of the
    first step's gradient as Adam receives it (after clipping), the
    per-leaf norms of the unclipped first gradient, and the per-leaf norms
    of the parameters' change after the last step. `keep_rows` plants the
    half-batch fault: the other rows are left out and the mean is taken
    over the rest. With a mesh every array is split over it."""
    w0 = W.make_weights(c, seed, mesh)
    dtypes = {k: v.dtype for k, v in w0.items()}
    p = {k: v.astype(jnp.float32) for k, v in w0.items()}
    m = {k: jnp.zeros_like(v) for k, v in p.items()}
    v2 = {k: jnp.zeros_like(v) for k, v in p.items()}
    grad_fn = _row_grad(W.frozen(c), _frozen(rl), prec)
    out = {"losses": [], "grad_norms": None, "raw_grad_norms": None}
    with jax.default_matmul_precision("highest"):
        for step, batch in enumerate(batches, start=1):
            rows = range(batch["tokens"].shape[0])
            if keep_rows is not None:
                rows = [r for r in rows if r in set(keep_rows)]
            n_total = max(float(sum(batch["loss_mask"][r].sum()
                                    for r in rows)), 1.0)
            loss, g = 0.0, {k: jnp.zeros_like(v) for k, v in p.items()}
            for r in rows:
                row = {k: jnp.asarray(batch[k][r]) for k in ROW_KEYS}
                lr_, gr = grad_fn(p, row, jnp.float32(n_total))
                loss += float(lr_)
                g = {k: g[k] + gr[k] for k in g}
            out["losses"].append(loss)
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
            if step == 1:
                out["raw_grad_norms"] = leaf_norms(g)
            if opt["grad_clip"] > 0:
                scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
                g = {k: x * scale for k, x in g.items()}
            if step == 1:
                out["grad_norms"] = leaf_norms(g)
            b1c = 1.0 - opt["b1"] ** step
            b2c = 1.0 - opt["b2"] ** step
            for k in p:
                m[k] = opt["b1"] * m[k] + (1 - opt["b1"]) * g[k]
                v2[k] = opt["b2"] * v2[k] + (1 - opt["b2"]) * jnp.square(g[k])
                delta = (m[k] / b1c) / (jnp.sqrt(v2[k] / b2c) + opt["eps"])
                # stored in the configuration's dtype, as the program does
                p[k] = (p[k] - opt["lr"] * delta).astype(dtypes[k]).astype(
                    jnp.float32)
    out["change_norms"] = leaf_norms(
        {k: p[k] - w0[k].astype(jnp.float32) for k in p})
    return out


def rollout_logprobs(c: dict, seed: int, rollouts: List[Dict[str, np.ndarray]],
                     pad_to: int, prec: str = "f32",
                     mesh=None) -> List[np.ndarray]:
    """Reference logprob of every token of each rollout (prompt + sampled
    tokens), under the seed's weights. Rollouts are padded to `pad_to`
    (padding sits after the rollout, so causal attention never sees it)."""
    w = W.make_weights(c, seed, mesh)
    f = _logprob_fn(W.frozen(c), prec)
    out = []
    with jax.default_matmul_precision("highest"):
        for r in rollouts:
            n = len(r["tokens"])
            tok = np.zeros(pad_to, np.int32)
            tok[:n] = r["tokens"]
            pos = np.arange(pad_to, dtype=np.int32)
            seg = np.ones(pad_to, np.int32)
            out.append(np.asarray(f(w, tok, pos, seg))[:n])
    return out
