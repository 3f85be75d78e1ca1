"""Plain reference of a dense decoder and of one PipelineRL optimizer step.

Straightforward `jax.numpy` in float32 under `default_matmul_precision
("highest")`: no kernels, no cache, no packing tricks, one packed row or
one rollout at a time so that it fits beside nothing else on the chip.
It imports nothing of the program and takes no array the program made:
its weights come from `weights.make_weights` with the run's seed, its
inputs are the prompts and sampled tokens (and the behaviour logprobs the
RL loss is defined on), and its hyperparameters come from the cell's
files.

`prec="fp8"` is the control: every matrix product takes its operands
through float8 e4m3 with one scale per tensor, the step below the bf16
the configuration states. A sound comparison has to fail it.

Follows the configuration file: RMSNorm, rotary embeddings on split
halves, grouped-query causal attention inside each packed segment, SwiGLU,
untied or tied head, optional value head; the multipliers in the file
(embedding, residual, attention, logits) are applied as stated.
Parameters are stored in the configuration's dtype after each update, as
the configuration states; the arithmetic is float32.
"""
from __future__ import annotations

import functools
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
    """One row. tokens/positions/segment_ids: (S,). Returns logits (S, V)
    (row t scores token t+1) and values (S,) or None."""
    f32 = lambda a: a.astype(jnp.float32)
    eps = c["rms_norm_eps"]
    H, KV, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    S = tokens.shape[0]
    h = f32(w["embed"])[tokens] * c["embedding_multiplier"]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = (i >= j) & (segment_ids[:, None] == segment_ids[None, :])
    for layer in range(c["num_hidden_layers"]):
        lw = lambda k: f32(w[k][layer])
        x = rms_norm(h, lw("norm1"), eps)
        q = rope(mm("sd,dhk->shk", x, lw("wq"), prec), positions,
                 c["rope_theta"])
        k = rope(mm("sd,dhk->shk", x, lw("wk"), prec), positions,
                 c["rope_theta"])
        v = mm("sd,dhk->shk", x, lw("wv"), prec)
        qg = q.reshape(S, KV, H // KV, Dh)
        s = mm("qgrd,kgd->grqk", qg, k, prec) * c["attention_multiplier"]
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = mm("grqk,kgd->qgrd", p, v, prec).reshape(S, H, Dh)
        h = h + c["residual_multiplier"] * mm("shk,hkd->sd", o, lw("wo"),
                                              prec)
        x = rms_norm(h, lw("norm2"), eps)
        g = mm("sd,df->sf", x, lw("w_gate"), prec)
        u = mm("sd,df->sf", x, lw("w_up"), prec)
        h = h + c["residual_multiplier"] * mm(
            "sf,fd->sd", jax.nn.silu(g) * u, lw("w_down"), prec)
    hn = rms_norm(h, f32(w["final_norm"]), eps)
    if c["tie_word_embeddings"]:
        logits = mm("sd,vd->sv", hn, w["embed"], prec)
    else:
        logits = mm("sd,dv->sv", hn, w["lm_head"], prec)
    logits = logits / c["logits_scaling"]
    values = None
    if "value_head" in w:
        values = jnp.einsum("sd,dk->sk", hn, f32(w["value_head"]),
                            precision=HIGHEST)[:, 0]
    return logits, values


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
def _row_grad(spec: tuple, rl_spec: tuple, prec: str):
    c, rl = dict(spec), dict(rl_spec)
    return jax.jit(jax.value_and_grad(
        lambda w, row, n: row_loss(w, row, n, c, rl, prec)))


@functools.lru_cache(maxsize=None)
def _logprob_fn(spec: tuple, prec: str):
    c = dict(spec)

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
                keep_rows: Optional[Sequence[int]] = None) -> dict:
    """Follow the program's first len(batches) optimizer steps from the
    seed's weights. Returns each step's loss, the per-leaf norms of the
    first step's gradient as Adam receives it (after clipping), the
    per-leaf norms of the unclipped first gradient, and the per-leaf norms
    of the parameters' change after the last step. `keep_rows` plants the
    half-batch fault: the other rows are left out and the mean is taken
    over the rest."""
    w0 = W.make_weights(c, seed)
    dtypes = {k: v.dtype for k, v in w0.items()}
    p = {k: v.astype(jnp.float32) for k, v in w0.items()}
    m = {k: jnp.zeros_like(v) for k, v in p.items()}
    v2 = {k: jnp.zeros_like(v) for k, v in p.items()}
    grad_fn = _row_grad(_frozen(c), _frozen(rl), prec)
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
                     pad_to: int, prec: str = "f32") -> List[np.ndarray]:
    """Reference logprob of every token of each rollout (prompt + sampled
    tokens), under the seed's weights. Rollouts are padded to `pad_to`
    (padding sits after the rollout, so causal attention never sees it)."""
    w = W.make_weights(c, seed)
    f = _logprob_fn(_frozen(c), prec)
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
