"""The plain dense decoder block: RMSNorm, grouped-query attention with
rotary embeddings, SwiGLU, an untied or tied head and an optional value
head. Loaded by `weights.arch` for a configuration whose `arch` is
"dense".

What an architecture module gives the shared code (`weights.py`,
`reference.py`, `flops.py`, `harness.py`):

- program_config(c, interpret): the program's ModelConfig, or a refusal
  where the program cannot run the file as stated;
- shapes(c): leaf name -> (shape, init rule) of the benchmark's weights;
- to_program_tree(w, c), from_program_tree(t): the benchmark's flat
  names <-> the program's parameter tree;
- forward(w, tokens, positions, segment_ids, c, prec): the plain float32
  reference of one row, built from `reference.mm`, `rms_norm`, `rope`;
- matmul_params(c), attn_flops_per_token(c, context): the counts of
  `flops.py`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import reference as R
import weights as W


def program_config(c: dict, interpret):
    """The program's ModelConfig for a configuration file. Refuses a file
    the program cannot run as stated."""
    from repro.configs.base import ModelConfig
    plain = (c["embedding_multiplier"] == 1.0 and c["residual_multiplier"]
             == 1.0 and c["logits_scaling"] == 1.0
             and abs(c["attention_multiplier"] * c["head_dim"] ** 0.5 - 1)
             < 1e-9 and c["hidden_act"] == "silu" and c["arch"] == "dense")
    if not plain:
        raise ValueError(f"{c['name']}: the program runs only the plain "
                         "dense block (no Granite multipliers)")
    prog = c["program"]
    return ModelConfig(
        name=c["name"], arch_type="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        use_value_head=bool(c.get("value_head")),
        dtype=W.DTYPES[c["dtype"]], use_pallas=prog["use_pallas"],
        pallas_interpret=interpret if prog["use_pallas"] else None,
        fused_loss=prog["fused_loss"], remat=prog["remat"],
        source=c["source"])


def shapes(c: dict):
    """Leaf name -> (shape, kind) of a dense decoder; kind is the init rule:
    "normal", "out" (normal scaled for the residual), "ones", "zeros"."""
    d, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    H, KV, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    F = c["intermediate_size"]
    out = {
        "embed": ((V, d), "normal"),
        "final_norm": ((d,), "ones"),
        "norm1": ((L, d), "ones"),
        "wq": ((L, d, H, Dh), "normal"),
        "wk": ((L, d, KV, Dh), "normal"),
        "wv": ((L, d, KV, Dh), "normal"),
        "wo": ((L, H, Dh, d), "out"),
        "norm2": ((L, d), "ones"),
        "w_gate": ((L, d, F), "normal"),
        "w_up": ((L, d, F), "normal"),
        "w_down": ((L, F, d), "out"),
    }
    if not c["tie_word_embeddings"]:
        out["lm_head"] = ((d, V), "normal")
    if c.get("value_head"):
        out["value_head"] = ((d, 1), "zeros")
    return out


def to_program_tree(w: dict, c: dict) -> dict:
    """The benchmark's flat naming -> the program's parameter tree."""
    tree = {
        "embed": w["embed"],
        "final_norm": w["final_norm"],
        "groups": [{
            "norm1": w["norm1"],
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"]},
            "norm2": w["norm2"],
            "ffn": {"gate": w["w_gate"], "up": w["w_up"],
                    "down": w["w_down"]},
        }],
    }
    if "lm_head" in w:
        tree["lm_head"] = w["lm_head"]
    if "value_head" in w:
        tree["value_head"] = w["value_head"]
    return tree


def from_program_tree(t: dict) -> dict:
    """Inverse of `to_program_tree` (for reading the program's state back
    under the benchmark's names)."""
    g = t["groups"][0]
    w = {"embed": t["embed"], "final_norm": t["final_norm"],
         "norm1": g["norm1"], "norm2": g["norm2"],
         "wq": g["attn"]["wq"], "wk": g["attn"]["wk"],
         "wv": g["attn"]["wv"], "wo": g["attn"]["wo"],
         "w_gate": g["ffn"]["gate"], "w_up": g["ffn"]["up"],
         "w_down": g["ffn"]["down"]}
    for k in ("lm_head", "value_head"):
        if k in t:
            w[k] = t[k]
    return w


def forward(w, tokens, positions, segment_ids, c: dict, prec: str):
    """One row. tokens/positions/segment_ids: (S,). Returns logits (S, V)
    (row t scores token t+1) and values (S,) or None."""
    mm, rms_norm, rope = R.mm, R.rms_norm, R.rope
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
                            precision=R.HIGHEST)[:, 0]
    return logits, values


def matmul_params(c: dict) -> int:
    """Weights that take part in a matrix product per token (the input
    embedding is a lookup and is left out; the value head is one column)."""
    d, F, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    H, KV, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    per_layer = d * H * Dh * 2 + d * KV * Dh * 2 + 3 * d * F
    head = d * V + (d if c.get("value_head") else 0)
    return c["num_hidden_layers"] * per_layer + head


def attn_flops_per_token(c: dict, context: int) -> int:
    """Scores and weighted values of one query against `context` keys,
    over all layers (forward)."""
    return (4 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * context)
