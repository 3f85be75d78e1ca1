"""Seeded weights for a configuration file, made on the device in one jitted
call, in the dtype the configuration serves them in.

The weights belong to the benchmark, not to the program: the reference
(`reference.py`) makes the same tree again from the same seed, so it takes
nothing the program produced. `to_program_tree` hands the program the
layout its model code reads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def seed_key(seed: int, stream: int = 0):
    """A threefry key from any whole-number seed (64 bits and more)."""
    state = np.random.SeedSequence([int(seed) % (1 << 64), stream])
    return jax.random.wrap_key_data(state.generate_state(2).astype(np.uint32))


def seed31(seed: int, stream: int = 0) -> int:
    """A non-negative 31-bit integer drawn from the seed (for APIs that
    take a small int seed)."""
    state = np.random.SeedSequence([int(seed) % (1 << 64), stream])
    return int(state.generate_state(1)[0] & 0x7FFFFFFF)


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


def leaf_dtype(c: dict, name: str):
    # the value head is float32 in the program whatever the model dtype
    return jnp.float32 if name == "value_head" else DTYPES[c["dtype"]]


@functools.lru_cache(maxsize=None)
def _maker(spec: tuple):
    c = dict(spec)

    @jax.jit
    def make(key):
        std = c["init_std"]
        out_std = std / np.sqrt(2 * c["num_hidden_layers"])
        tree = {}
        for i, (name, (shape, kind)) in enumerate(sorted(shapes(c).items())):
            dt = leaf_dtype(c, name)
            if kind == "ones":
                tree[name] = jnp.ones(shape, dt)
            elif kind == "zeros":
                tree[name] = jnp.zeros(shape, dt)
            else:
                s = std if kind == "normal" else out_std
                z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
                tree[name] = (z * s).astype(dt)
        return tree

    return make


def _spec(c: dict) -> tuple:
    keys = ("hidden_size", "num_hidden_layers", "vocab_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "tie_word_embeddings", "value_head",
            "dtype", "init_std")
    return tuple((k, c.get(k)) for k in keys)


def make_weights(c: dict, seed: int):
    """The configuration's weights from the seed: one jitted call on the
    default device."""
    return _maker(_spec(c))(seed_key(seed, 1))


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
