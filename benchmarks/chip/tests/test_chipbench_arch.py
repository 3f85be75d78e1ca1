"""The architecture comes from a file: `arch/<arch>.py` is found by the
configuration's `arch` key, the dense module gives what the harness gave
before it moved there, and a new architecture needs only new files."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import tiny  # noqa: F401  (puts the benchmark and the program on sys.path)
import flops as FL
import harness
import reference as R
import weights as W

BENCH = tiny.BENCH
ROOT = tiny.ROOT
SEED = 2 ** 31 + 17


def test_an_unknown_arch_names_the_missing_module():
    with pytest.raises(FileNotFoundError, match=r"arch/no_such_block\.py"):
        W.arch(dict(tiny.CONFIG, arch="no_such_block"))
    cell = tiny.cell()
    cell.config["arch"] = "no_such_block"
    with pytest.raises(FileNotFoundError, match="no_such_block"):
        harness.build(cell, SEED, interpret=None)


def test_dense_module_gives_the_weights_logits_and_counts_of_before():
    """Recorded from the harness before the dense block moved into
    arch/dense.py (same seed, same CPU build of XLA)."""
    c = tiny.CONFIG
    w = W.make_weights(c, SEED)
    h = hashlib.sha256()
    for k in sorted(w):
        a = np.asarray(w[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == ("d423b1f42f293f090c64fe403157ab31"
                             "d528053f6fe82228557fe0db522e9bd4")
    tok = np.arange(12, dtype=np.int32) % c["vocab_size"] + 3
    pos = np.arange(12, dtype=np.int32)
    seg = np.ones(12, np.int32)
    seg[7:] = 2
    import jax
    with jax.default_matmul_precision("highest"):
        lg, _ = R.forward(w, tok, pos, seg, c, "f32")
    lg = np.asarray(lg)
    np.testing.assert_allclose(lg[3, :6], [
        0.24901747703552246, -0.09846425801515579, -0.22233562171459198,
        0.09368361532688141, -0.03573835268616676, 0.033086903393268585],
        rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(lg[11, 100:104], [
        -0.08390197902917862, 0.10542382299900055, -0.05747561529278755,
        -0.15341629087924957], rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(lg[0, 500:504], [
        0.00849930290132761, 0.16106753051280975, -0.17737483978271484,
        0.28940534591674805], rtol=1e-5, atol=1e-7)
    assert float(lg.sum()) == pytest.approx(-4.341217994689941, rel=1e-4)
    assert FL.matmul_params(c) == 106560
    assert FL.attn_flops_per_token(c, 10) == 5120
    assert FL.forward_flops(c, 7, 30) == 1507200
    assert FL.decode_kernel_cost(c, 100, 3) == (51200, 27136)
    assert FL.fused_loss_cost(c, 64) == (12582912, 147456)


# A parallel block (attention and MLP read one normed input, as in GPT-J):
# a block the program does not run, so its program_config refuses.
PARALLEL = '''
"""A parallel attention + MLP block, for the layout test."""
import jax
import jax.numpy as jnp

import reference as R
import weights as W

DENSE = W.load_arch("dense")
shapes = DENSE.shapes
to_program_tree = DENSE.to_program_tree
from_program_tree = DENSE.from_program_tree


def program_config(c, interpret):
    raise ValueError("parallel: the program runs no parallel block")


def forward(w, tokens, positions, segment_ids, c, prec):
    f32 = lambda a: a.astype(jnp.float32)
    H, KV, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    S = tokens.shape[0]
    h = f32(w["embed"])[tokens]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = (i >= j) & (segment_ids[:, None] == segment_ids[None, :])
    for layer in range(c["num_hidden_layers"]):
        lw = lambda k: f32(w[k][layer])
        x = R.rms_norm(h, lw("norm1"), c["rms_norm_eps"])
        q = R.rope(R.mm("sd,dhk->shk", x, lw("wq"), prec), positions,
                   c["rope_theta"])
        k = R.rope(R.mm("sd,dhk->shk", x, lw("wk"), prec), positions,
                   c["rope_theta"])
        v = R.mm("sd,dhk->shk", x, lw("wv"), prec)
        qg = q.reshape(S, KV, H // KV, Dh)
        s = R.mm("qgrd,kgd->grqk", qg, k, prec) / Dh ** 0.5
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
        o = R.mm("grqk,kgd->qgrd", p, v, prec).reshape(S, H, Dh)
        g = R.mm("sd,df->sf", x, lw("w_gate"), prec)
        u = R.mm("sd,df->sf", x, lw("w_up"), prec)
        h = (h + R.mm("shk,hkd->sd", o, lw("wo"), prec)
             + R.mm("sf,fd->sd", jax.nn.silu(g) * u, lw("w_down"), prec))
    hn = R.rms_norm(h, f32(w["final_norm"]), c["rms_norm_eps"])
    return R.mm("sd,dv->sv", hn, w["lm_head"], prec), None


def matmul_params(c):
    return DENSE.matmul_params(c) + 1


def attn_flops_per_token(c, context):
    return DENSE.attn_flops_per_token(c, context)
'''

PROBE = '''
import json, sys
sys.path.insert(0, "benchmarks/chip")
import numpy as np
import flops as FL, harness, reference as R, weights as W
cell = harness.load_cell("tiny-parallel-rl")
w = W.make_weights(cell.config, 5)
tok = np.arange(8, dtype=np.int32) + 3
lg, _ = R.forward(w, tok, np.arange(8, dtype=np.int32),
                  np.ones(8, np.int32), cell.config, "f32")
try:
    harness.build(cell, 5, interpret=None)
    refusal = ""
except ValueError as e:
    refusal = str(e)
print(json.dumps({"arch": W.arch(cell.config).__name__,
                  "logits": list(lg.shape), "finite": bool(np.isfinite(lg).all()),
                  "params": FL.matmul_params(cell.config),
                  "dense_params": W.load_arch("dense").matmul_params(cell.config),
                  "per_layer": [m["name"] for m in cell.per_layer],
                  "refusal": refusal}))
'''


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_a_new_arch_needs_only_new_files(tmp_path):
    lay = tmp_path / "checkout"
    shutil.copytree(tiny.BENCH, lay / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), lay)
    before = _digests(lay)
    chip = lay / "benchmarks" / "chip"
    (chip / "arch" / "parallel.py").write_text(textwrap.dedent(PARALLEL))
    cfg = dict(tiny.CONFIG, name="tiny-parallel", arch="parallel",
               reduced=[], published={}, assumed={}, deployment="test")
    (chip / "configs" / "tiny-parallel.json").write_text(json.dumps(cfg))
    (chip / "traffic" / "tiny-math.json").write_text(json.dumps(tiny.MIX))
    wl = dict(tiny.WORKLOAD, name="tiny-parallel-rl", config="tiny-parallel")
    (chip / "workloads" / "tiny-parallel-rl.json").write_text(json.dumps(wl))
    bench = json.loads((lay / "BENCHMARK.json").read_text())
    old = json.loads(json.dumps(bench))
    bench["configs"].append({
        "name": "tiny-parallel", "source": "test",
        "file": "benchmarks/chip/configs/tiny-parallel.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny-parallel-rl", "config": "tiny-parallel",
        "traffic": "tiny-math", "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] == "trained_lag_mean":
            m["workloads"].append("tiny-parallel-rl")
    (lay / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", PROBE], cwd=lay, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["arch"] == "arch_parallel"
    assert got["logits"] == [8, tiny.CONFIG["vocab_size"]] and got["finite"]
    assert got["params"] == got["dense_params"] + 1
    assert got["per_layer"] == ["trained_lag_mean"]
    assert got["refusal"] == "parallel: the program runs no parallel block"

    # every file that was there is as it was, but BENCHMARK.json, which
    # only gained entries
    after = _digests(lay)
    changed = [k for k in before if after[k] != before[k]]
    assert changed == ["BENCHMARK.json"]
    now = json.loads((lay / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads"):
        assert now[key][:len(old[key])] == old[key]
    assert now["end_to_end"] == old["end_to_end"]
    for a, b in zip(now["per_layer"], old["per_layer"]):
        assert {k: v for k, v in a.items() if k != "workloads"} == \
            {k: v for k, v in b.items() if k != "workloads"}
        assert a["workloads"][:len(b["workloads"])] == b["workloads"]
