"""A cell with a mesh, at the tiny size on four virtual CPU devices (in a
subprocess: the device count is fixed when JAX starts): the harness
builds the mesh pipeline, a publication is executed across submeshes and
lands bitwise, a whole traced run on the mesh is correct and reads the
install counter, and a run whose publications leave out the exchange
between chips is not correct."""
import json
import os
import subprocess
import sys

import tiny  # noqa: F401  (puts the benchmark and the program on sys.path)

SCRIPT = r'''
import json, sys
sys.path.insert(0, sys.argv[1])
import jax
import tiny, harness, checks

def mesh_cell():
    cell = tiny.cell()
    cell.chips = 4
    cell.workload["mesh"] = {"shape": [4], "axes": ["model"]}
    cell.workload["pipeline"]["n_engines"] = 2
    if all(m["name"] != "install_s_per_update" for m in cell.per_layer):
        cell.per_layer.append({
            "name": "install_s_per_update", "unit": "s", "better": "lower",
            "source": "program_counter", "layer": "engine",
            "moves": "sampled_tokens_per_s", "workloads": ["tiny-rl"]})
    return cell

out = {"devices": len(jax.devices())}
cell = mesh_cell()
pipe, rec, _ = harness.build(cell, 2 ** 31 + 41, interpret=None)
harness.warm_up(pipe, cell)
subs = [sorted(d.id for d in e.mesh.devices.reshape(-1)) for e in pipe.engines]
out["submeshes"] = subs
out["trainer_devices"] = sorted({d.id for leaf in jax.tree.leaves(
    pipe.trainer.params) for d in leaf.sharding.device_set})
out["executed"] = pipe.broadcast_stats()["executed"]
out["install_mismatch"] = checks.install_mismatch(pipe)
del pipe, rec

run = harness.run(mesh_cell(), 2 ** 31 + 43, 0.5, trace=True, t_start=0.0,
                  log=lambda s: None)
out["correct"] = run["correct"]
out["checks"] = run["checks"]
out["metrics"] = sorted(run["metrics"])
out["install_s_per_update"] = run["metrics"].get(
    "install_s_per_update", {}).get("value")

from repro.launch import meshrt

def left_out(self, engine, params, version, n_chunks):
    # the engine gets its own leaves back: no transfer between chips
    from repro.core.events import chunk_spans
    mine = jax.tree_util.tree_leaves(engine.params)
    spans = chunk_spans(jax.tree_util.tree_leaves(params), n_chunks)
    return {"chunks": [list(mine[lo:hi]) for lo, hi in spans],
            "per_chunk": [0.0] * len(spans), "seconds": 0.0,
            "sizes": [0] * len(spans), "nbytes": 0, "version": int(version),
            "jit": False}

meshrt.MeshBroadcastExecutor.run = left_out
bad = harness.run(mesh_cell(), 2 ** 31 + 47, 0.5, trace=False, t_start=0.0,
                  log=lambda s: None)
out["fault_correct"] = bad["correct"]
out["fault_install_mismatch"] = bad["checks"]["install_mismatch"]["value"]
print(json.dumps(out))
'''


def test_mesh_cell_builds_publishes_and_checks_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(tiny.ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT, tiny.HERE], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    a, b = out["submeshes"]
    assert len(a) == len(b) == 2 and not set(a) & set(b)
    assert out["trainer_devices"] == [0, 1, 2, 3]
    assert out["executed"] >= 2               # one publication, two engines
    assert out["install_mismatch"] == 0
    assert out["correct"], out["checks"]
    assert out["checks"]["install_mismatch"]["value"] == 0.0
    assert {"install_s_per_update", "trained_lag_mean"} <= set(out["metrics"])
    assert out["install_s_per_update"] > 0
    assert not out["fault_correct"]
    assert out["fault_install_mismatch"] > 0
