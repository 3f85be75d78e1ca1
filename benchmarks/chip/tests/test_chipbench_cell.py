"""The harness finds a cell from files alone; BENCHMARK.json names only
what exists; the entry point refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import traffic_gen  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_every_cell_is_found_from_files():
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        assert cell.mix["name"] == w["traffic"]
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.load_reader(m["name"]))
        for k in ("engine", "pipeline", "optimizer", "rl", "limits"):
            assert k in cell.workload


def test_a_cell_not_in_benchmark_json_is_refused():
    import pytest
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")


def test_config_files_state_their_cut():
    for c in BENCH["configs"]:
        f = json.load(open(os.path.join(ROOT, c["file"])))
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert sorted(f["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert k in f["published"] and f[k] != f["published"][k]
        assert "assumed" in f and "deployment" in f


def test_traffic_is_a_function_of_the_seed():
    mix = traffic_gen.load_mix("math-long")
    a = traffic_gen.Traffic(mix, 2 ** 31 + 5)
    b = traffic_gen.Traffic(mix, 2 ** 31 + 5)
    c = traffic_gen.Traffic(mix, 2 ** 31 + 6)
    pa = [a.source().prompt_ids for _ in range(50)]
    assert pa == [b.source().prompt_ids for _ in range(50)]
    assert pa != [c.source().prompt_ids for _ in range(50)]
    assert max(len(p) for p in pa) <= 9
    p = a.source()
    assert a.reward(p, traffic_gen.encode(str(p.answer))[1:], 100) == 1.0
    assert p.done_at is not None and p.done_at >= p.drawn_at


def test_a_mix_can_run_every_rollout_to_max_len():
    assert traffic_gen.eos_id({}) == traffic_gen.EOS
    assert traffic_gen.eos_id(traffic_gen.load_mix("math-long")) == -1


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "chip", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run(ROOT, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr
    # a directory holding only BENCHMARK.json and the benchmark's files
    bare = tmp_path / "bare"
    shutil.copytree(HERE, bare / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), bare)
    r = _run(str(bare), env)
    assert r.returncode != 0 and r.stdout.strip() == ""
