"""Whole runs of a tiny cell on the CPU (the chip check skipped): the
program agrees with the reference, the traced run reports per-layer
metrics, and the control (the reference in float8) is not correct."""
import math

import pytest

import tiny  # noqa: F401  (puts the benchmark on sys.path)
import checks
import harness

SEED = 2 ** 31 + 17


@pytest.fixture(scope="module")
def sound():
    return harness.run(tiny.cell(), SEED, 1.0, trace=False, t_start=0.0,
                       log=lambda s: None)


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert set(sound["metrics"]) == {"trained_tokens_per_s",
                                     "sampled_tokens_per_s",
                                     "rollout_p90_s", "setup_s"}
    assert all(math.isfinite(m["value"]) and m["value"] > 0
               for m in sound["metrics"].values())
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert list(sound)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics_and_stays_correct():
    out = harness.run(tiny.cell(), SEED + 1, 1.0, trace=True, t_start=0.0,
                      log=lambda s: None)
    assert out["correct"], out["checks"]
    assert "trained_lag_mean" in out["metrics"]
    assert "window_s" in out["device"] and "busy_s" in out["device"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_control_in_float8_is_not_correct():
    cell = tiny.cell()
    pipe, rec, _ = harness.build(cell, SEED, interpret=None)
    harness.warm_up(pipe, cell)
    prog = checks.program_readings(pipe, rec, cell, SEED)
    ref = checks.reference_readings(cell, SEED, prog["batches"],
                                    prog["rollouts"])
    ctl = checks.reference_readings(cell, SEED, prog["batches"],
                                    prog["rollouts"], prec="fp8")
    limits = cell.workload["limits"]
    got = checks.numbers(prog, ref, prog["rollouts"])
    bad = checks.numbers(ctl, ref, prog["rollouts"])
    assert all(got[k] <= limits[k] for k in got), got
    assert any(bad[k] > limits[k] for k in bad), bad
