"""The trace reduction on synthetic traces: busy union, idle gaps and their
labels, kernel sums, and the readers that use them."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

import harness  # noqa: E402
import profile_reduce as PR  # noqa: E402


def op(name, a, b):
    return PR.Op(name, a, b)


def trace(ops, spans=(), modules=(), span=(0.0, 10.0)):
    return PR.Trace({"/device:TPU:0": list(ops)},
                    {"/device:TPU:0": list(modules)}, list(spans), span)


def test_union_merges_overlaps_and_drops_empty():
    assert PR.union([(0, 2), (1, 3), (5, 6), (6, 7), (8, 8)]) == [
        (0, 3), (5, 7)]


def test_busy_and_gaps_clip_to_the_window():
    ops = [op("a", -1, 1), op("b", 0.5, 2), op("c", 4, 5), op("d", 9, 12)]
    span = (0.0, 10.0)
    assert PR.busy_seconds(ops, span) == pytest.approx(2 + 1 + 1)
    assert PR.gaps(ops, span) == [(2, 4), (5, 9)]


def test_gap_labels_take_the_innermost_span():
    ops = [op("a", 0, 1), op("b", 3, 4), op("c", 8, 10)]
    spans = [op(PR.SPAN_PREFIX + "event", 0, 10),
             op(PR.SPAN_PREFIX + "decode", 1, 2.5),
             op(PR.SPAN_PREFIX + "install", 4.5, 7)]
    gaps = PR.top_gaps(ops, spans, (0.0, 10.0))
    assert gaps[0] == ["install", pytest.approx(4.0)]
    assert gaps[1] == ["decode", pytest.approx(2.0)]
    assert PR.label_at([], 1.0) == "untraced"


def test_kernel_sums_match_on_the_operation_name():
    ops = [op("%flash_decode.7 = bf16[2] custom-call(x)", 0, 1),
           op("%flash_decode.7 = bf16[2] custom-call(x)", 2, 2.5),
           op("%fusion.3 = bf16[2] fusion(x)", 3, 4)]
    got = PR.matching(ops, ("flash_decode",))
    assert PR.summed(got) == pytest.approx(1.5)
    assert PR.summed(got, (0.5, 2.25)) == pytest.approx(0.75)
    assert PR.top_ops(ops)[0] == ["flash_decode.7 = bf16[2]", 1.5]


def ctx(tr, window=None, peak=None):
    import json
    cfg = json.load(open(os.path.join(harness.HERE, "configs",
                                      "granite-3-2b-l4.json")))
    wl = json.load(open(os.path.join(harness.HERE, "workloads",
                                     "granite2b-rl-long.json")))
    w = {"window_s": 10.0, "decode_ctx": 0, "decode_tokens": 0, "rows": 0,
         "sampled": 0, "seq_tokens": 0, "ctx_sum": 0, "prefill_tokens": 0,
         "lag_hist": {}}
    w.update(window or {})
    return harness.Ctx(cfg, wl, tr, w, peak or {"bf16_flops": 197e12,
                                                "hbm_bytes_per_s": 819e9},
                       1)


def test_idle_share_reader():
    tr = trace([op("a", 0, 2.5), op("b", 5, 7.5)])
    assert harness.load_reader("device_idle_share")(ctx(tr)) == \
        pytest.approx(50.0)
    assert harness.load_reader("device_idle_share")(ctx(None)) is None


def test_programs_take_the_role_of_the_span_that_launched_them():
    spans = [op(PR.SPAN_PREFIX + "event", 0, 9),
             op(PR.SPAN_PREFIX + "decode", 0, 1.01),
             op(PR.SPAN_PREFIX + "train_step", 2, 2.01),
             op(PR.SPAN_PREFIX + "decode", 4, 4.5),
             op(PR.SPAN_PREFIX + "train_step", 5.5, 5.51),
             op(PR.SPAN_PREFIX + "decode", 6, 6.5)]
    mods = [op("jit__unknown(1)", 0.5, 0.502),
            op("jit__unknown(2)", 2.3, 2.5),      # started after its span
            op("jit__unknown(1)", 4.1, 4.104),
            op("jit__unknown(1)", 5.99, 5.994)]   # clocks a little apart
    ops = [op("%flash_decode.7 = bf16[2] custom-call(x)", 0.5, 0.501),
           op("%fusion.1 = bf16[2] fusion(x)", 0.501, 0.502),
           op("%jvp__.1 = f32[2] custom-call(y)", 2.3, 2.4),
           op("%custom-call.9 = pred[2] custom-call(y)", 2.4, 2.45),
           op("%flash_decode.7 = bf16[2] custom-call(x)", 4.1, 4.103),
           op("%flash_decode.7 = bf16[2] custom-call(x)", 5.99, 5.993)]
    tr = trace(ops, spans, mods)
    roles = PR.module_roles(tr)
    assert [m.start for m in roles["decode"]] == [0.5, 4.1, 5.99]
    assert [m.start for m in roles["train_step"]] == [2.3]
    assert [o.start for o in PR.kernels(tr, "decode")] == [0.5, 4.1, 5.99]
    assert [o.start for o in PR.kernels(tr, "train_step")] == [2.3]
    assert harness.load_reader("decode_device_ms")(ctx(tr)) == \
        pytest.approx(10.0 / 3)
    assert harness.load_reader("train_step_device_ms")(ctx(tr)) == \
        pytest.approx(100.0)


def test_self_time_subtracts_nested_operations():
    ops = [op("%while.4 = (s32[]) while(x)", 0, 10),
           op("%fusion.1 = bf16[2] fusion(x)", 1, 3),
           op("%copy.2 = bf16[2] copy(x)", 4, 5),
           op("%fusion.1 = bf16[2] fusion(x)", 11, 12)]
    assert PR.self_times(ops) == [7, 2, 1, 1]
    top = PR.top_ops(ops)
    assert top[0] == ["while.4 = (s32[])", 7]
    assert top[1] == ["fusion.1 = bf16[2]", 3]


def test_decode_roofline_reader_is_the_least_time_over_kernel_time():
    # 1000 slot-steps at 512 cached tokens each on granite-3-2b-l4
    n, L = 1000, 512
    kv_bytes = 2 * 4 * 8 * 64 * (n * L) * 2
    qo_bytes = 2 * 4 * n * 32 * 64 * 2
    least = (kv_bytes + qo_bytes) / 819e9          # memory-bound
    spans = [op(PR.SPAN_PREFIX + "decode", 0, 2 * least + 1)]
    mods = [op("jit__unknown(1)", 0, 2 * least + 0.5)]
    kern = [op("%flash_decode.7 = bf16[2] custom-call(x)", 0, 2 * least)]
    got = harness.load_reader("decode_kernel_roofline")(
        ctx(trace(kern, spans, mods, span=(0.0, 10.0)),
            {"decode_ctx": n * L, "decode_tokens": n}))
    assert got == pytest.approx(50.0)
    none = trace([op("%fusion.1 = bf16[2] fusion(x)", 0, 1)], spans, mods)
    assert harness.load_reader("decode_kernel_roofline")(
        ctx(none, {"decode_ctx": n * L, "decode_tokens": n})) is None


class _Pipe:
    """Enough of a pipeline for `harness.measure`: each pass of the event
    loop executes one more publication of 0.25 s into an engine."""

    def __init__(self, executed: int, exec_seconds: float, per_run: int):
        from types import SimpleNamespace as NS
        self.stats = {"executed": executed, "exec_seconds": exec_seconds}
        self.per_run = per_run
        self.trainer = NS(state=NS(params={}))
        self.trainer_stage = NS(lag_hist={})
        self.engines = [NS(tokens_generated=0, prefill_tokens=0,
                           prompts_rejected=0)]
        self.actors = [NS(rollouts_lost=0)]
        self.loop = NS(run=self._run)

    def _run(self, until):
        self.stats["executed"] += self.per_run
        self.stats["exec_seconds"] += 0.25 * self.per_run

    def broadcast_stats(self):
        return dict(self.stats)


@pytest.mark.parametrize("per_run", [0, 3])
def test_install_seconds_per_update_is_a_delta_over_the_window(per_run):
    from types import SimpleNamespace as NS
    pipe = _Pipe(executed=5, exec_seconds=10.0, per_run=per_run)
    rec = NS(loss_tokens=0.0, steps=0, seq_tokens=0, ctx_sum=0, rows=0)
    d = harness.measure(pipe, rec, NS(done=[]), harness.Counters(), 0.0,
                        None)
    got = harness.load_reader("install_s_per_update")(
        harness.Ctx({}, {}, None, d, {}, 4))
    if per_run:
        # 3 publications of 0.25 s in the window, not 10.75 s over 8
        assert got == pytest.approx(0.25)
    else:
        assert got is None


def test_train_step_device_time_reduces_each_plane_then_averages():
    # one optimizer step whose program runs on four chips for 0.1 s each
    spans = [op(PR.SPAN_PREFIX + "train_step", 1.0, 1.01)]
    planes = [f"/device:TPU:{i}" for i in range(4)]
    mods = {p: [op("jit_train_step(1)", 1.0 + 0.001 * i, 1.1 + 0.001 * i)]
            for i, p in enumerate(planes)}
    ops = {p: [op("%fusion.1 = bf16[2] fusion(x)", m[0].start, m[0].end)]
           for p, m in mods.items()}
    tr = PR.Trace(ops, mods, spans, (0.0, 10.0))
    assert PR.role_seconds_by_plane(tr, "train_step") == \
        pytest.approx([0.1] * 4)
    assert harness.load_reader("train_step_device_ms")(ctx(tr)) == \
        pytest.approx(100.0)
    # idle share: the mean of each chip's own
    assert harness.load_reader("device_idle_share")(ctx(tr)) == \
        pytest.approx(99.0)
