"""The readers of the program's spans on a synthetic trace: the window's
spans placed on the trace's clock by one offset, each idle instant put
down to the innermost span open over it, the install pause per
publication, and nothing read from a program without spans."""
import sys

import pytest

import tiny  # noqa: F401  (puts the benchmark and the program on sys.path)
import harness
import profile_reduce as PR
import program_spans as PS
from repro import spans

T0 = 1000.0        # the window's start on the perf_counter clock (s)
GAP_READERS = ("decode_host_gap_ms", "train_host_gap_ms", "loop_gap_ms")


def rec(name, a, b, parent=None, **counts):
    """A span at trace seconds [a, b), stamped on the perf_counter clock:
    the trace's window opens at 0, the program's at T0."""
    return spans.Span(name, round((a + T0) * 1e9), round((b + T0) * 1e9),
                      parent, counts)


# device busy [0,1) [2,3) [4,6) [7,9.5): idle 3.5 s of the 10-second window
OPS = [PR.Op("op", a, b) for a, b in ((0, 1), (2, 3), (4, 6), (7, 9.5))]
RECS = [
    rec("actor.tick", 0.5, 3.5),
    rec("engine.decode", 0.6, 3.2, "actor.tick", active=4, ctx=40),
    rec("engine.decode.dispatch", 0.6, 0.7, "engine.decode"),
    rec("engine.decode.wait", 0.7, 1.5, "engine.decode"),
    rec("engine.decode.finish", 1.8, 3.1, "engine.decode", finished=1),
    rec("trainer.train", 5.5, 6.8),
    rec("trainer.pack", 5.5, 5.6, "trainer.train"),
    rec("trainer.step", 5.6, 6.2, "trainer.train", rows=4, loss_tokens=9,
        version=3),
    rec("trainer.sync", 6.2, 6.5, "trainer.train"),
    rec("actor.tick", 6.85, 7.5),
    rec("engine.install", 6.9, 7.2, "actor.tick", version=4, chunk=7,
        nbytes=10, swapped=1),
    rec("engine.swap", 7.0, 7.1, "engine.install", version=4, nbytes=80),
    rec("engine.decode", 7.2, 7.4, "actor.tick", active=4, ctx=44),
    rec("engine.swap", 9.6, 9.8, None, version=5, nbytes=80),
    rec("engine.install", 12.0, 12.5, "actor.tick", swapped=1),  # after
]
# idle by class, worked by hand: engine 1.0 + 0.5 + 0.15 + 0.2, trainer
# 0.2 + 0.3 + 0.3, none 0.5 + 0.05 + 0.1 + 0.2
IDLE = {"decode_host_gap_ms": 1.85, "train_host_gap_ms": 0.8,
        "loop_gap_ms": 0.85}
PER = {"decode_host_gap_ms": 2, "train_host_gap_ms": 1, "loop_gap_ms": 2}


@pytest.fixture
def ctx(monkeypatch):
    monkeypatch.setattr(spans, "records", lambda t0, t1: [
        r for r in RECS if t0 * 1e9 <= r.start_ns < t1 * 1e9])
    tr = PR.Trace({"/device:TPU:0": list(OPS)}, {"/device:TPU:0": []}, [],
                  (0.0, 10.0))
    return harness.Ctx({}, {}, tr, {"t0": T0, "t1": T0 + 10.0}, {}, 1)


def test_innermost_span_takes_each_idle_instant(ctx):
    placed = PS.place(ctx, spans.records(T0, T0 + 10.0))
    idle = PS.idle_by_name(OPS, placed, (0.0, 10.0))
    want = {"engine.decode.wait": 0.5, "engine.decode": 0.4,
            "engine.decode.finish": 0.3, "actor.tick": 0.35,
            "engine.install": 0.1, "engine.swap": 0.2, "trainer.step": 0.2,
            "trainer.sync": 0.3, "trainer.train": 0.3, None: 0.85}
    assert set(idle) == set(want)
    for k, v in want.items():
        assert idle[k] == pytest.approx(v, abs=1e-6), k


@pytest.mark.parametrize("metric", GAP_READERS)
def test_gap_readers(ctx, metric):
    got = harness.load_reader(metric)(ctx)
    assert got == pytest.approx(1e3 * IDLE[metric] / PER[metric], rel=1e-6)


def test_gap_metrics_times_their_counts_make_up_the_idle_time(ctx):
    total = sum(harness.load_reader(m)(ctx) * PER[m] for m in GAP_READERS)
    idle = 10.0 - PR.busy_seconds(OPS, (0.0, 10.0))
    assert total == pytest.approx(1e3 * idle, rel=1e-9)


def test_install_pause_counts_a_nested_swap_once(ctx):
    # outermost: the install [6.9, 7.2) that swapped, the lone swap
    # [9.6, 9.8); the install after the window is not read
    got = harness.load_reader("install_pause_ms")(ctx)
    assert got == pytest.approx(1e3 * (0.3 + 0.2) / 2, rel=1e-6)


@pytest.mark.parametrize("metric", GAP_READERS + ("install_pause_ms",))
def test_readers_read_nothing_without_records(ctx, monkeypatch, metric):
    monkeypatch.setattr(spans, "records", lambda t0, t1: [])
    assert harness.load_reader(metric)(ctx) is None


@pytest.mark.parametrize("metric", GAP_READERS + ("install_pause_ms",))
def test_readers_read_nothing_from_a_program_without_spans(
        ctx, monkeypatch, metric):
    import repro
    monkeypatch.delattr(repro, "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)   # import fails
    assert harness.load_reader(metric)(ctx) is None


def test_gap_readers_read_nothing_without_a_trace(ctx):
    ctx.trace = None
    for m in GAP_READERS:
        assert harness.load_reader(m)(ctx) is None
