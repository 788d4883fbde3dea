"""The three readers of the program's span log (`span_stat`, `host_gap`,
`build_stat`) on a hand-made log: nested spans, a cross-thread span, a
window that cuts a span, the window found in the log, and an empty log
giving None.  Nanoseconds here are
made up; no number is a device number."""

import importlib

import pytest

from chipbench.readers import build_stat, host_gap, span_stat, spanlog
from chipbench.readers.spanlog import Span

W, H = 1, 2          # the worker's thread and a handler's


def sp(name, start, end, tid=W, cause=None, rid=None, traced=True, **args):
    return Span(name, start, end, tid, cause, rid, args, traced)


def hand_made():
    """Two engine steps on the worker thread between 1000 and 3000:

    1000-1100 worker.inbox      1100-1400 engine.admit, inside it
    1150-1200 prefill.build, 1200-1250 prefill.enqueue, 1250-1350
    prefill.wait, 1350-1390 prefill.harvest
    1400-1450 decode.prepare    1450-1500 decode.enqueue
    1500-1900 decode.wait       1900-2000 decode.harvest
    2000-2020 step.publish      2020-2060 worker.flush   2060-2100 nothing
    2100-2150 engine.admit      2150-2200 decode.prepare
    2200-2250 decode.enqueue    2250-2900 decode.wait (the window cuts it
    at 2800)                    2900-3000 decode.harvest
    and on a handler thread two gateway.deliver spans that began on the
    worker (their start was handed over)."""
    return [
        sp("worker.inbox", 1000, 1100, commands=1),
        sp("engine.admit", 1100, 1400, requests=1),
        sp("engine.prefill.build", 1150, 1200, cause="engine.admit"),
        sp("engine.prefill.enqueue", 1200, 1250, cause="engine.admit"),
        sp("engine.prefill.wait", 1250, 1350, cause="engine.admit"),
        sp("engine.prefill.harvest", 1350, 1390, cause="engine.admit"),
        sp("engine.decode.prepare", 1400, 1450),
        sp("engine.decode.enqueue", 1450, 1500, horizon=1),
        sp("engine.decode.wait", 1500, 1900),
        sp("engine.decode.harvest", 1900, 2000, tokens=2),
        sp("engine.step.publish", 2000, 2020),
        sp("worker.flush", 2020, 2060, handles=2),
        sp("engine.admit", 2100, 2150, requests=0),
        sp("engine.decode.prepare", 2150, 2200),
        sp("engine.decode.enqueue", 2200, 2250, horizon=1),
        sp("engine.decode.wait", 2250, 2900),
        sp("engine.decode.harvest", 2900, 3000, tokens=2),
        sp("gateway.deliver", 2030, 2090, tid=H, rid=1, tokens=1),
        sp("gateway.deliver", 2040, 2190, tid=H, rid=2, tokens=1),
    ]


LO, HI = 1000, 2800


def test_span_stat_mean_share_and_percentile():
    log = hand_made()
    assert span_stat.reduce(log, LO, HI, "engine.decode.harvest") == \
        pytest.approx(100 / 1e6)                  # one inside, 100 ns
    assert span_stat.reduce(log, LO, HI, "engine.decode.wait",
                            "share") == pytest.approx(
        100.0 * (400 + 550) / 1800)               # the second one is cut
    assert span_stat.reduce(log, LO, HI, "gateway.deliver", "p95") == \
        pytest.approx(150 / 1e6)
    assert span_stat.reduce(log, LO, HI, "gateway.deliver", "p50") == \
        pytest.approx(60 / 1e6)
    assert span_stat.reduce(log, LO, HI, "no.such.span") is None


def test_span_stat_sum_of_names_a_decode_step():
    got = span_stat.reduce(
        hand_made(), LO, HI, ["engine.decode.harvest",
                              "engine.step.publish", "worker.flush"],
        "mean", per="engine.decode.harvest")
    assert got == pytest.approx((100 + 20 + 40) / 1 / 1e6)
    # over the whole log both steps' harvests count
    got = span_stat.reduce(
        hand_made(), 0, 4000, ["engine.decode.harvest",
                               "engine.step.publish", "worker.flush"],
        "mean", per="engine.decode.harvest")
    assert got == pytest.approx((200 + 20 + 40) / 2 / 1e6)


def test_span_stat_self_time_takes_out_children():
    log = hand_made()
    # both admits (300 + 50) without the one wait (100), a prefill dispatch
    assert span_stat.reduce(log, LO, HI, "engine.admit", "mean",
                            per="engine.prefill.enqueue",
                            self_time=["engine.prefill.wait"]) == \
        pytest.approx((300 - 100 + 50) / 1 / 1e6)
    # every child out: 300 - (50 + 50 + 100 + 40) and the childless 50
    assert span_stat.reduce(log, LO, HI, "engine.admit", "share",
                            self_time=True) == pytest.approx(
        100.0 * (60 + 50) / 1800)
    # a span of another thread inside the interval is no child
    assert span_stat.reduce(log, LO, HI, "worker.flush", "mean",
                            self_time=True) == pytest.approx(40 / 1e6)


def test_host_gap_share_and_table():
    share, table = host_gap.reduce(hand_made(), LO, HI)
    # queued: 1200-1350, 1450-1900, 2200-2800 (cut) = 1200 of 1800
    assert share == pytest.approx(100.0 * 600 / 1800)
    assert sum(table.values()) == pytest.approx(600 / 1e9)
    assert table["worker.inbox"] == pytest.approx(100e-9)
    assert table["engine.admit"] == pytest.approx((50 + 10 + 50) * 1e-9)
    assert table["engine.prefill.build"] == pytest.approx(50e-9)
    assert table["engine.prefill.harvest"] == pytest.approx(40e-9)
    assert table["engine.decode.prepare"] == pytest.approx(100e-9)
    assert table["engine.decode.harvest"] == pytest.approx(100e-9)
    assert table["engine.step.publish"] == pytest.approx(20e-9)
    assert table["worker.flush"] == pytest.approx(40e-9)
    assert table["no span"] == pytest.approx(40e-9)
    # the handler thread's spans cover nothing of the worker's gaps
    assert "gateway.deliver" not in table
    assert not any(k.endswith((".enqueue", ".wait")) for k in table)


def test_host_gap_needs_a_worker_thread_with_a_dispatch():
    handler_only = [s for s in hand_made() if s.tid == H]
    assert host_gap.reduce(handler_only, LO, HI) is None
    idle = [sp("worker.idle", 1000, 2000)]
    assert host_gap.reduce(idle, LO, HI) is None


def test_innermost_pieces_cover_the_window():
    pieces = spanlog.innermost([s for s in hand_made() if s.tid == W],
                               LO, HI)
    assert pieces[0][0] == LO and pieces[-1][1] == HI
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))
    assert (2060, 2100, None) in pieces


def build(program, start, total, trace, lower, retrieval, rest, **key):
    return {"program": program, "key": key, "start_ns": start,
            "total_s": total, "trace_s": trace, "lower_s": lower,
            "compile_s": 0.0, "cache_retrieval_s": retrieval,
            "rest_s": rest, "cache_hit": True, "thread": W, "error": None}


def test_build_stat():
    table = [build("serving.prefill", 100, 8.0, 3.0, 2.0, 2.0, 1.0, lanes=1),
             build("serving.decode", 200, 10.0, 4.0, 3.0, 2.0, 1.0, nb=8),
             build("serving.decode", 5000, 20.0, 4.0, 3.0, 2.0, 11.0, nb=16)]
    assert build_stat.reduce(table, 1000) == pytest.approx(9.0)
    assert build_stat.reduce(table, None) == pytest.approx(38.0 / 3)
    assert build_stat.reduce(table, 1000, "trace_s", "sum") == \
        pytest.approx(7.0)
    assert build_stat.reduce(table, 1000, program="serving.decode") == \
        pytest.approx(10.0)
    assert build_stat.reduce(table, 50) is None


class Ctx:
    def __init__(self, traced_run=True):
        self.reduced = {"window_ns": (0.0, 1.0)} if traced_run else None
        self.lines = []

    def log(self, msg):
        self.lines.append(msg)


def test_window_is_found_in_the_log():
    """From the first program queued under the profiler to the end of the
    last span that began under it; the trace file's own times are not the
    log's (they count from the profiler session's start)."""
    log = [sp("worker.idle", 10, 60, traced=False),
           sp("worker.idle", 60, 110),                    # traced, idle
           sp("engine.prefill.enqueue", 120, 130),
           sp("engine.decode.wait", 500, 900),
           sp("engine.decode.harvest", 900, 950, traced=False)]
    assert spanlog.window(Ctx(), log) == (120, 900)
    assert spanlog.window(Ctx(traced_run=False), log) is None
    untraced = [s._replace(traced=False) for s in log]
    assert spanlog.window(Ctx(), untraced) is None
    assert spanlog.window(Ctx(), []) is None


def test_readers_on_the_programs_own_log(tmp_path):
    """Through `read()`: spans the program's span module wrote while a
    profiler trace ran are found and reduced, those before it are not;
    the tables are logged."""
    import jax

    log = importlib.import_module("paddle_tpu.observability.span")
    events = importlib.import_module("paddle_tpu.observability.events")
    events.clear()
    with log.build("serving.prefill", {"lanes": 1, "bucket": 8}):
        with log.span("engine.decode.harvest", before=True):
            pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        t0 = log.now_ns()
        with log.span("worker.flush"):
            pass
        with log.span("engine.decode.enqueue"):
            pass
        with log.span("engine.decode.wait"):
            pass
        with log.span("engine.decode.harvest"):
            pass
        log.complete("gateway.deliver", t0, rid=3, tokens=1)
    finally:
        jax.profiler.stop_trace()
    with log.span("engine.decode.harvest", after=True):
        pass
    ctx = Ctx()
    records = spanlog.spans()
    assert [s.traced for s in records if s.name == "engine.decode.harvest"] \
        == [False, True, False]
    lo, hi = spanlog.window(ctx, records)
    inside = spanlog.clipped(records, lo, hi)
    assert [s.name for s in inside if s.name == "engine.decode.harvest"] \
        == ["engine.decode.harvest"]
    assert span_stat.read(ctx, "engine.decode.harvest") > 0
    assert span_stat.read(ctx, "gateway.deliver", "p95") > 0
    share = host_gap.read(ctx)
    assert 0 < share < 100
    assert any("seconds by the span over them" in m for m in ctx.lines)
    assert build_stat.read(ctx) >= 0          # built before the window
    assert any("serving.prefill {'lanes': 1, 'bucket': 8}" in m
               for m in ctx.lines)
    assert span_stat.read(ctx, "train.step.enqueue") is None
    assert span_stat.read(Ctx(traced_run=False),
                          "engine.decode.harvest") is None


def test_an_empty_log_gives_none():
    events = importlib.import_module("paddle_tpu.observability.events")
    events.clear()
    ctx = Ctx()
    assert span_stat.read(ctx, "engine.decode.harvest") is None
    assert host_gap.read(ctx) is None
    assert spanlog.window(ctx, spanlog.spans()) is None


def test_a_program_without_a_span_log_gives_none(monkeypatch):
    """The parent of the PR that brought the log: its span module has no
    `records` and no `builds`."""
    class Parent:
        pass

    monkeypatch.setattr(spanlog, "_program_log", lambda: Parent())
    ctx = Ctx()
    assert spanlog.spans() is None and spanlog.builds() is None
    assert span_stat.read(ctx, "train.step.enqueue") is None
    assert host_gap.read(ctx) is None
    assert build_stat.read(ctx) is None
    monkeypatch.setattr(spanlog, "_program_log", lambda: None)
    assert span_stat.read(ctx, "train.step.enqueue") is None
