"""The sweep reduction against `trace.reduce_events` as its oracle, and the
program spans it adds."""
import random
import time

import pytest

from chipbench import sweep, trace
from chipbench.tests.test_trace import DATA, MS

OLD_FIELDS = ("window_s", "busy_s", "top_ops", "span_busy_s", "span_count",
              "cut_s")


def random_events(rng: random.Random, cut: bool):
    """Device operations on one to three planes, the window, overlapping
    and nested benchmark spans, and program spans with stats; with `cut`,
    benchmark spans go on after the last device operation."""
    ops = {}
    for d in range(rng.randint(1, 3)):
        ops[f"/device:TPU:{d}"] = [
            (f"%op{rng.randint(0, 5)} = f32[] add(%state_x)", a,
             a + rng.randint(0, 40))
            for a in (rng.randint(0, 900) for _ in range(rng.randint(1, 40)))]
    w0 = rng.randint(0, 100)
    w1 = rng.randint(w0 + 50, 1000)
    spans = [("window", w0, w1)]
    end = 1100 if cut else 1000
    for _ in range(rng.randint(0, 20)):
        a = rng.randint(0, end)
        spans.append((rng.choice("abc"), a, a + rng.randint(0, 300)))
    rng.shuffle(spans)
    prog = []
    for _ in range(rng.randint(0, 30)):
        a = rng.randint(0, end)
        prog.append((rng.choice(["p.x", "p.y", "p.z"]), a,
                     a + rng.randint(0, 200), {"k": rng.randint(0, 9)}))
    return ops, spans, prog


def both(ops, spans, prog=()):
    try:
        old = trace.reduce_events(ops, spans)
    except ValueError:
        with pytest.raises(ValueError):
            sweep.reduce_events(ops, spans, prog)
        return None, None
    return old, sweep.reduce_events(ops, spans, prog)


@pytest.mark.parametrize("cut", [False, True])
def test_every_old_field_reads_the_same_on_random_events(cut):
    rng = random.Random(7 + cut)
    compared = cuts = 0
    for _ in range(200):
        ops, spans, prog = random_events(rng, cut)
        old, new = both(ops, spans)
        if old is None:
            continue
        compared += 1
        cuts += old.cut_s > 0
        for f in OLD_FIELDS + ("idle_gaps",):
            assert getattr(new, f) == getattr(old, f), f
        # program spans change the gap labels only
        _, new = both(ops, spans, prog)
        for f in OLD_FIELDS:
            assert getattr(new, f) == getattr(old, f), f
        assert [s for _, s in new.idle_gaps] == [s for _, s in old.idle_gaps]
    assert compared > 150
    assert cuts > 20 if cut else cuts < compared


def test_the_recorded_v5e_trace_reads_the_same():
    old = trace.reduce_events(*trace.read_xplane(DATA, ["train_call"]))
    ops, spans, prog = sweep.read_xplane(DATA, ["train_call"], ["train.step"])
    assert prog == []
    new = sweep.reduce_events(ops, spans, prog)
    for f in OLD_FIELDS + ("idle_gaps",):
        assert getattr(new, f) == getattr(old, f), f
    assert new.span_host_s["train_call"] > new.span_busy_s["train_call"]


def test_program_spans_count_inside_kept_calls_and_label_the_gaps():
    ops = {"/device:TPU:0": [("%a", 10 * MS, 20 * MS),
                             ("%a", 40 * MS, 50 * MS),
                             ("%a", 70 * MS, 75 * MS)]}
    spans = [("window", 0, 100 * MS),
             ("simulate_call", 0, 30 * MS), ("simulate_call", 30 * MS,
                                             60 * MS),
             ("simulate_call", 80 * MS, 95 * MS)]     # after the last op
    prog = [("fleet.run_many", 1 * MS, 29 * MS, {"n": 32}),
            ("fleet.draws", 1 * MS, 8 * MS, {}),
            ("fleet.loop", 9 * MS, 21 * MS, {"regrow": 0}),
            ("fleet.run_many", 31 * MS, 59 * MS, {"n": 32}),
            ("fleet.loop", 32 * MS, 51 * MS, {"regrow": 1}),
            ("python.gc", 52 * MS, 59 * MS, {"generation": 2}),
            ("python.gc", 62 * MS, 64 * MS, {"generation": 0}),  # no call
            ("fleet.loop", 81 * MS, 90 * MS, {"regrow": 0})]     # cut off
    r = sweep.reduce_events(ops, spans, prog)
    assert r.cut_s == pytest.approx(0.025)
    assert r.span_count == {"simulate_call": 2}
    assert r.span_host_s == {"simulate_call": pytest.approx(0.060)}
    assert r.prog_count == {"fleet.run_many": 2, "fleet.draws": 1,
                            "fleet.loop": 2, "python.gc": 1}
    assert r.prog_host_s["fleet.loop"] == pytest.approx(0.031)
    assert r.prog_stats["fleet.run_many"] == {"n": 64}
    assert r.prog_stats["fleet.loop"] == {"regrow": 1}
    # each instant of a gap goes to the innermost span over it: 0-10 ms
    # draws 7, run_many 1, loop 1; 20-40 the loops 1 + 8, the run_manys
    # 8 + 1 (a tie: the first seen); 50-70 the collections 7 + 2
    assert [(n, round(s * 1e3)) for n, s in r.idle_gaps] == [
        ("fleet.loop", 20), ("python.gc", 20), ("fleet.draws", 10)]
    assert {n: round(s * 1e3) for n, s in r.prog_idle_s.items()} == {
        "fleet.draws": 7, "fleet.run_many": 11, "fleet.loop": 11,
        "python.gc": 9}

    def labels(prog):
        return [n for n, _ in sweep.reduce_events(ops, spans,
                                                  prog).idle_gaps]

    # gaps no program span covers keep the benchmark span's name
    assert labels([("fleet.wait", 21 * MS, 39 * MS, {}),
                   ("fleet.outer", 20 * MS, 40 * MS, {})]) == [
        "fleet.wait", "simulate_call", "simulate_call"]
    assert labels([("fleet.outer", 15 * MS, 45 * MS, {}),
                   ("fleet.inner", 20 * MS, 40 * MS, {})])[0] == \
        "fleet.inner"


def synthetic(calls: int):
    """`calls` calls of 30 ms, each with 8 program spans and 50 device
    operations, 10 ms of host time between calls."""
    ops, spans, prog = [], [("window", 0, calls * 40 * MS)], []
    for i in range(calls):
        t = i * 40 * MS
        spans.append(("simulate_call", t, t + 30 * MS))
        prog.append(("fleet.run_many", t + MS, t + 29 * MS, {"n": 32}))
        for j, name in enumerate(("fleet.draws", "fleet.setup",
                                  "fleet.pools", "fleet.loop",
                                  "fleet.compact", "fleet.results",
                                  "python.gc")):
            prog.append((name, t + (2 + 3 * j) * MS, t + (4 + 3 * j) * MS,
                         {"rows": 32}))
        loop = t + 11 * MS
        ops += [("%while.19 = f64[] while(%state_t)", loop + k * 30_000,
                 loop + k * 30_000 + 20_000) for k in range(50)]
    return {"/device:TPU:0": ops}, spans, prog


def test_a_two_thousand_call_trace_reduces_in_seconds():
    ops, spans, prog = synthetic(2000)
    t = time.monotonic()
    r = sweep.reduce_events(ops, spans, prog)
    assert time.monotonic() - t < 30
    assert r.span_count == {"simulate_call": 2000}
    assert r.prog_count["fleet.loop"] == 2000
    assert r.prog_host_s["fleet.loop"] == pytest.approx(4.0)
    # between calls: the end of one call's run_many, the start of the next
    assert r.idle_gaps[0][0] == "fleet.run_many"
    small = synthetic(50)
    old, new = both(*small[:2])
    for f in OLD_FIELDS + ("idle_gaps",):
        assert getattr(new, f) == getattr(old, f), f
