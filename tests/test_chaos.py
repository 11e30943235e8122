"""Chaos subsystem tests (docs/chaos.md): fault-timeline semantics, the
keyed-hazard determinism contract, two-engine parity under every
registered scenario, the ground-truth evaluator on hand-built histories,
and the live detect -> attribute -> mitigate runs behind
`python -m repro chaos`."""
import contextlib
import json

import numpy as np
import pytest
from jax import enable_x64

from repro.api import Session
from repro.chaos import (CheckpointOutage, FaultTimeline, LiveFault,
                         LivePlan, PSCrash, PreemptionWave, PriceSpike,
                         Scenario, StragglerFault, get_scenario,
                         list_scenarios, register_scenario, run_scenario,
                         score_history)
from repro.chaos.injectors import keyed_uniforms
from repro.chaos.runner import _ens_summary, _run_sim
from repro.core.transient import fleet_jit
from repro.core.transient.fleet import FleetSim, SimWorker
from repro.core.transient.draws import FleetDraws


@pytest.fixture(scope="module")
def session():
    return Session.from_arch("qwen3-1.7b", smoke=True)


def _mk_sim(seed=0, n_workers=4, handover=True, chaos=None):
    sp = 15.61
    workers = [SimWorker(i, "v100", "us-central1", sp)
               for i in range(n_workers)]
    return FleetSim(workers, model_gflops=1.54, model_bytes=1.87e6,
                    step_speed_of=lambda g: sp,
                    checkpoint_interval_steps=4000, checkpoint_time_s=3.84,
                    n_ps=1, seed=seed, handover=handover, replace=True,
                    price_of={"v100": 0.74}, provider="gcp", chaos=chaos)


def _timeline(faults, sim=None, seed=0):
    sim = sim or _mk_sim()
    return FaultTimeline(faults, sim._roster, seed=seed)


# ------------------------------------------------- timeline semantics
def test_timeline_factors_are_half_open_windows():
    tl = _timeline((StragglerFault(1.0, 1.0, slot=2, speed_factor=0.3),
                    PSCrash(0.5, 1.0, 0.25),
                    CheckpointOutage(2.0, 0.5)))
    t = np.array([0.0, 3600.0, 7200.0 - 1e-6, 7200.0])
    m = tl.speed_mults(t)
    assert m.shape == (4, 4)
    assert m[0, 2] == 1.0 and m[1, 2] == 0.3 and m[2, 2] == 0.3
    assert m[3, 2] == 1.0                       # end instant excluded
    assert np.all(m[:, [0, 1, 3]] == 1.0)       # only slot 2 touched
    pf = tl.ps_factor(np.array([1799.0, 1800.0, 5399.0, 5400.0]))
    assert list(pf) == [1.0, 0.25, 0.25, 1.0]
    blk = tl.ckpt_blocked(np.array([7199.0, 7200.0, 9000.0 - 1e-3, 9000.0]))
    assert list(blk) == [False, True, True, False]
    # boundaries: every factor-change instant, sorted, in seconds
    assert list(tl.boundaries_s) == [1800.0, 3600.0, 5400.0, 7200.0, 9000.0]
    nb = tl.next_boundary(np.array([0.0, 1800.0, 9000.0]))
    assert list(nb) == [1800.0, 3600.0, np.inf]


def test_timeline_rejects_out_of_roster_slot():
    with pytest.raises(ValueError, match="slot 9"):
        _timeline((StragglerFault(0.0, 1.0, slot=9, speed_factor=0.5),))


def test_hazard_faults_add_no_boundaries():
    tl = _timeline((PreemptionWave(1.0, 2.0, 4.0),
                    PriceSpike(0.5, 1.0, 2.0)))
    assert tl.boundaries_s.size == 0
    assert np.isinf(tl.next_boundary(np.array([0.0]))).all()


def test_truth_spans_record_fault_fields():
    tl = _timeline((PreemptionWave(0.5, 1.0, 6.0, region="us-central1"),
                    PSCrash(1.0, 0.5, 0.0)))
    spans = tl.truth_spans()
    assert spans[0]["kind"] == "preemption_wave"
    assert spans[0]["start_s"] == 1800.0 and spans[0]["end_s"] == 5400.0
    assert spans[0]["region"] == "us-central1"
    assert spans[0]["hazard_per_h"] == 6.0
    assert spans[1] == {"kind": "ps_crash", "start_s": 3600.0,
                        "end_s": 5400.0, "capacity_factor": 0.0}


# ------------------------------------------- keyed hazard determinism
def test_initial_transform_is_pure_function_of_seed():
    wave = PreemptionWave(0.0, 2.0, 5.0)
    lt = np.full((16, 4), np.inf)
    a = _timeline((wave,), seed=7).transform_initial(lt)
    b = _timeline((wave,), seed=7).transform_initial(lt)
    c = _timeline((wave,), seed=8).transform_initial(lt)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.all(a[np.isfinite(a)] <= 2.0)     # kills land in the window
    assert np.isfinite(a).any()                 # hazard 5/h over 2h: some do


def test_region_filter_spares_other_regions():
    sim = FleetSim([SimWorker(0, "v100", "us-central1", 15.0),
                    SimWorker(1, "v100", "europe-west1", 15.0)],
                   model_gflops=1.54, model_bytes=1.87e6,
                   step_speed_of=lambda g: 15.0,
                   checkpoint_interval_steps=4000, checkpoint_time_s=3.84,
                   n_ps=1, seed=0, price_of={"v100": 0.74}, provider="gcp")
    tl = FaultTimeline((PreemptionWave(0.0, 8.0, 50.0,
                                       region="us-central1"),),
                       sim._roster, seed=0)
    lt = np.full((64, 2), np.inf)
    out = tl.transform_initial(lt)
    assert np.isfinite(out[:, 0]).all()         # hazard 50/h: all killed
    assert np.isinf(out[:, 1]).all()            # other region untouched


def test_join_transform_independent_of_batch_grouping():
    """The keyed-stream contract: transforming joins one at a time must
    equal transforming them as one batch (the event engine asks per join,
    the batched engine per generation)."""
    tl = _timeline((PriceSpike(0.0, 4.0, 3.0),), seed=3)
    lt = np.array([5.0, np.inf, 1.5, 8.0])
    trajs = np.array([0, 0, 1, 2])
    slots = np.array([0, 1, 2, 3])
    gens = np.array([1, 1, 2, 1])
    hours = np.array([0.5, 1.0, 0.0, 2.0])
    batch = tl.transform_joins(lt, trajs, slots, gens, hours)
    single = np.array([
        tl.transform_joins(lt[i:i + 1], trajs[i:i + 1], slots[i:i + 1],
                           gens[i:i + 1], hours[i:i + 1])[0]
        for i in range(4)])
    np.testing.assert_array_equal(batch, single)


@pytest.mark.parametrize("n_words", [1, 3, 4, 6, 9])
def test_keyed_uniforms_equal_numpy_seedsequence_draws(n_words):
    """The array form of the keyed draws is numpy's scalar stream, bit for
    bit: key lengths below, at and above the SeedSequence pool size, with
    zeros and words near 2**32."""
    rng = np.random.default_rng(n_words)
    keys = rng.integers(0, 2 ** 32, (200, n_words), dtype=np.int64)
    keys[::3, 0] = 0
    keys[1::5, -1] = 2 ** 32 - 1
    got = keyed_uniforms(*keys.T)
    want = [np.random.default_rng(np.random.SeedSequence(
        tuple(int(w) for w in k))).random() for k in keys]
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="2\\*\\*32"):
        keyed_uniforms(2 ** 32, 1)


def test_join_uniform_matrix_equals_transform_joins_draws(monkeypatch):
    """Every level of the jit engine's join-uniform matrix holds exactly
    the scalar draw `transform_joins` makes for the same (fault, traj,
    slot, gen) key — two hazard faults in two regions, behind a
    non-hazard fault so the fault index is not the hazard index."""
    roster = [(i, "v100", r, 15.0) for i, r in enumerate(
        ("us-central1", "europe-west1", "us-central1", "europe-west1"))]
    tl = FaultTimeline((PSCrash(0.0, 1.0, 0.5),
                        PreemptionWave(0.5, 1.0, 6.0, region="us-central1"),
                        PriceSpike(1.0, 2.0, 3.0, region="europe-west1")),
                       roster, seed=2 ** 32 + 17)
    n, G = 5, 8
    got = tl.join_uniform_matrix(n, range(1, G + 1))
    assert got.shape == (G, n, 4, 2)
    seen = []
    apply = FaultTimeline._apply_hazard

    def spy(lt, U, f, h0):
        seen.append(np.array(U))
        return apply(lt, U, f, h0)

    monkeypatch.setattr(FaultTimeline, "_apply_hazard", staticmethod(spy))
    gens, trajs, slots = (a.ravel() for a in np.meshgrid(
        np.arange(1, G + 1), np.arange(n), np.arange(4), indexing="ij"))
    tl.transform_joins(np.ones(gens.size), trajs, slots, gens,
                       np.zeros(gens.size))
    assert len(seen) == 2
    for k, U in enumerate(seen):
        np.testing.assert_array_equal(got[..., k], U.reshape(G, n, 4))


def test_jit_pools_levels_independent_of_depth(monkeypatch):
    """A deeper pool build holds the shallower one's levels unchanged, so
    the level-paging schedule cannot change results; the `fleet.pools`
    span's `draws` stat counts G x S x n x F join uniforms."""
    stats = []

    @contextlib.contextmanager
    def record(name, **kw):
        stats.append((name, kw))
        yield

    monkeypatch.setattr(fleet_jit, "TraceAnnotation", record)
    sim = _mk_sim(seed=5)
    sim.chaos = _timeline((PreemptionWave(0.25, 1.0, 6.0),
                           PriceSpike(0.5, 2.0, 2.0)), sim=sim, seed=9)
    n, S, F = 5, 4, 2
    with enable_x64(True):
        shallow = fleet_jit._pools(FleetDraws(sim, n, 0.0), 4, True)
        deep = fleet_jit._pools(FleetDraws(sim, n, 0.0), 8, True)
        for name, arr in shallow.items():
            np.testing.assert_array_equal(np.asarray(deep[name])[:4 * S],
                                          np.asarray(arr))
        assert deep["join_U"].shape == (8 * S, n, F)
    assert stats == [("fleet.pools", {"levels": G, "draws": G * S * n * F})
                     for G in (4, 8)]


# ------------------------------------------------- engine parity
def test_standalone_run_matches_ensemble_of_one():
    """`FleetSim.run` under chaos builds its own single-trajectory
    `FleetDraws`, so it must reproduce `run_many(1)` on both engines."""
    faults = (PreemptionWave(0.25, 1.0, 6.0),)

    def fresh():
        sim = _mk_sim()
        sim.chaos = _timeline(faults, sim=sim)
        return sim

    solo = fresh().run(300_000, max_hours=8.0)
    ens_b = fresh().run_many(300_000, 1, max_hours=8.0)
    ens_e = fresh().run_many(300_000, 1, max_hours=8.0, engine="event")
    for r in (ens_b.results[0], ens_e.results[0]):
        assert r.revocations == solo.revocations
        assert r.replacements == solo.replacements
        assert r.total_time_s == pytest.approx(solo.total_time_s, rel=1e-9)


@pytest.mark.parametrize("name", list_scenarios())
def test_every_scenario_holds_engine_parity(session, name):
    """Per-trajectory revocation/replacement/steps counts must be equal
    and times bit-close on both engines, for every registered scenario —
    and the scorecard's ensemble summaries must equal the event loop's."""
    sc = get_scenario(name)
    a = _run_sim(session, sc, 4, seed=1)
    assert a["parity"]["counts_equal"]
    assert a["parity"]["time_max_rel_err"] < 1e-9
    # the card's ensembles (jit) summarize exactly as the event loop's
    event = {}
    for key, chaos in (("faulted", True), ("baseline", False)):
        sim, n_steps = session._fleet_sim(
            n_workers=sc.n_workers, gpu=sc.gpu, region=sc.region,
            steps=sc.total_steps, seed=1, handover=sc.handover,
            provider=sc.provider)
        if chaos:
            sim.chaos = sc.timeline(sim._roster, seed=1)
        event[key] = _ens_summary(sim.run_many(
            n_steps, 4, max_hours=sc.max_hours, engine="event"))
    assert a["faulted"] == event["faulted"]
    assert a["baseline"] == event["baseline"]


def test_dead_ps_stalls_for_the_window(session):
    """Capacity 0 for an hour must cost the run ~the whole window (plus
    nothing else: no revocations are scripted)."""
    card = _run_sim(session, get_scenario("dead_ps"), 4, seed=0)
    assert card["impact"]["extra_time_s"] == pytest.approx(3600.0, abs=600)
    # no scripted hazard — only stock lifetimes that now fire because the
    # stalled run ends later can add the odd revocation
    assert card["impact"]["extra_revocations"] <= 1.0


# ------------------------------------------------- scenario registry
def test_registry_lists_builtins_and_rejects_duplicates():
    names = list_scenarios()
    assert len(names) >= 6
    for expected in ("regional_wave", "price_spike", "dead_ps", "ps_crash",
                     "straggler", "ckpt_outage", "wave_price_combo"):
        assert expected in names
    with pytest.raises(ValueError, match="already registered"):
        @register_scenario
        def dup():
            return Scenario(name="regional_wave", description="dup")
    with pytest.raises(ValueError, match="unknown scenario"):
        get_scenario("nope")


def test_liveplan_truth_pairs_spans():
    plan = LivePlan(
        n_steps=100,
        faults=(LiveFault(10, "ps_crash", {"capacity_factor": 0.1}),
                LiveFault(40, "ps_recover"),
                LiveFault(20, "straggler", {"slot": 1,
                                            "speed_factor": 0.5}),
                LiveFault(60, "ckpt_outage")))
    spans = {s["kind"]: s for s in plan.truth()}
    assert spans["ps_crash"]["start_step"] == 10
    assert spans["ps_crash"]["end_step"] == 40          # paired
    assert spans["straggler"]["end_step"] == 100        # unpaired -> n_steps
    assert spans["straggler"]["slot"] == 1
    assert spans["ckpt_outage"]["end_step"] == 100


# ------------------------------------------------- evaluator
def _span(kind, start, end, **kw):
    return {"kind": kind, "start_step": start, "end_step": end, **kw}


def test_evaluator_latency_miss_false_alarm_and_wrong_action():
    truth = [_span("ps_crash", 20, 60),
             _span("straggler", 120, 160, slot=1)]
    history = [
        ("detection", {"step": 30, "bottleneck": True,
                       "action": "enable_compression"}),      # latency 10
        ("detection", {"step": 90, "bottleneck": True,
                       "action": "add_parameter_server"}),    # false alarm
        ("detection", {"step": 50, "bottleneck": False}),     # not counted
        ("mitigation", {"action": "enable_compression"}),
    ]
    s = score_history(history, truth)
    assert s["detections"] == 2
    assert s["detection_latency_steps"] == 10
    assert s["missed_detections"] == 1          # straggler span never hit
    assert s["false_alarms"] == 1
    assert s["wrong_actions"] == 0              # compression fits ps_crash
    assert s["actions_applied"] == ["enable_compression"]
    # a PS lever pulled while only the straggler span covers the step
    wrong = score_history(
        [("detection", {"step": 130, "bottleneck": True,
                        "action": "enable_compression"})], truth)
    assert wrong["wrong_actions"] == 1 and wrong["wrong_action_rate"] == 1.0


def test_evaluator_grace_forgives_post_span_decay():
    truth = [_span("straggler", 20, 50, slot=0)]
    late = [("detection", {"step": 55, "bottleneck": True,
                           "action": "replace_worker"})]
    strict = score_history(late, truth, grace=0)
    lenient = score_history(late, truth, grace=10)
    assert strict["false_alarms"] == 1 and strict["missed_detections"] == 1
    assert lenient["false_alarms"] == 0 and lenient["missed_detections"] == 0


def test_evaluator_counts_checkpoint_failures_inside_outage():
    truth = [_span("ckpt_outage", 20, 45)]
    history = [("checkpoint_failed", {"step": s, "failures": i + 1})
               for i, s in enumerate((25, 30, 35, 40, 45))]
    history.append(("checkpoint_failed", {"step": 90, "failures": 6}))
    s = score_history(history, truth)
    assert s["spans"][0]["checkpoint_failures"] == 5
    assert s["checkpoint_failures"] == 6        # global count keeps all
    assert s["missed_detections"] == 0          # outages aren't detectable


# ------------------------------------------------- live runs, end to end
def test_live_ps_crash_walks_the_compression_ladder(session):
    """The headline loop: a silent PS slowdown detected from measurement
    alone, attributed to the PS, mitigated by walking none -> int8 ->
    topk, after which the payload shrink restores full speed."""
    card = run_scenario(get_scenario("ps_crash"), session=session,
                        samples=4, smoke=True)
    live = card["live"]
    assert card["smoke"]["passed"], card["smoke"]["failures"]
    assert live["actions_applied"] == ["enable_compression",
                                       "enable_compression"]
    assert live["final_compression"] == "topk"
    assert live["missed_detections"] == 0
    assert live["false_alarms"] == 0
    assert live["detection_latency_steps"] == 0
    assert live["faults"] == [{"fault": "ps_crash", "step": 20,
                               "capacity_factor": 0.1}]


def test_live_straggler_is_not_blamed_on_the_ps(session):
    card = run_scenario(get_scenario("straggler"), session=session,
                        samples=4, smoke=True)
    live = card["live"]
    assert card["smoke"]["passed"], card["smoke"]["failures"]
    assert live["actions_applied"] == []        # no PS lever fits
    assert live["wrong_actions"] == 0
    assert live["missed_detections"] == 0
    assert live["final_compression"] == "none"


def test_live_ckpt_outage_fails_saves_and_stays_quiet(session):
    card = run_scenario(get_scenario("ckpt_outage"), session=session,
                        samples=4, smoke=True)
    live = card["live"]
    assert card["smoke"]["passed"], card["smoke"]["failures"]
    assert live["checkpoint_failures"] == 5     # every save in 20..45
    assert live["false_alarms"] == 0            # invisible to the profiler
    assert {"fault": "ckpt_outage", "step": 20} in live["faults"]
    assert {"fault": "ckpt_recover", "step": 45} in live["faults"]


def test_inject_fault_rejects_unknown_kind():
    import tempfile

    from repro.configs import RunConfig, get_config
    from repro.core.trainer import TransientTrainer
    from repro.data.pipeline import ShardedLoader, SyntheticTokenSource

    cfg = get_config("qwen3-1.7b", smoke=True)
    run = RunConfig(total_steps=4, warmup_steps=1, checkpoint_interval=0,
                    checkpoint_dir=tempfile.mkdtemp(), lr=1e-3, zero1=False)
    tr = TransientTrainer(cfg, run, ShardedLoader(
        SyntheticTokenSource(cfg.vocab_size, 24), 8))
    with pytest.raises(ValueError, match="unknown fault kind"):
        tr.inject_fault("gamma_ray")
    tr.inject_fault("ckpt_outage", step=3)
    assert tr.ckpt_outage and tr.faults == [{"fault": "ckpt_outage",
                                             "step": 3}]
    tr.inject_fault("ckpt_recover", step=4)
    assert not tr.ckpt_outage


def test_scorecard_is_deterministic(session):
    a = run_scenario(get_scenario("ps_crash"), session=session,
                     samples=4, seed=0, smoke=True)
    b = run_scenario(get_scenario("ps_crash"), session=session,
                     samples=4, seed=0, smoke=True)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
