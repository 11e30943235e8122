"""Fleet engine tests: `run_many`'s default (jit) engine against the
event-loop oracle (exact revocation/replacement counts under the shared
`FleetDraws` streams, KS agreement on time/cost distributions),
censoring, all three providers including the AWS 2-minute warning
window, custom lifetime laws on the event loop, `grad_compression` in
the simulated PS term, the `score="sim"` planner and the Session paths
that reach the jit engine, and the vectorized `ps_queue_sim` against a
pinned copy of the retired heap loop."""
import heapq
import math

import numpy as np
import pytest

from repro.core.ps_async import ps_queue_sim
from repro.core.perf_model.cluster_model import (PS_NET_BYTES_PER_S,
                                                 PSBottleneckModel)
from repro.core.scheduler import plan_launch
from repro.core.transient import fleet_jit
from repro.core.transient.draws import FleetDraws
from repro.core.transient.fleet import FleetEnsemble, FleetSim, SimWorker
from repro.providers import registry
from repro.providers.base import LifetimeLaw
from repro.providers.gcp import GCPPreemptible


def _mk_sim(seed=0, provider="gcp", region="us-central1", gpu="v100",
            sp=15.61, n_workers=4, handover=True, replace=True, i_c=4000,
            t_c=3.84, n_tensors=0, grad_compression="none",
            model_bytes=1.87e6, n_ps=1):
    workers = [SimWorker(i, gpu, region, sp) for i in range(n_workers)]
    return FleetSim(workers, model_gflops=1.54, model_bytes=model_bytes,
                    step_speed_of=lambda g: sp,
                    checkpoint_interval_steps=i_c, checkpoint_time_s=t_c,
                    n_ps=n_ps, seed=seed, handover=handover, replace=replace,
                    price_of={gpu: 0.74}, provider=provider,
                    n_tensors=n_tensors, grad_compression=grad_compression)


def _both(sim_kwargs, run_args):
    a = _mk_sim(**sim_kwargs).run_many(*run_args)
    b = _mk_sim(**sim_kwargs).run_many(*run_args, engine="event")
    return a, b


def _count_run_jit(monkeypatch):
    """Trajectory counts of every `fleet_jit.run_jit` call from here on."""
    calls, real = [], fleet_jit.run_jit

    def counted(sim, total_steps, n, *args, **kwargs):
        calls.append(n)
        return real(sim, total_steps, n, *args, **kwargs)
    monkeypatch.setattr(fleet_jit, "run_jit", counted)
    return calls


def _ks_distance(a, b):
    grid = np.sort(np.concatenate([a, b]))
    fa = np.searchsorted(np.sort(a), grid, side="right") / len(a)
    fb = np.searchsorted(np.sort(b), grid, side="right") / len(b)
    return float(np.max(np.abs(fa - fb)))


# ------------------------------------------------- engine parity (exact)
@pytest.mark.parametrize("provider,region,gpu,handover", [
    ("gcp", "us-central1", "v100", True),
    ("gcp", "europe-west1", "k80", False),   # revocation-heavy + stock chief
    ("aws", "us-east-1", "v100", True),
    ("azure", "southeastasia", "v100", False),
])
def test_engines_agree_exactly_on_shared_draws(provider, region, gpu,
                                               handover):
    """Both engines consume the same `FleetDraws` streams, so identical
    pre-drawn lifetimes and replacement chains must give EXACT
    revocation/replacement counts per trajectory; times/costs agree up
    to float association order (the jit round walks checkpoint pauses
    in closed form, the event loop incrementally)."""
    kw = dict(seed=3, provider=provider, region=region, gpu=gpu, sp=4.56,
              handover=handover)
    a, b = _both(kw, (400_000, 24, 60.0, 7.0))
    assert [r.revocations for r in a.results] == \
        [r.revocations for r in b.results]
    assert [r.replacements for r in a.results] == \
        [r.replacements for r in b.results]
    np.testing.assert_allclose([r.total_time_s for r in a.results],
                               [r.total_time_s for r in b.results],
                               rtol=1e-9)
    np.testing.assert_allclose([r.monetary_cost for r in a.results],
                               [r.monetary_cost for r in b.results],
                               rtol=1e-9)
    np.testing.assert_allclose([r.checkpoint_time_s for r in a.results],
                               [r.checkpoint_time_s for r in b.results],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose([r.lost_steps for r in a.results],
                               [r.lost_steps for r in b.results],
                               rtol=1e-6, atol=1e-6)
    assert a.stats.finished == b.stats.finished


def test_engines_agree_distributionally_ks():
    """Beyond per-trajectory equality: the time/cost samples of the two
    engines pass a two-sample KS test at the ~99.9% level (trivially,
    given exactness — this guards a future engine change that keeps
    counts but drifts the continuous laws)."""
    kw = dict(seed=11, region="us-central1", gpu="v100", sp=15.61,
              n_workers=4)
    a, b = _both(kw, (600_000, 96, 80.0, 12.0))
    ta = np.array([r.total_time_s for r in a.results])
    tb = np.array([r.total_time_s for r in b.results])
    ca = np.array([r.monetary_cost for r in a.results])
    cb = np.array([r.monetary_cost for r in b.results])
    n_eff = len(ta) / 2.0
    assert _ks_distance(ta, tb) < 1.95 / math.sqrt(n_eff)
    assert _ks_distance(ca, cb) < 1.95 / math.sqrt(n_eff)


def test_stock_chief_recompute_parity_and_positive():
    """handover=False on a revocation-heavy cell: the stock chief loses
    steps (Fig 11) identically in both engines."""
    kw = dict(seed=1, region="europe-west1", gpu="k80", sp=4.56,
              n_workers=8, handover=False, i_c=1000)
    a, b = _both(kw, (300_000, 32, 80.0, 0.0))
    lost_a = [r.lost_steps for r in a.results]
    np.testing.assert_allclose(lost_a, [r.lost_steps for r in b.results],
                               rtol=1e-6, atol=1e-6)
    assert sum(lost_a) > 0          # the pathology actually exercised
    np.testing.assert_allclose([r.recompute_time_s for r in a.results],
                               [r.recompute_time_s for r in b.results],
                               rtol=1e-6, atol=1e-6)


def test_aws_warning_window_graceful_checkpoint():
    """AWS's 2-minute notice covers T_c, so even stock identity-reuse
    (handover=False) loses no steps — in both engines; GCP's 30 s notice
    is ignored by stock frameworks, so the same setup there loses steps."""
    kw = dict(seed=2, provider="aws", region="us-east-1", gpu="v100",
              sp=4.56, n_workers=6, handover=False, i_c=1000, t_c=60.0)
    a, b = _both(kw, (400_000, 32, 80.0, 9.0))
    assert sum(r.revocations for r in a.results) > 0
    assert all(r.lost_steps == 0 for r in a.results)
    assert all(r.lost_steps == 0 for r in b.results)
    gcp = _mk_sim(seed=2, region="europe-west1", gpu="k80", sp=4.56,
                  n_workers=6, handover=False, i_c=1000, t_c=60.0)
    ens = gcp.run_many(400_000, 32, max_hours=80.0)
    assert sum(r.lost_steps for r in ens.results) > 0


def test_batched_censoring_reported():
    ens = _mk_sim(seed=0).run_many(10_000_000, 8, max_hours=0.5)
    assert isinstance(ens, FleetEnsemble)
    assert ens.stats.finished == 0
    assert all(r.steps_done < 10_000_000 for r in ens.results)
    # censoring parity with the oracle
    ev = _mk_sim(seed=0).run_many(10_000_000, 8, max_hours=0.5,
                                  engine="event")
    assert [r.steps_done for r in ens.results] == \
        pytest.approx([r.steps_done for r in ev.results], abs=1)


def test_no_replace_freezes_dead_fleet():
    """replace=False: once every worker is revoked the trajectory
    freezes where it stands (the event loop's `sp <= 0 and not q`
    break) — identically in both engines."""
    kw = dict(seed=5, region="europe-west1", gpu="k80", sp=4.56,
              n_workers=2, replace=False)
    a, b = _both(kw, (5_000_000, 24, 100.0, 0.0))
    np.testing.assert_allclose([r.total_time_s for r in a.results],
                               [r.total_time_s for r in b.results],
                               rtol=1e-9)
    assert [r.steps_done for r in a.results] == \
        pytest.approx([r.steps_done for r in b.results], abs=1)
    assert any(r.steps_done < 5_000_000 for r in a.results)


def test_engines_agree_on_finished_for_awkward_step_counts():
    """Float-fuzzed completions: steps accumulates float increments, so
    a finished run can sit an ulp below total_steps — both engines must
    round it up (the event loop used to truncate to total-1 and report
    finished=0 for completed runs)."""
    for total in (12345, 4321, 99991):
        kw = dict(seed=0, sp=3.7, n_workers=4, i_c=997, t_c=1.3)
        a = _mk_sim(**kw).run_many(total, 6, max_hours=1000.0)
        b = _mk_sim(**kw).run_many(total, 6, max_hours=1000.0,
                                   engine="event")
        assert a.stats.finished == b.stats.finished == 6
        assert [r.steps_done for r in a.results] == \
            [r.steps_done for r in b.results]


@pytest.mark.parametrize("engine", ["warp", "batched"])
def test_run_many_rejects_unknown_engine(engine):
    with pytest.raises(ValueError, match="unknown engine"):
        _mk_sim().run_many(1000, 2, engine=engine)


class _CappedExponential(LifetimeLaw):
    """A custom law: exponential lifetimes, survived past 24 h. It has
    no uniform-block sampler and no jittable port."""

    def __init__(self, hazard_per_h: float):
        self.hazard_per_h = hazard_per_h

    def cdf(self, t_hours):
        t = np.minimum(np.asarray(t_hours, float), 24.0)
        return 1.0 - np.exp(-self.hazard_per_h * t)

    def prob_revoked_within(self, t_hours):
        return float(self.cdf(t_hours))

    def sample(self, rng, n=1, start_hour=0.0):
        t = rng.exponential(1.0 / self.hazard_per_h, int(n))
        return np.where(t > 24.0, np.inf, t)

    def mean_time_to_revocation(self):
        return 1.0 / self.hazard_per_h


class _CustomMarket(GCPPreemptible):
    name = "custom-exp"
    display_name = "custom exponential market"

    def lifetime_model(self, region, gpu):
        self.check_offered(region, gpu)
        return _CappedExponential(0.3)


def test_custom_law_ensemble_runs_on_the_event_loop(monkeypatch):
    """A registered provider whose law has no jittable port runs its
    ensembles through `run_many` on the event loop, with no option set,
    and equals `run_many(engine="event")`."""
    monkeypatch.setattr(registry, "_REGISTRY", dict(registry._REGISTRY))
    registry.register_provider(_CustomMarket())
    calls = _count_run_jit(monkeypatch)
    kw = dict(seed=3, provider="custom-exp", sp=4.56, n_workers=4)
    assert fleet_jit.law_family(_mk_sim(**kw)) is None
    a, b = _both(kw, (400_000, 12, 60.0, 5.0))
    assert calls == []
    assert sum(r.revocations for r in a.results) > 0
    assert [r.revocations for r in a.results] == \
        [r.revocations for r in b.results]
    assert [r.total_time_s for r in a.results] == \
        [r.total_time_s for r in b.results]
    assert [r.monetary_cost for r in a.results] == \
        [r.monetary_cost for r in b.results]


def test_single_run_unchanged_by_engine_dispatch():
    """`run()` keeps its historic sequential streams bit-for-bit: the
    engine dispatch and shared draws only apply to `run_many`."""
    a = _mk_sim(seed=2).run(200_000, max_hours=100.0)
    _ = _mk_sim(seed=2).run_many(200_000, 4, max_hours=100.0)
    b = _mk_sim(seed=2).run(200_000, max_hours=100.0)
    assert a.total_time_s == b.total_time_s
    assert a.revocations == b.revocations


# ------------------------------------------- grad_compression in the sim
def test_sim_ps_term_sees_grad_compression():
    """A PS-bound fleet (big payload, 1 PS) speeds up under int8
    compression exactly as `PSBottleneckModel` predicts — the simulator
    no longer ignores the scheme the §VI-B predictor applies."""
    kw = dict(model_bytes=4.0e8, n_workers=8, sp=15.61, i_c=100_000,
              seed=4)
    plain = _mk_sim(**kw)
    comp = _mk_sim(grad_compression="int8", **kw)
    cap_plain = PSBottleneckModel(4.0e8, 1).capacity_steps_per_s()
    cap_comp = PSBottleneckModel(4.0e8, 1,
                                 compression="int8").capacity_steps_per_s()
    assert cap_comp == pytest.approx(4 * cap_plain)
    e_plain = plain.run_many(50_000, 8, max_hours=200.0)
    e_comp = comp.run_many(50_000, 8, max_hours=200.0)
    # int8 quarters the wire bytes -> 4x the PS ceiling -> ~4x faster
    ratio = e_plain.stats.time_mean_s / e_comp.stats.time_mean_s
    assert ratio > 2.0
    # both engines apply the same compressed capacity
    e_event = comp.run_many(50_000, 8, max_hours=200.0, engine="event")
    np.testing.assert_allclose([r.total_time_s for r in e_comp.results],
                               [r.total_time_s for r in e_event.results],
                               rtol=1e-9)


def test_session_simulate_engine_and_compression(tmp_path):
    from repro.api import Session
    s = Session.from_arch("qwen3-1.7b", total_steps=300,
                          checkpoint_interval=100, zero1=False)
    kw = dict(n_workers=2, gpu="v100", steps=300, seed=0)
    ens_b = s.simulate(samples=6, **kw)
    sim, n_steps = s._fleet_sim(**kw)
    ens_e = sim.run_many(n_steps, 6, engine="event")
    np.testing.assert_allclose(
        [r.total_time_s for r in ens_b.results],
        [r.total_time_s for r in ens_e.results], rtol=1e-9)
    assert [r.revocations for r in ens_b.results] == \
        [r.revocations for r in ens_e.results]
    comp = Session.from_arch("qwen3-1.7b", total_steps=300,
                             checkpoint_interval=100, zero1=False,
                             grad_compression="int8")
    ens_c = comp.simulate(n_workers=2, gpu="v100", steps=300, seed=0,
                          samples=6)
    # same model, compressed wire: never slower than uncompressed
    assert ens_c.stats.time_mean_s <= ens_b.stats.time_mean_s + 1e-9


# ------------------------------------------------- sim-scored planner
def test_plan_launch_sim_score_golden_and_fields():
    """us-west1 is by far the most stable K80 region (Table V), so the
    simulation-backed grid must rank it fastest and least-revoked. (The
    realized-$ ranking is allowed to differ from Eq (4)'s: a revoked
    worker accrues no GPU-hours while its replacement spins up, so a
    churny region can be marginally cheaper in $ yet slower — exactly
    the distinction simulation-backed scoring surfaces.) Every
    sim-scored plan carries ordered percentiles and its censoring
    count."""
    best, plans = plan_launch("k80", 4, 4.56, n_w=400_000, i_c=4000,
                              t_c=3.84, hours=[0, 12], seed=0,
                              samples=96, score="sim")
    fastest = {}
    for p in plans:
        cur = fastest.get(p.region)
        if cur is None or p.expected_time_s < cur.expected_time_s:
            fastest[p.region] = p
    uw = fastest["us-west1"]
    assert all(uw.expected_time_s <= p.expected_time_s + 1e-9
               for p in fastest.values())
    assert all(uw.expected_revocations <= p.expected_revocations + 1e-9
               for p in fastest.values())
    for p in plans:
        assert p.score == "sim"
        assert p.samples == 96
        assert p.time_p50_s <= p.time_p90_s
        assert p.cost_p50 <= p.cost_p90
        assert 0 <= p.finished <= 96
        assert p.expected_cost > 0
    assert best.expected_cost == min(p.expected_cost for p in plans)


def test_plan_launch_sim_engines_agree():
    """Every sim-scored cell equals the event loop's ensemble of the
    same fleet, seed and launch hour."""
    _, plans = plan_launch("v100", 2, 15.61, n_w=200_000, i_c=4000,
                           t_c=3.84, hours=[6], seed=1, samples=24,
                           score="sim", max_sim_hours=48.0)
    assert len(plans) > 1
    for p in plans:
        sim = _mk_sim(seed=1, region=p.region, gpu="v100", sp=15.61,
                      n_workers=2)
        sim.price_of = {"v100": sim.provider.price("v100")}
        st = sim.run_many(200_000, 24, max_hours=48.0,
                          start_hour=float(p.launch_hour),
                          engine="event").stats
        assert p.expected_revocations == st.revocations_mean
        assert p.expected_time_s == pytest.approx(st.time_mean_s,
                                                  rel=1e-9)
        assert p.expected_cost == pytest.approx(st.cost_mean, rel=1e-9)


def test_plan_launch_sim_rejects_bad_score():
    with pytest.raises(ValueError, match="unknown score"):
        plan_launch("v100", 2, 10.0, n_w=1000, i_c=100, t_c=1.0,
                    hours=[0], score="montecarlo")


def test_session_plan_sim_score():
    from repro.api import Session
    s = Session.from_arch("qwen3-1.7b", total_steps=20_000,
                          checkpoint_interval=1000, zero1=False)
    best, plans = s.plan(gpu="v100", n_workers=2, steps=20_000,
                         hours=[0, 12], samples=16, score="sim")
    assert best.score == "sim"
    assert len(plans) == 2 * len({p.region for p in plans})
    assert all(p.time_p90_s >= p.time_p50_s for p in plans)
    # sim scoring always models the Fig 4 PS capacity (1 PS default) —
    # the same configuration simulate() uses, so an explicit n_ps=1
    # changes nothing
    explicit, _ = s.plan(gpu="v100", n_workers=2, steps=20_000,
                         hours=[0, 12], samples=16, score="sim", n_ps=1)
    assert explicit.expected_time_s == best.expected_time_s
    assert explicit.expected_cost == best.expected_cost


def test_session_fleet_paths_reach_run_jit(monkeypatch):
    """`Session.plan(score="sim")`, `Session.simulate(samples>1)` and
    `Session.chaos` run their ensembles on the jit engine by default;
    `simulate(samples=1)` stays the single `FleetSim.run` trajectory."""
    from repro.api import Session
    from repro.chaos.runner import PARITY_SAMPLES
    s = Session.from_arch("qwen3-1.7b", total_steps=300,
                          checkpoint_interval=100, zero1=False)
    calls = _count_run_jit(monkeypatch)
    s.plan(gpu="v100", n_workers=2, steps=300, hours=[0],
           region="us-central1", samples=6, score="sim")
    assert calls == [6]
    s.simulate(n_workers=2, gpu="v100", steps=300, samples=5)
    assert calls == [6, 5]
    s.simulate(n_workers=2, gpu="v100", steps=300)
    assert calls == [6, 5]
    card = s.chaos("dead_ps", live=False, samples=4)
    # faulted and baseline ensembles, then the parity probe's jit half
    assert calls == [6, 5, 4, 4, PARITY_SAMPLES]
    assert card["scenarios"]["dead_ps"]["sim"]["parity"]["counts_equal"]


def test_cli_plan_forwards_n_ps(capsys):
    """`repro plan --n-ps` must reach Session.plan (it was parsed and
    silently dropped before); the plan parser defaults it to None so
    eq4 planning stays uncapped unless asked."""
    from repro import __main__ as main_mod
    parser = main_mod.build_parser()
    args = parser.parse_args(["plan", "--gpu", "v100", "--workers", "2",
                              "--samples", "8"])
    assert args.n_ps is None
    args = parser.parse_args(["plan", "--gpu", "v100", "--workers", "2",
                              "--samples", "8", "--n-ps", "2"])
    assert args.n_ps == 2
    assert main_mod._cmd_plan(args) == 0
    assert "best:" in capsys.readouterr().out


# ------------------------------------- vectorized ps_queue_sim parity
def _heap_reference(compute_times, model_bytes, n_ps=1,
                    ps_bw=PS_NET_BYTES_PER_S, steps=400, seed=0,
                    n_tensors=0, grad_compression="none"):
    """The retired per-push heap loop, pinned verbatim as the parity
    reference for the array-reduction stepper."""
    n = len(compute_times)
    service = PSBottleneckModel(model_bytes, n_ps, ps_bw,
                                n_tensors=n_tensors,
                                compression=grad_compression
                                ).service_time_s()
    q = []
    rng = np.random.default_rng(seed)
    for w, ct in enumerate(compute_times):
        heapq.heappush(q, (ct * rng.uniform(0.2, 1.0), w))
    ps_free_at = 0.0
    done_steps = np.zeros(n, int)
    finish_t = np.zeros(n, float)
    busy = 0.0
    while q:
        t, w = heapq.heappop(q)
        start = max(t, ps_free_at)
        ps_free_at = start + service
        busy += service
        done_steps[w] += 1
        finish_t[w] = start
        if done_steps[w] < steps:
            heapq.heappush(q, (start + compute_times[w], w))
    eff = {w: finish_t[w] / done_steps[w] for w in range(n)}
    total = float(finish_t.max())
    return eff, float(done_steps.sum()) / total, busy / total


@pytest.mark.parametrize("cts,mb,kw", [
    ([0.082] * 4, 1.87e6, dict(n_tensors=97)),      # unsaturated, uniform
    ([0.082] * 12, 1.87e6, dict(n_tensors=97)),     # saturated plateau
    ([0.05, 0.08, 0.22, 0.3, 0.082], 1.87e6, dict(n_tensors=97)),  # hetero
    ([0.1], 9.8e7, {}),                             # n=1 network-bound
    ([0.02] * 8, 9.8e7, dict(grad_compression="int8")),
    ([0.082] * 6, 1.87e6, dict(n_ps=2, n_tensors=97)),
    ([0.219, 0.219, 0.082, 0.064], 1.87e6, dict(n_tensors=97)),  # §II mix
])
def test_ps_queue_sim_matches_heap_reference(cts, mb, kw):
    for steps in (60, 300):
        res = ps_queue_sim(cts, mb, steps=steps, **kw)
        eff, cs, util = _heap_reference(cts, mb, steps=steps, **kw)
        np.testing.assert_allclose(
            [res.worker_step_time[w] for w in range(len(cts))],
            [eff[w] for w in range(len(cts))], rtol=1e-9)
        assert res.cluster_speed == pytest.approx(cs, rel=1e-9)
        assert res.ps_utilization == pytest.approx(util, rel=1e-9)


def test_ps_queue_sim_rejects_nonpositive_steps():
    """steps <= 0 must fail loudly instead of hanging the array rounds
    (workers would start with nothing to serve and never drain)."""
    with pytest.raises(ValueError, match="at least one step"):
        ps_queue_sim([0.1] * 12, 1.87e6, steps=0)
    with pytest.raises(ValueError, match="at least one step"):
        ps_queue_sim([0.1, 0.2], 1.87e6, steps=-3)


def test_sim_stats_revocations_stderr_matches_planner():
    """SimStats owns the trajectory-sample SEM; the sim-scored planner
    reads it instead of re-deriving it."""
    ens = _mk_sim(seed=1, region="europe-west1", gpu="k80", sp=4.56,
                  n_workers=4).run_many(200_000, 16, max_hours=60.0)
    revs = [float(r.revocations) for r in ens.results]
    expect = float(np.std(revs, ddof=1)) / math.sqrt(len(revs))
    assert ens.stats.revocations_stderr == pytest.approx(expect)
    _, plans = plan_launch("k80", 4, 4.56, n_w=200_000, i_c=4000,
                           t_c=3.84, hours=[0], seed=1, samples=16,
                           score="sim")
    assert all(p.revocation_stderr >= 0.0 for p in plans)


def test_ps_queue_sim_fuzz_against_reference():
    """Random populations/paces: aggregates match the pinned heap loop
    within the documented float-association bound (~0.5% for short
    runs; near-coincident arrivals may serve in either order)."""
    rng = np.random.default_rng(11)
    for _ in range(15):
        nn = int(rng.integers(1, 24))
        r = rng.random()
        cts = ([float(rng.uniform(0.01, 0.4))] * nn if r < 0.4 else
               list(rng.choice([0.219, 0.082, 0.064], nn)) if r < 0.7 else
               list(rng.uniform(0.01, 0.4, nn)))
        mb = float(rng.choice([1.87e6, 5e7, 9.8e7]))
        kw = dict(n_tensors=int(rng.integers(0, 120)),
                  n_ps=int(rng.integers(1, 3)))
        res = ps_queue_sim(cts, mb, steps=120, **kw)
        eff, cs, util = _heap_reference(cts, mb, steps=120, **kw)
        np.testing.assert_allclose(
            [res.worker_step_time[w] for w in range(len(cts))],
            [eff[w] for w in range(len(cts))], rtol=1e-2)
        assert res.cluster_speed == pytest.approx(cs, rel=5e-3)
        assert res.ps_utilization == pytest.approx(util, rel=1e-2)


# ------------------------------------------------ FleetDraws invariants
def test_fleet_draws_deterministic_and_order_independent():
    sim = _mk_sim(seed=9)
    d1 = FleetDraws(sim, 16, 0.0)
    d2 = FleetDraws(sim, 16, 0.0)
    np.testing.assert_array_equal(d1.initial, d2.initial)
    # pool values do not depend on request order
    a = d1.replacement_delay(3, 1, 2)
    b = d2.replacement_delay(0, 0, 1)
    assert d2.replacement_delay(3, 1, 2) == a
    assert d1.replacement_delay(0, 0, 1) == b
    la = d1.join_lifetime(5, 2, 1, 13.25)
    assert d2.join_lifetime(5, 2, 1, 13.25) == la

