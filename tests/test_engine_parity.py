"""Differential parity harness — the lock on `run_many`'s jit engine.

`fleet_jit.run_jit` compiles the lockstep rounds into one jitted JAX
program; this file pins its contract against the event-loop oracle: for
ANY (provider, fleet shape, horizon, compression, chaos scenario, seed)
both must report exactly equal per-trajectory
revocation/replacement/step counts — they consume the same `FleetDraws`
uniform streams — and times/costs within float association tolerance.

Three layers:

* a committed seed corpus (`CORPUS`) of configurations that each pin a
  distinct code path (stock-chief step loss, AWS graceful window,
  no-replace frozen fleets, single-slot fleets, compression in the PS
  cap, deep replacement chains that force jit pool paging);
* a `hypothesis` fuzz sweep over the same axes;
* schedule-invariance regressions: results must be byte-identical
  whatever the `jax_enable_x64` global flag and whatever compaction
  schedule the host driver happens to pick, and exact under trajectory
  sharding with pad rows (multidevice CI job).
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.chaos.scenarios import get_scenario, list_scenarios
from repro.core.transient import fleet_jit
from repro.core.transient.fleet import FleetSim, SimWorker
from repro.core.transient.draws import FleetDraws
from repro.core.transient.fleet_jit import run_jit
from repro.providers import get_provider


def _mk_sim(seed=0, provider="gcp", region="us-central1", gpu="v100",
            sp=4.56, n_workers=4, handover=True, replace=True, i_c=4000,
            t_c=3.84, grad_compression="none", model_bytes=1.87e6):
    workers = [SimWorker(i, gpu, region, sp) for i in range(n_workers)]
    return FleetSim(workers, model_gflops=1.54, model_bytes=model_bytes,
                    step_speed_of=lambda g: sp,
                    checkpoint_interval_steps=i_c, checkpoint_time_s=t_c,
                    n_ps=1, seed=seed, handover=handover, replace=replace,
                    price_of={gpu: 0.74}, provider=provider,
                    grad_compression=grad_compression)


def _assert_parity(mk, run_args, engines=("event",)):
    """run_many on the jit engine and every engine in `engines` from
    identical fresh sims; counts must be exactly equal, continuous stats
    equal up to float association order."""
    j = mk().run_many(*run_args, engine="jit")
    for other in engines:
        o = mk().run_many(*run_args, engine=other)
        assert [r.revocations for r in j.results] == \
            [r.revocations for r in o.results], f"vs {other}"
        assert [r.replacements for r in j.results] == \
            [r.replacements for r in o.results], f"vs {other}"
        assert [r.steps_done for r in j.results] == \
            pytest.approx([r.steps_done for r in o.results], abs=1)
        np.testing.assert_allclose([r.total_time_s for r in j.results],
                                   [r.total_time_s for r in o.results],
                                   rtol=1e-9)
        np.testing.assert_allclose([r.monetary_cost for r in j.results],
                                   [r.monetary_cost for r in o.results],
                                   rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose([r.checkpoint_time_s for r in j.results],
                                   [r.checkpoint_time_s for r in o.results],
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose([r.lost_steps for r in j.results],
                                   [r.lost_steps for r in o.results],
                                   rtol=1e-6, atol=1e-6)
        # recovery accrual (zeros when resilience is off) is part of the
        # contract too: pause windows and retry-delayed restores must be
        # engine-independent (docs/resilience.md)
        np.testing.assert_allclose([r.paused_s for r in j.results],
                                   [r.paused_s for r in o.results],
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose([r.restore_delay_s for r in j.results],
                                   [r.restore_delay_s for r in o.results],
                                   rtol=1e-6, atol=1e-6)
        assert j.stats.finished == o.stats.finished
    return j


# --------------------------------------------------------- seed corpus
# Each row froze a distinct engine code path while the jit engine was
# built; keep appending the shrunk form of any future fuzz failure.
#   (provider, region, gpu, workers, handover, replace, compression,
#    i_c, horizon_h, start_h, seed)
CORPUS = [
    ("gcp", "us-central1", "v100", 4, True, True, "none",
     4000, 48.0, 0.0, 0),         # the paper's baseline cell
    ("gcp", "europe-west1", "k80", 8, False, True, "none",
     1000, 32.0, 0.0, 3),         # revocation-heavy + stock-chief loss
    ("gcp", "us-west1", "k80", 2, True, False, "none",
     4000, 100.0, 7.0, 5),        # replace=False frozen dead fleets
    ("aws", "us-east-1", "v100", 6, False, True, "none",
     1000, 80.0, 9.0, 2),         # 2-min warning: graceful checkpoint
    ("azure", "southeastasia", "v100", 4, False, True, "int8",
     4000, 60.0, 13.5, 1),        # compressed PS cap in the sim
    ("azure", "southcentralus", "v100", 1, True, True, "none",
     4000, 12.0, 23.75, 7),       # single slot, censoring, hour wrap
]


@pytest.mark.parametrize("prov,region,gpu,nw,ho,rep,comp,i_c,mh,sh,seed",
                         CORPUS)
def test_corpus_three_engine_parity(prov, region, gpu, nw, ho, rep, comp,
                                    i_c, mh, sh, seed):
    def mk():
        return _mk_sim(seed=seed, provider=prov, region=region, gpu=gpu,
                       n_workers=nw, handover=ho, replace=rep,
                       grad_compression=comp, i_c=i_c)
    _assert_parity(mk, (250_000, 12, mh, sh))


@pytest.mark.slow
@given(cell=st.sampled_from([("gcp", "us-central1", "v100"),
                             ("gcp", "europe-west1", "k80"),
                             ("aws", "us-east-1", "v100"),
                             ("azure", "southeastasia", "v100")]),
       n_workers=st.sampled_from([1, 3, 4]),
       horizon=st.sampled_from([12.0, 48.0, 96.0]),
       compression=st.sampled_from(["none", "int8"]),
       handover=st.sampled_from([True, False]),
       start_hour=st.sampled_from([0.0, 7.0, 13.5]),
       seed=st.integers(0, 1000))
@settings(max_examples=8, deadline=None)
def test_fuzz_three_engine_parity(cell, n_workers, horizon, compression,
                                  handover, start_hour, seed):
    prov, region, gpu = cell

    def mk():
        return _mk_sim(seed=seed, provider=prov, region=region, gpu=gpu,
                       n_workers=n_workers, handover=handover,
                       grad_compression=compression)
    _assert_parity(mk, (150_000, 12, horizon, start_hour))


# -------------------------------------------------- resilience parity
@pytest.mark.parametrize("quorum", [0.6, 0.9])
def test_resilience_three_engine_parity(quorum):
    """Recovery semantics ride the same contract: keyed restore-retry
    stalls after stock-chief revocations and quorum pause windows must
    reproduce bit-for-bit on both engines (`paused_s` /
    `restore_delay_s` asserted inside `_assert_parity`), and arming a
    `ResilienceConfig` must not perturb counts or completion times'
    agreement."""
    from repro.resilience import (DegradationPolicy, ResilienceConfig,
                                  RetryPolicy)
    res = ResilienceConfig(
        retry=RetryPolicy(max_attempts=3, base_delay_s=60.0,
                          multiplier=2.0, max_delay_s=600.0, jitter=0.5,
                          deadline_s=1800.0),
        degradation=DegradationPolicy(quorum=quorum, shrink_below=0.95,
                                      shrink_factor=0.7),
        restore_fail_p=0.7, seed=5)

    def mk():
        sim = _mk_sim(seed=5, region="europe-west1", gpu="k80",
                      n_workers=8, handover=False, i_c=1000)
        sim.resilience = res
        return sim
    j = _assert_parity(mk, (250_000, 12, 32.0, 0.0))
    # the config is chosen so the stall channel always fires; the pause
    # channel needs the tight quorum (8-worker fleets rarely drop below
    # 60 % alive with replacement on)
    assert sum(r.restore_delay_s for r in j.results) > 0.0
    if quorum >= 0.9:
        assert sum(r.paused_s for r in j.results) > 0.0


# ----------------------------------------------------- chaos scenarios
@pytest.mark.slow
@pytest.mark.parametrize("name", list_scenarios())
def test_jit_parity_every_chaos_scenario(name):
    """All seven scripted fault timelines run under the jit engine —
    fault-window factors as piecewise-constant device tables, keyed
    join-hazard uniforms as a pool matrix — bit-identically to the
    event loop."""
    sc = get_scenario(name)
    region = sc.region or get_provider(sc.provider).default_region

    def mk():
        sim = _mk_sim(seed=11, provider=sc.provider, region=region,
                      gpu=sc.gpu, n_workers=sc.n_workers,
                      handover=sc.handover)
        sim.chaos = sc.timeline(sim._roster, seed=11)
        return sim
    _assert_parity(mk, (sc.total_steps, 8, sc.max_hours))


@pytest.mark.slow
def test_chaos_scorecard_truth_hash_engine_and_x64_independent():
    """The scorecard a chaos run emits — truth spans, `truth_hash`,
    ensemble summaries — must be byte-identical whatever the global
    `jax_enable_x64` flag, with its jit-vs-event parity probe exact."""
    from repro.api import Session
    from repro.chaos.runner import _run_sim

    ses = Session.from_arch("qwen3-1.7b", smoke=True)
    sc = get_scenario("regional_wave")
    cards = {}
    prev = jax.config.jax_enable_x64
    try:
        for x64 in (False, True):
            jax.config.update("jax_enable_x64", x64)
            cards[x64] = _run_sim(ses, sc, 8, seed=1)
    finally:
        jax.config.update("jax_enable_x64", prev)
    ref_card = cards[False]
    for key, card in cards.items():
        assert card["truth_hash"] == ref_card["truth_hash"], key
        assert card["truth"] == ref_card["truth"], key
        assert card["faulted"] == ref_card["faulted"], key
        assert card["baseline"] == ref_card["baseline"], key
        assert card["parity"]["counts_equal"], key
        assert card["parity"]["time_max_rel_err"] < 1e-9, key


# ------------------------------------------------ schedule invariances
def _raw_bytes_equal(a, b):
    assert set(a) == set(b)
    for key in a:
        assert np.asarray(a[key]).tobytes() == \
            np.asarray(b[key]).tobytes(), key


def test_results_independent_of_x64_flag():
    """run_jit pins float64 via `jax.experimental.enable_x64` no matter
    the global flag, so the raw result arrays are byte-identical with
    and without `jax_enable_x64`."""
    sim = _mk_sim(seed=6, region="europe-west1", gpu="k80", n_workers=4)
    draws = FleetDraws(sim, 32, 0.0)
    prev = jax.config.jax_enable_x64
    try:
        jax.config.update("jax_enable_x64", False)
        a = run_jit(sim, 200_000, 32, 48.0, draws=draws, raw=True)
        jax.config.update("jax_enable_x64", True)
        b = run_jit(sim, 200_000, 32, 48.0, draws=draws, raw=True)
    finally:
        jax.config.update("jax_enable_x64", prev)
    _raw_bytes_equal(a, b)


@pytest.mark.slow
def test_results_independent_of_compaction_schedule():
    """The host driver pages finished trajectories out between
    `lax.while_loop` entries; the body math is width-blind, so forcing
    aggressive compaction (COMPACT_MIN=8 on a 96-wide ensemble, many
    re-entries at shrinking widths) must reproduce the single-entry
    result byte for byte."""
    sim = _mk_sim(seed=6, region="europe-west1", gpu="k80", n_workers=4)
    draws = FleetDraws(sim, 96, 0.0)
    base = run_jit(sim, 150_000, 96, 48.0, draws=draws, raw=True)
    old = fleet_jit.COMPACT_MIN
    fleet_jit.COMPACT_MIN = 8
    fleet_jit._compiled.cache_clear()   # cond() captures it at trace time
    try:
        comp = run_jit(sim, 150_000, 96, 48.0, draws=draws, raw=True)
    finally:
        fleet_jit.COMPACT_MIN = old
        fleet_jit._compiled.cache_clear()
    _raw_bytes_equal(base, comp)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >1 device "
                           "(xla_force_host_platform_device_count)")
def test_sharded_pad_rows_match_event_oracle():
    """Under trajectory sharding, n not divisible by the device count
    pads the state with inert rows; parity with the event loop proves
    the pads never leak into real trajectories."""
    n = 13                       # 13 % 4 != 0 on the 4-device CI job
    def mk():
        return _mk_sim(seed=4, region="europe-west1", gpu="k80",
                       n_workers=4)
    _assert_parity(mk, (150_000, n, 48.0, 0.0))


def test_run_jit_rejects_empty_ensemble():
    with pytest.raises(ValueError, match="at least one trajectory"):
        run_jit(_mk_sim(), 1000, 0)


def test_unsupported_law_family_points_at_event():
    """A roster whose lifetime law has no jittable port must fail in
    `run_jit` with actionable advice, not compile garbage."""
    class _OddLaw:
        pass

    class _OddProvider:
        name = "odd"
        warning_seconds = 0.0
        graceful_checkpoint_on_warning = False

        def lifetime_model(self, region, gpu):
            return _OddLaw()

    sim = _mk_sim()
    sim.provider = _OddProvider()
    assert fleet_jit.law_family(sim) is None
    with pytest.raises(ValueError,
                       match="no jittable port.*event loop"):
        run_jit(sim, 1000, 4)
