"""Random draws shared by the fleet engines (`FleetSim.run_many`).

The jitted lockstep engine (`fleet_jit.run_jit`) and the per-trajectory
event loop (`FleetSim.run`, the parity oracle) read every random value of
an ensemble from one `FleetDraws`:

* initial lifetimes are pre-drawn as ONE `(n, slots)` matrix (one batched
  `RevocationSampler.lifetimes` call per (region, gpu) roster group);
* every replacement-chain draw (startup stages after a revocation, the
  cold start, the replacement's own lifetime at its realized join hour)
  comes from a stream keyed by (seed, trajectory, slot, generation), so
  both engines consume identical values no matter in which order they
  reach each event.

That makes the two engines trajectory-for-trajectory comparable:
identical revocation/replacement counts, and times/costs equal up to
float association order (docs/DESIGN.md §2; tests/test_fleet_engines.py
and tests/test_engine_parity.py pin both properties).
"""
from __future__ import annotations

from typing import TYPE_CHECKING

import numpy as np

from repro.core.transient.revocation import RevocationSampler
from repro.core.transient.startup import POST_REVOCATION_COV

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.core.transient.fleet import FleetSim


class FleetDraws:
    """Deterministic random draws shared by both fleet engines.

    One instance covers one `run_many` call:

    * `initial` — the pre-drawn `(n, slots)` initial-lifetime matrix
      (hours, np.inf = survived), one batched
      `RevocationSampler.lifetimes` call per (region, gpu) roster group.
    * replacement chains — per *generation level* g (the g-th
      replacement a slot has seen), one pre-drawn pool: an `(n, slots)`
      matrix of join delays (post-revocation §V-B startup + Fig 10 cold
      start, drawn in one vectorized call) and an `(n, slots, K)` block
      of uniforms the lifetime law turns into the replacement's lifetime
      at its realized join hour (`LifetimeLaw.sample_from_uniforms`).
      Pools are keyed on (seed, level) and drawn lazily, so both engines
      read identical values no matter in which order they reach each
      event. Laws without a uniform-block sampler fall back to one
      counter-based stream per (trajectory, slot, generation).
    """

    def __init__(self, sim: "FleetSim", n: int, start_hour: float):
        self.seed = int(sim.seed)
        self.provider = sim.provider
        self.model_gflops = sim.model_gflops
        self.start_hour = float(start_hour)
        # the sim's chaos timeline (hazard faults transform every lifetime
        # this object hands out — both engines therefore share identical
        # post-fault revocation timelines by construction)
        self.chaos = getattr(sim, "chaos", None)
        roster = sim._roster
        self.n = n
        self.n_slots = len(roster)
        groups = {}
        for idx, (_, gpu, region, _) in enumerate(roster):
            groups.setdefault((region, gpu), []).append(idx)
        samp = RevocationSampler(self.seed, self.provider)
        pre = np.empty((n, len(roster)))
        for (region, gpu), idxs in groups.items():
            draws = samp.lifetimes(region, gpu, n * len(idxs), start_hour)
            pre[:, idxs] = draws.reshape(n, len(idxs))
        if self.chaos is not None:
            pre = self.chaos.transform_initial(pre)
        self.initial = pre
        # per-slot laws and delay moments, resolved once
        self._laws = [self.provider.lifetime_model(region, gpu)
                      for _, gpu, region, _ in roster]
        anchors = self.provider.replacement_anchors()
        cold = anchors.cold_start_s(self.model_gflops)
        self._delay_means = np.array(
            [list(self.provider.startup_stages(gpu).means(True)) + [cold]
             for _, gpu, _, _ in roster])                       # (S, 4)
        self._delay_sds = self._delay_means * POST_REVOCATION_COV
        self._delay_sds[:, 3] = 0.05 * self._delay_means[:, 3]
        # laws without a uniform-block sampler draw from per-key fallback
        # streams, so their pool contribution is a single placeholder
        # column, not the default 33
        self._K = max([getattr(law, "SAMPLE_UNIFORMS_K", 33)
                       if getattr(law, "sample_from_uniforms", None)
                       is not None else 1
                       for law in self._laws], default=1)
        self._levels = {}
        # restore-retry stall pools (repro.resilience): keyed like the
        # replacement levels, shared by both engines
        self._stall_levels = {}

    def _level(self, gen: int):
        """The pre-drawn pool of generation level `gen` (lazy, keyed on
        (seed, gen) — identical whenever and from whichever engine it is
        first requested)."""
        pool = self._levels.get(gen)
        if pool is None:
            rng = np.random.default_rng(np.random.SeedSequence(
                (self.seed % (2 ** 32), 0x6A01, gen)))
            stages = rng.normal(self._delay_means, self._delay_sds,
                                size=(self.n, self.n_slots, 4))
            delays = np.maximum(1.0, stages).sum(axis=-1)
            uniforms = rng.random((self.n, self.n_slots, self._K))
            pool = self._levels[gen] = (delays, uniforms)
        return pool

    def _fallback_rng(self, traj: int, slot: int,
                      gen: int) -> np.random.Generator:
        return np.random.default_rng(np.random.SeedSequence(
            (self.seed % (2 ** 32), int(traj), int(slot), int(gen))))

    def replacement_delay(self, traj: int, slot: int, gen: int) -> float:
        """Seconds from a revocation to the replacement's join: the §V-B
        post-revocation startup (4x CoV) plus the Fig 10 cold start —
        the same laws `StartupModel.sample(after_revocation=True)` and
        `ReplacementModel.sample(cold=True)` draw from. The draw is fully
        determined by the slot (a replacement inherits its slot's gpu)."""
        return float(self._level(gen)[0][traj, slot])

    def _raw_join_lifetime(self, traj: int, slot: int, gen: int,
                           start_hour_abs: float) -> float:
        law = self._laws[slot]
        if getattr(law, "sample_from_uniforms", None) is None:
            return float(law.sample(self._fallback_rng(traj, slot, gen),
                                    1, start_hour_abs)[0])
        U = self._level(gen)[1][traj, slot][None, :]
        return float(law.sample_from_uniforms(
            U, np.array([start_hour_abs]))[0])

    def join_lifetime(self, traj: int, slot: int, gen: int,
                      start_hour_abs: float) -> float:
        """The replacement's own lifetime (hours; np.inf = survived),
        drawn at its realized local join hour so diurnal laws see it —
        from the slot's own (region, gpu) lifetime law. Chaos hazard
        faults (keyed on (seed, fault, traj, slot, gen)) then thin it."""
        lt = self._raw_join_lifetime(traj, slot, gen, start_hour_abs)
        if self.chaos is not None:
            lt = float(self.chaos.transform_joins(
                np.array([lt]), np.array([traj]), np.array([slot]),
                np.array([gen]),
                np.array([start_hour_abs - self.start_hour]))[0])
        return lt

    def restore_stall_level(self, res, gen: int) -> np.ndarray:
        """The `(n, slots)` restore-retry stall matrix (seconds) for
        generation level `gen` — the keyed-deterministic delay a
        stock-chief trajectory spends retrying its checkpoint reload
        after the slot's generation-`gen` occupant is revoked (lazy,
        keyed on (seed + resilience seed, gen); identical whichever
        engine asks first)."""
        pool = self._stall_levels.get(gen)
        if pool is None:
            from repro.resilience.policy import stall_pool
            pool = self._stall_levels[gen] = stall_pool(
                res, self.seed, self.n, self.n_slots, gen)
        return pool

    def restore_stall(self, res, traj: int, slot: int, gen: int) -> float:
        return float(self.restore_stall_level(res, gen)[traj, slot])
