"""Fleet traffic: ensembles of the configuration's job on the jitted engine,
one call after another.

Each call builds the program's `FleetSim` from the numbers the
configuration states (workers, speeds, checkpoint interval and time,
model size, price, provider) and scores one ensemble of `samples`
trajectories with `FleetSim.run_many(engine="jit")`, the call that
`Session.simulate`, `Session.plan(score="sim")` and the chaos runner make
underneath. A call has a fresh pair of seeds drawn from `--seed` and the
call's index, so no call reuses another's draws. Set-up makes one call of
its own, which compiles and warms every program the window's calls use.
The engine's pools of replacement draws start at a few generations and
double, with a new program, whenever a chain outgrows them, so under a
fault timeline the set-up call runs the same waves at `warm_hazard_per_h`:
its chains grow deeper than any window's, and every depth a window can
meet is compiled. The traffic file holds:

    samples   trajectories per call, at most `fleet_jit.COMPACT_MIN` (at
              more, the engine compacts to widths that hang on the draws,
              and a window could meet a width set-up did not compile)
    faults    hazard faults, each {"kind": "preemption_wave", "start_h",
              "duration_h", "hazard_per_h", "region"}
    compare   trajectories of the window, drawn from the seed, that are
              compared with the plain reference once the window has closed
    warm_hazard_per_h
              the waves' hazard in the set-up call (with faults only)

`fleet_trajectories_per_s` is the trajectories of all completed calls
over the time from the window's start to the end of the last call.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.harness import Check, Outcome, memory_peak_bytes

# the host spans this driver writes into a traced window
SPANS = ("simulate_call",)

_RESULT_KEYS = ("total_time_s", "steps_done", "revocations", "replacements",
                "monetary_cost")


def call_seeds(seed: int, i: int):
    """The engine's seed and the fault timeline's seed of call `i`."""
    a, b = np.random.SeedSequence([seed, i]).generate_state(2)
    return int(a), int(b)


class Fleet:
    """The deployment the configuration states, as the program builds it."""

    def __init__(self, c: dict, faults: List[dict], n: int):
        from repro.core.transient import fleet_jit
        from repro.providers import get_provider
        if n > fleet_jit.COMPACT_MIN:
            raise ValueError(f"{n} samples per call is more than "
                             f"{fleet_jit.COMPACT_MIN}: the engine would "
                             "compact to widths that hang on the draws")
        self.c, self.faults, self.n = c, faults, n
        self.provider = get_provider(c["provider"])
        self.roster = [(i, c["gpu"], c["region"], c["worker_steps_per_s"])
                       for i in range(c["n_workers"])]

    def timeline(self, tseed: int, hazard_per_h=None):
        from repro.chaos.injectors import FaultTimeline, PreemptionWave
        if not self.faults:
            return None
        waves = []
        for f in self.faults:
            if f["kind"] != "preemption_wave":
                raise ValueError(f"no fault kind {f['kind']!r}")
            waves.append(PreemptionWave(f["start_h"], f["duration_h"],
                                        hazard_per_h or f["hazard_per_h"],
                                        region=f["region"]))
        return FaultTimeline(waves, self.roster, seed=tseed)

    def simulate(self, seed: int, tseed: int, warm_hazard_per_h=None):
        from repro.core.transient.fleet import FleetSim, SimWorker
        c = self.c
        speed = c["worker_steps_per_s"]
        sim = FleetSim(
            [SimWorker(*w) for w in self.roster],
            model_gflops=c["model_gflops"], model_bytes=c["model_bytes"],
            step_speed_of=lambda gpu: speed,
            checkpoint_interval_steps=c["checkpoint_interval_steps"],
            checkpoint_time_s=c["checkpoint_s"], seed=seed,
            replace=c["replace"], handover=c["handover"],
            price_of={c["gpu"]: c["price_per_h"]}, provider=self.provider,
            chaos=self.timeline(tseed, warm_hazard_per_h))
        return sim.run_many(c["total_steps"], self.n,
                            max_hours=c["max_hours"],
                            start_hour=c["start_hour"], engine="jit").results


def answers(results, rows) -> List[dict]:
    return [{k: getattr(results[j], k) for k in _RESULT_KEYS} for j in rows]


def compare_rows(seed: int, calls: int, n: int, m: int
                 ) -> Dict[int, np.ndarray]:
    """The trajectories compared, drawn from the seed over all `calls`
    calls of `n`: call index (from 1) -> its rows."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    picks = np.sort(rng.choice(calls * n, size=min(m, calls * n),
                               replace=False))
    out: Dict[int, np.ndarray] = {}
    for p in picks:
        out.setdefault(int(p) // n + 1, []).append(int(p) % n)
    return {i: np.array(r) for i, r in out.items()}


def reference_answers(ref, c, faults, n, seeds, rows, dtype=np.float64):
    draws = ref.Draws(c, faults, n, *seeds)
    return [ref.trajectory(c, draws, int(j), dtype) for j in rows]


def run(cell) -> Outcome:
    c, tf = cell.config, cell.traffic
    n, faults = tf["samples"], tf.get("faults", [])
    fleet = Fleet(c, faults, n)
    cell.mark("import_program")
    fleet.simulate(*call_seeds(cell.seed, 0),
                   warm_hazard_per_h=tf.get("warm_hazard_per_h"))
    cell.mark("warm_call")
    calls = []
    with cell.window():
        while not calls or cell.remaining() > 0:
            seeds = call_seeds(cell.seed, len(calls) + 1)
            with cell.span("simulate_call"):
                calls.append((seeds, fleet.simulate(*seeds)))
    elapsed = cell.window_end - cell.window_start
    peak = memory_peak_bytes()
    failed = sum(not np.isfinite(r.total_time_s)
                 for _, results in calls for r in results)
    ref = cell.reference()
    got, want = [], []
    for i, rows in compare_rows(cell.seed, len(calls), n,
                                tf["compare"]).items():
        seeds, results = calls[i - 1]
        got += answers(results, rows)
        want += reference_answers(ref, c, faults, n, seeds, rows)
    limits = c["limits"]
    checks = [Check(k, v, limits[k]) for k, v in ref.compare(got,
                                                             want).items()]
    return Outcome(
        attempted=n * len(calls), failed=int(failed),
        end_to_end={"fleet_trajectories_per_s": n * len(calls) / elapsed},
        facts={"calls": len(calls), "window_s": elapsed},
        checks=checks, memory_peak_bytes=peak)


def readings(root: str, workload: str, seeds, control_seeds,
             require_tpu: bool = True, emit=print):
    """Program (lower) readings: one call per seed, every trajectory of it
    against the float64 reference. Control (upper) readings: the
    reference with its state in float32, on the same draws."""
    from chipbench import harness

    harness.place_compile_cache(root)
    cell = harness.Cell(root, workload, 0, 0.0, False)
    harness.device_info(require_tpu, cell.workload["chips"])
    c, tf = cell.config, cell.traffic
    ref = cell.reference()
    n, faults = tf["samples"], tf.get("faults", [])
    fl = Fleet(c, faults, n) if seeds else None
    rows = np.arange(n)
    out = []
    for kind, seed_list in (("program", seeds), ("float32", control_seeds)):
        for seed in seed_list:
            seeds2 = call_seeds(seed, 1)
            want = reference_answers(ref, c, faults, n, seeds2, rows)
            if kind == "program":
                got = answers(fl.simulate(*seeds2), rows)
            else:
                got = reference_answers(ref, c, faults, n, seeds2, rows,
                                        np.float32)
            line = {"reading": kind, "seed": seed, **ref.compare(got, want)}
            out.append(line)
            emit(line)
    return out
