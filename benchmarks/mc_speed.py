"""Monte-Carlo engine throughput — the repo's first perf-trajectory
benchmark (docs/performance.md has the methodology and the JSON schema).

Two measurements, written to BENCH_mc.json at the repo root (CI's `perf`
job uploads it as an artifact):

* **planner grid** — the §V-C planner's default grid (all regions offering
  the GPU x 8 launch hours x 200 MC samples), timed twice: once through
  the *pinned scalar baseline* (the pre-vectorization per-sample loop,
  reproduced verbatim below: per-sample lifetime-model resolution plus a
  per-index diurnal-thinning rejection loop) and once through the batched
  `plan_launch`. The headline number is the speedup at equal sample
  counts.
* **simulation ensemble** — `FleetSim.run_many` trajectory throughput for
  a 4-worker V100 cluster on its default engine (one call, compile
  included), vs the pre-ensemble pattern of re-building a simulator per
  seed in a Python loop (what `benchmarks/cross_provider.py` did before
  the ensemble API). Informational, not gated.
"""
from __future__ import annotations

import json
import math
import pathlib
import time
from typing import List

import numpy as np

from repro.core.perf_model.cluster_model import (Eq4Inputs, WorkerSpec,
                                                 cluster_speed,
                                                 predict_total_time)
from repro.core.perf_model.speed_model import TABLE1_MODELS, calibrate_generators
from repro.core.scheduler import plan_launch
from repro.core.transient.fleet import FleetSim, SimWorker
from repro.core.transient.replacement import ReplacementModel
from repro.core.transient.revocation import (MAX_LIFETIME_H,
                                             _diurnal_weight)
from repro.core.transient.startup import StartupModel
from repro.providers import get_provider

ROOT = pathlib.Path(__file__).resolve().parent.parent
OUT_PATH = ROOT / "BENCH_mc.json"

# The default planner-grid workload (matches scheduler_gains.py).
N_W = 256_000
I_C = 4_000
T_C = 3.84
N_WORKERS = 4
SAMPLES = 200
HOURS = [0, 3, 6, 9, 12, 15, 18, 21]
ENSEMBLE_N = 64


# ------------------------------------------------- pinned scalar baseline
def reference_scalar_lifetime(m, rng: np.random.Generator,
                              start_hour: float = 0.0) -> float:
    """One lifetime from the pre-vectorization `LifetimeModel.sample`
    loop, reproduced verbatim (per-index rejection, up to 64 rounds).
    Kept here — not in the library — as the frozen baseline every future
    BENCH_mc.json entry is measured against, and as the reference
    distribution for the sampler-parity tests."""
    u = rng.uniform(size=1)
    out = np.full(1, np.inf)
    revoked = u < m.p24
    uu = rng.uniform(size=1)
    raw24 = 1.0 - math.exp(-((MAX_LIFETIME_H / m.lam) ** m.k))
    t = m.lam * (-np.log(1.0 - uu * raw24)) ** (1.0 / m.k)
    for i in np.where(revoked)[0]:
        accepted = False
        for _ in range(64):
            w = float(_diurnal_weight(m.gpu, start_hour + t[i]))
            if rng.uniform() < w / 2.5:
                accepted = True
                break
            uu_i = rng.uniform()
            t[i] = m.lam * (-np.log(1.0 - uu_i * raw24)) ** (1.0 / m.k)
        if not accepted and float(_diurnal_weight(
                m.gpu, start_hour + t[i])) == 0.0:
            t[i] += 4.0
        out[i] = min(t[i], MAX_LIFETIME_H)
    return float(out[0])


def scalar_expected_revocations(prov, region: str, gpu: str,
                                start_hour: float, run_hours: float,
                                n_workers: int, samples: int,
                                seed: int) -> float:
    """Pre-PR `expected_revocations_mc`: one model resolution and one
    scalar rejection loop per sample."""
    rng = np.random.default_rng(seed)
    horizon = min(run_hours, prov.max_lifetime_hours)
    hits = 0
    for _ in range(samples):
        model = prov.lifetime_model(region, gpu)   # re-resolved per sample
        lt = reference_scalar_lifetime(model, rng, start_hour)
        if math.isfinite(lt) and lt <= horizon:
            hits += 1
    return n_workers * hits / samples


def scalar_plan_grid(gpu: str, n_workers: int, worker_speed: float,
                     n_w: int, i_c: int, t_c: float, hours: List[int],
                     seed: int, prov) -> List[dict]:
    """Pre-PR `plan_launch` (compute-only MC horizon, scalar MC)."""
    startup = StartupModel(seed, prov)
    repl = ReplacementModel(seed, prov)
    price = prov.price(gpu)
    sp = cluster_speed([WorkerSpec(gpu, worker_speed)] * n_workers)
    base_hours = n_w / sp / 3600.0
    t_p = startup.mean_total(gpu)
    t_s = repl.cold_start_s(1.54)
    plans = []
    for region in prov.regions_offering(gpu):
        for h in hours:
            n_r = scalar_expected_revocations(prov, region, gpu, float(h),
                                              base_hours, n_workers,
                                              SAMPLES, seed)
            probs = [n_r / n_workers] * n_workers
            t = predict_total_time(sp, Eq4Inputs(n_w, i_c, t_c, t_p, t_s,
                                                 probs))
            cost = (t / 3600.0) * n_workers * price \
                + n_r * (t_p / 3600.0) * price
            plans.append({"region": region, "hour": h, "cost": cost})
    return plans


# ------------------------------------------------------------ measurement
def _best_of(fn, reps: int = 3) -> float:
    best = math.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_planner_grid(gpu: str = "v100") -> dict:
    prov = get_provider("gcp")
    gens = calibrate_generators()
    sp = 1.0 / gens[gpu].step_time(TABLE1_MODELS["resnet_32"])
    n_cells = len(prov.regions_offering(gpu)) * len(HOURS)
    scalar_s = _best_of(lambda: scalar_plan_grid(
        gpu, N_WORKERS, sp, N_W, I_C, T_C, HOURS, 0, prov))
    batched_s = _best_of(lambda: plan_launch(
        gpu, N_WORKERS, sp, n_w=N_W, i_c=I_C, t_c=T_C, hours=HOURS,
        seed=0, provider=prov, samples=SAMPLES))
    return {
        "gpu": gpu, "cells": n_cells, "samples_per_cell": SAMPLES,
        "scalar_s": round(scalar_s, 4), "batched_s": round(batched_s, 4),
        "speedup": round(scalar_s / batched_s, 1),
        "cells_per_s": round(n_cells / batched_s, 1),
    }


def bench_ensemble(n: int = ENSEMBLE_N) -> dict:
    gens = calibrate_generators()
    c_m = TABLE1_MODELS["resnet_32"]
    sp = 1.0 / gens["v100"].step_time(c_m)
    steps = 100_000

    def mk(seed):
        workers = [SimWorker(i, "v100", "us-central1", sp)
                   for i in range(N_WORKERS)]
        return FleetSim(workers, model_gflops=c_m, model_bytes=1.87e6,
                        step_speed_of=lambda g: sp,
                        checkpoint_interval_steps=I_C, checkpoint_time_s=T_C,
                        seed=seed, price_of={"v100": 0.74})

    t0 = time.perf_counter()
    ens = mk(0).run_many(steps, n, max_hours=100.0)
    batched_s = time.perf_counter() - t0
    # the pre-ensemble pattern: one simulator re-built and run per seed
    t0 = time.perf_counter()
    for s in range(n):
        mk(s).run(steps, max_hours=100.0)
    loop_s = time.perf_counter() - t0
    return {
        "trajectories": n, "steps": steps,
        "batched_s": round(batched_s, 4), "loop_s": round(loop_s, 4),
        "traj_per_s": round(n / batched_s, 1),
        "time_p50_s": round(ens.stats.time_p50_s, 1),
        "time_p90_s": round(ens.stats.time_p90_s, 1),
        "revocations_mean": round(ens.stats.revocations_mean, 2),
    }


def run():
    grid = bench_planner_grid()
    ens = bench_ensemble()
    payload = {
        "schema": 3,
        "planner_grid": grid,
        "ensemble": ens,
    }
    OUT_PATH.write_text(json.dumps(payload, indent=2) + "\n")
    return [
        {"name": f"mc_speed/planner_grid/{grid['gpu']}",
         "value": grid["speedup"],
         "derived": (f"{grid['cells']} cells x {grid['samples_per_cell']} "
                     f"samples: scalar {grid['scalar_s']}s -> batched "
                     f"{grid['batched_s']}s ({grid['cells_per_s']} cells/s; "
                     f"speedup x)")},
        {"name": "mc_speed/ensemble/v100x4",
         "value": ens["traj_per_s"],
         "derived": (f"{ens['trajectories']} trajectories in "
                     f"{ens['batched_s']}s (loop: {ens['loop_s']}s); "
                     f"p50={ens['time_p50_s']}s p90={ens['time_p90_s']}s "
                     f"E[rev]={ens['revocations_mean']} (traj/s)")},
    ]


if __name__ == "__main__":
    for r in run():
        print(r)
    print(f"wrote {OUT_PATH}")
