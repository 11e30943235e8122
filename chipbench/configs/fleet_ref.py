"""Plain reference for a transient training fleet: one trajectory at a
time, as a discrete-event loop, from the deployment the configuration
file states and draws made here from the seed.

Nothing here imports the program. The semantics are those of CM-DARE's
fleet (paper §V-§VI): workers train at a fixed speed each, the cluster
at the sum of its live workers' speeds under the parameter server's
capacity; every `checkpoint_interval_steps` steps the run pauses
`checkpoint_s`; a revoked worker is replaced after a startup delay, and
when the chief is revoked a survivor takes the checkpoint lease over
(handover), so nothing is recomputed. The run ends at `total_steps` or
at `max_hours`.

The draws follow the keyed scheme the system documents, so that the same
seed gives the same trajectories:

* initial lifetimes: one `numpy.random.default_rng(seed)` stream through
  the truncated-Weibull law with diurnal thinning (pooled rejection),
  `n x workers` values in trajectory-major order;
* replacement generation g: `default_rng(SeedSequence((seed, 0x6A01, g)))`
  gives `(n, workers, 4)` normal startup stages, then `(n, workers, 33)`
  uniforms that the law turns into the replacement's lifetime at its
  local join hour;
* a hazard fault f (a preemption wave) thins initial lifetimes with one
  `(n, workers)` uniform matrix from `SeedSequence((tseed, 0xC4A05, f))`
  and each join's lifetime with the uniform of
  `SeedSequence((tseed, 0xC4A15, f, traj, slot, g))`.

`dtype` sets the precision of the loop's state: float64 as stated, or
float32 for the control.
"""
from __future__ import annotations

import heapq
import math
from typing import Dict, List

import numpy as np

_TAG_POOL = 0x6A01
_TAG_INITIAL = 0xC4A05
_TAG_JOIN = 0xC4A15
_INV_ENVELOPE = 1.0 / 2.5           # thinning envelope: the largest weight
K_UNIFORMS = 33


# ------------------------------------------------------------- the law
def diurnal_weight(gpu: str, hour):
    """Fig 9 local-time revocation weight of each GPU family."""
    h = np.asarray(hour, float) % 24.0
    if gpu == "k80":
        return 1.0 + 1.5 * np.exp(-((h - 10.0) ** 2) / (2 * 2.0 ** 2))
    if gpu == "v100":
        w = 1.0 + 0.6 * np.exp(-((h - 9.0) ** 2) / (2 * 3.0 ** 2))
        return np.where((h >= 16.0) & (h < 20.0), 0.0, w)
    return 1.0 + 0.8 * np.exp(-((h - 13.0) ** 2) / (2 * 4.0 ** 2))


class Law:
    """Weibull(k, lam) truncated at `cap_h`, revoked with probability p24
    within it, thinned by the GPU's diurnal weight."""

    def __init__(self, spec: dict, gpu: str):
        self.k, self.lam, self.p24 = spec["k"], spec["lam"], spec["p24"]
        self.cap = spec["cap_h"]
        self.gpu = gpu
        self.raw = 1.0 - math.exp(-((self.cap / self.lam) ** self.k))

    def inv(self, u):
        return self.lam * (-np.log(1.0 - u * self.raw)) ** (1.0 / self.k)

    def sample_batch(self, rng, n: int, start_hour: float) -> np.ndarray:
        u = rng.uniform(size=n)
        out = np.full(n, np.inf)
        revoked = u < self.p24
        m = int(np.count_nonzero(revoked))
        if m == 0:
            return out
        vals, got = np.empty(m), 0
        for _ in range(16):
            need = m - got
            k = 3 * need + 16
            cand = self.inv(rng.uniform(size=k))
            w = diurnal_weight(self.gpu, start_hour + cand)
            acc = cand[rng.uniform(size=k) < w * _INV_ENVELOPE]
            take = min(acc.size, need)
            vals[got:got + take] = acc[:take]
            got += take
            if got == m:
                break
        if got < m:
            cand = self.inv(rng.uniform(size=m - got))
            w = diurnal_weight(self.gpu, start_hour + cand)
            vals[got:] = np.where(w == 0.0, cand + 4.0, cand)
        out[revoked] = np.minimum(vals, self.cap)
        return out

    def from_uniforms(self, U: np.ndarray, hour: float) -> float:
        """One lifetime from a row of K_UNIFORMS uniforms: column 0 decides
        revocation, then up to 16 (candidate, accept) pairs thin."""
        if not U[0] < self.p24:
            return math.inf
        cand = self.inv(U[1])
        pending = not (U[2] < diurnal_weight(self.gpu, hour + cand)
                       * _INV_ENVELOPE)
        j = 1
        while pending and j < 16:
            cand = self.inv(U[1 + 2 * j])
            pending = not (U[2 + 2 * j] < diurnal_weight(self.gpu, hour + cand)
                           * _INV_ENVELOPE)
            j += 1
        if pending and diurnal_weight(self.gpu, hour + cand) == 0.0:
            cand = cand + 4.0
        return float(min(cand, self.cap))


def thin(lt: float, u: float, fault: dict, h0: float) -> float:
    """An extra exponential clock of the fault's hazard over the part of
    the fault's window the worker is alive in; if it fires first, the
    worker is revoked then."""
    a = max(fault["start_h"], h0)
    b = min(fault["start_h"] + fault["duration_h"], h0 + lt)
    tau = -math.log1p(-u) / fault["hazard_per_h"]
    if b - a > 0 and tau < b - a:
        return min(lt, a + tau - h0)
    return lt


# --------------------------------------------------------------- draws
class Draws:
    """Every random number one ensemble of `n` trajectories consumes."""

    def __init__(self, c: dict, faults: List[dict], n: int, seed: int,
                 tseed: int):
        self.c, self.faults, self.n = c, faults, n
        self.seed, self.tseed = seed % 2 ** 32, tseed % 2 ** 32
        self.S = c["n_workers"]
        self.law = Law(c["lifetime"], c["gpu"])
        init = self.law.sample_batch(np.random.default_rng(seed), n * self.S,
                                     c["start_hour"]).reshape(n, self.S)
        for fi, f in enumerate(faults):
            U = np.random.default_rng(np.random.SeedSequence(
                (self.tseed, _TAG_INITIAL, fi))).random(init.shape)
            hit = [f["region"] in (None, c["region"])] * self.S
            init = np.where(hit, np.vectorize(
                lambda lt, u: thin(lt, u, f, 0.0))(init, U), init)
        self.initial = init
        self._pools: Dict[int, tuple] = {}

    def pool(self, g: int):
        if g not in self._pools:
            d = self.c["replacement_delay"]
            rng = np.random.default_rng(np.random.SeedSequence(
                (self.seed, _TAG_POOL, g)))
            stages = rng.normal(np.array(d["stage_means_s"]),
                                np.array(d["stage_sds_s"]),
                                size=(self.n, self.S, 4))
            delays = np.maximum(d["floor_s"], stages).sum(axis=-1)
            self._pools[g] = (delays, rng.random((self.n, self.S,
                                                  K_UNIFORMS)))
        return self._pools[g]

    def join_lifetime(self, traj: int, slot: int, g: int,
                      elapsed_h: float) -> float:
        lt = self.law.from_uniforms(self.pool(g)[1][traj, slot],
                                    self.c["start_hour"] + elapsed_h)
        for fi, f in enumerate(self.faults):
            if f["region"] in (None, self.c["region"]):
                u = np.random.default_rng(np.random.SeedSequence(
                    (self.tseed, _TAG_JOIN, fi, traj, slot, g))).random()
                lt = thin(lt, u, f, elapsed_h)
        return lt


# ---------------------------------------------------------- trajectory
def trajectory(c: dict, draws: Draws, traj: int, dtype=np.float64) -> dict:
    """One trajectory's answer: wall time, steps done, revocations,
    replacements and cost, with the loop's state held in `dtype`."""
    F = dtype
    speed, cap = F(c["worker_steps_per_s"]), F(c["ps_capacity_steps_per_s"])
    i_c, t_c = F(c["checkpoint_interval_steps"]), F(c["checkpoint_s"])
    total, tmax = F(c["total_steps"]), F(c["max_hours"] * 3600.0)
    S = draws.S
    # workers in join order: [slot, generation, alive, chief]
    workers = [[s, 0, True, s == 0] for s in range(S)]
    q, seq = [], 0
    for s in range(S):
        lt = draws.initial[traj, s]
        if math.isfinite(lt):
            heapq.heappush(q, (F(lt * 3600.0), seq, "revoke", s))
            seq += 1
    t = steps = ckpt = alive_s = F(0.0)
    revs = reps = 0

    def rate():
        n = sum(1 for w in workers if w[2])
        return min(F(n) * speed, cap) if n else F(0.0)

    def advance(to):
        nonlocal t, steps, ckpt, alive_s
        sp, span = rate(), F(to - t)
        alive_s = F(alive_s + span * F(sum(1 for w in workers if w[2])))
        left = span
        while sp > 0 and left > 1e-12:
            to_b = F(i_c - steps % i_c)
            if to_b <= 1e-9:
                to_b = i_c
            need = F(to_b / sp)
            if need <= left:
                steps = F(steps + to_b)
                left = F(left - need)
                pause = min(t_c, left)
                ckpt = F(ckpt + pause)
                left = F(left - pause)
            else:
                steps = F(steps + sp * left)
                left = F(0.0)
        t = F(to)

    while steps < total - 1e-6 and t < tmax:
        sp = rate()
        if sp <= 0 and not q:
            break
        if sp > 0:
            n_ck = math.floor(total / i_c) - math.floor(steps / i_c)
            t_fin = F(t + (F(total - steps) / sp + F(n_ck) * t_c))
        else:
            t_fin = F(math.inf)
        if q and q[0][0] < t_fin:
            te, _, kind, wid = heapq.heappop(q)
            advance(max(te, t))
            w = workers[wid]
            if kind == "revoke":
                w[2] = False
                revs += 1
                if w[3]:
                    w[3] = False
                    for o in workers:
                        if o[2]:
                            o[3] = True
                            break
                g = w[1] + 1
                delay = draws.pool(g)[0][traj, w[0]]
                workers.append([w[0], g, False, False])
                heapq.heappush(q, (F(t + F(delay)), seq, "join",
                                   len(workers) - 1))
                seq += 1
            else:
                w[2] = True
                reps += 1
                lt = draws.join_lifetime(traj, w[0], w[1],
                                         float(t) / 3600.0)
                if math.isfinite(lt):
                    heapq.heappush(q, (F(t + F(lt * 3600.0)), seq, "revoke",
                                       wid))
                    seq += 1
        else:
            advance(t_fin)
    return {"total_time_s": float(t), "steps_done": int(steps + 1e-6),
            "revocations": revs, "replacements": reps,
            "monetary_cost": float(alive_s) / 3600.0 * c["price_per_h"]}


def compare(prog: List[dict], ref: List[dict]) -> Dict[str, float]:
    """count_mismatch: trajectories whose revocations, replacements or
    steps done differ. time_gap, cost_gap: the largest relative gap of a
    trajectory's wall time and cost."""
    counts = ("revocations", "replacements", "steps_done")
    mism = sum(any(p[k] != r[k] for k in counts) for p, r in zip(prog, ref))
    gap = lambda k: max(abs(p[k] - r[k]) / abs(r[k])  # noqa: E731
                        for p, r in zip(prog, ref))
    return {"count_mismatch": float(mism), "time_gap": gap("total_time_s"),
            "cost_gap": gap("monetary_cost")}
