"""Jitted mega-ensemble fleet engine — the lockstep simulator as ONE
compiled JAX program (`engine="jit"`).

`fleet_batched.run_batched` advances all trajectories per lockstep round
but pays NumPy's interpreter tax per round: dozens of temporaries, fancy
indexing, per-round Python grouping in the draw batchers. This module
compiles the identical round into a `lax.while_loop` body — trajectory
state as stacked `(n,)`/`(n, slots)` device arrays, the next-event select
as an XLA masked min+argmin on every backend (the state is f64, which the
Pallas `event_select` kernel cannot take), and every draw the engines
share pre-materialized on device:

* the `(n, slots)` initial-lifetime matrix is `FleetDraws.initial`
  verbatim (chaos hazard transforms already applied on host);
* generation-level replacement pools (`FleetDraws._level`) are stacked to
  `(G, n, slots)` delays + `(G, n, slots, K)` uniforms. The per-slot
  `LifetimeLaw.sample_from_uniforms` samplers are ported to jittable form
  (GCP truncated-Weibull + 16-round Fig 9 diurnal thinning, AWS inverse
  cumulative hazard on the per-launch-hour grids, Azure inverse
  exponential), so the keyed-draw contract holds unchanged: all three
  engines consume identical uniforms and agree exactly on
  revocation/replacement counts (tests/test_engine_parity.py);
* chaos `FaultTimeline` factors become piecewise-constant device tables
  (`factor_tables`) indexed by `searchsorted(boundaries, t)`, and the
  keyed join-hazard uniforms a `(G, n, slots, F)` matrix
  (`join_uniform_matrix`) — all seven scripted scenarios run under this
  engine bit-identically to the other two.

Generation pools are *level-paged*: G levels are materialized up front;
a trajectory whose next revocation needs a deeper replacement chain
freezes (`stalled`) BEFORE mutating anything, the loop drains everyone
else, and the host doubles G and re-enters with the carried state — the
frozen trajectory replays its pending round against the grown pools, so
results are independent of the paging schedule.

Everything runs under `jax.enable_x64` with explicit f64
state regardless of the global `jax_enable_x64` flag, and the math is
elementwise per trajectory, so results are byte-identical whatever the
flag or the trajectory sharding (`_shard` splits the trajectory axis
across `jax.devices()` when more than one is visible —
`xla_force_host_platform_device_count` in the multidevice CI job).
docs/DESIGN.md §2 has the state layout; docs/performance.md the
engine-selection matrix and the `bench_jit_engine` gate.
"""
from __future__ import annotations

import functools
from typing import TYPE_CHECKING, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import enable_x64, lax
from jax.profiler import TraceAnnotation

from repro.core.perf_model.cluster_model import PSBottleneckModel

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.core.transient.fleet import FleetSim, SimResult
    from repro.core.transient.fleet_batched import FleetDraws

#: generation levels materialized before the first entry; doubled on
#: every stall re-entry
INITIAL_LEVELS = 4

#: widths at or below this run to completion without compaction; above
#: it the loop exits once the active set halves, the host pages finished
#: trajectories out (the device analogue of the NumPy engine's shrinking
#: boolean-mask active set) and re-enters at the next power of two
COMPACT_MIN = 4096

_GPU_CODES = {"k80": 0, "v100": 1}  # 2 = the p100-family default weight
_ENVELOPE_INV = 1.0 / 2.5           # 1 / _DIURNAL_MAX_WEIGHT
_GCP_CAP_H = 24.0                   # revocation.MAX_LIFETIME_H


# ---------------------------------------------------------------------------
# jittable ports of the three `sample_from_uniforms` laws
# ---------------------------------------------------------------------------
def _diurnal_weight(code, h):
    """`revocation._diurnal_weight` with the gpu string as a code array."""
    h = h % 24.0
    wk = 1.0 + 1.5 * jnp.exp(-((h - 10.0) ** 2) / (2 * 2.0 ** 2))
    wv = jnp.where((h >= 16.0) & (h < 20.0), 0.0,
                   1.0 + 0.6 * jnp.exp(-((h - 9.0) ** 2) / (2 * 3.0 ** 2)))
    wp = 1.0 + 0.8 * jnp.exp(-((h - 13.0) ** 2) / (2 * 4.0 ** 2))
    return jnp.where(code == 0, wk, jnp.where(code == 1, wv, wp))


def _sample_gcp(U, hours, p24, k, lam, raw24, code):
    """`LifetimeModel.sample_from_uniforms`, params gathered per row:
    column 0 decides the 24 h survival mass, then 16 (candidate, accept)
    pairs run the diurnal thinning, with the hard-zero +4 h push."""
    def inv_cdf(u):
        return lam * (-jnp.log(1.0 - u * raw24)) ** (1.0 / k)

    revoked = U[:, 0] < p24

    def thin(j, carry):
        # a loop, not 16 unrolled copies: the f64 math the TPU emulates
        # would otherwise multiply the program's size and compile time
        cand, w, pending = carry
        c2 = inv_cdf(U[:, 1 + 2 * j])
        w2 = _diurnal_weight(code, hours + c2)
        return (jnp.where(pending, c2, cand), jnp.where(pending, w2, w),
                pending & ~(U[:, 2 + 2 * j] < w2 * _ENVELOPE_INV))

    zero = jnp.zeros(U.shape[:1], U.dtype)
    cand, w, pending = lax.fori_loop(
        0, 16, thin, (zero, zero, jnp.ones(U.shape[:1], bool)))
    cand = jnp.where(pending & (w == 0.0), cand + 4.0, cand)
    return jnp.where(revoked, jnp.minimum(cand, _GCP_CAP_H), jnp.inf)


def _sample_aws(U, hours, slot, ts_all, cum_all):
    """`PriceSignalLifetime.sample_from_uniforms`: inverse cumulative
    hazard of column 0 on the slot's 15-min-quantized launch-hour grid.

    `ts_all`: (S, P) time grids; `cum_all`: (S, 96, P) cumulative-hazard
    grids per quantized hour key. The interpolation runs as an
    elementwise bisection (12 gathered probes per row) instead of
    materializing the `(n, P)` gathered grid rows `jnp.interp` would
    need — per round, joins are rare but every row computes."""
    P = ts_all.shape[-1]
    target = -jnp.log(1.0 - U[:, 0])
    key = (jnp.round(hours % 24.0 * 4.0)).astype(jnp.int32) % 96
    cum2 = cum_all.reshape(-1, P)
    row = slot * 96 + key
    lo = jnp.zeros(row.shape, jnp.int32)
    hi = jnp.full(row.shape, P, jnp.int32)
    for _ in range(12):  # 2^12 >= P + 1 outcomes
        mid = (lo + hi) // 2
        v = cum2[row, jnp.minimum(mid, P - 1)]
        upd = lo < hi
        right = upd & (v <= target)
        lo = jnp.where(right, mid + 1, lo)
        hi = jnp.where(upd & ~right, mid, hi)
    j = jnp.clip(lo, 1, P - 1)          # searchsorted(cum, target, 'right')
    c0, c1 = cum2[row, j - 1], cum2[row, j]
    t0, t1 = ts_all[slot, j - 1], ts_all[slot, j]
    out = t0 + (target - c0) * ((t1 - t0) / (c1 - c0))
    return jnp.where(target > cum2[row, P - 1], jnp.inf, out)


def _sample_azure(U, hazard, horizon):
    """`TieredEvictionLifetime.sample_from_uniforms`: inverse-transform
    exponential; inf beyond the sampling horizon."""
    t = -jnp.log(1.0 - U[:, 0]) / hazard
    return jnp.where(t > horizon, jnp.inf, t)


def _law_spec(sim: "FleetSim"):
    """Classify the roster's lifetime laws into one jittable kind plus
    stacked per-slot parameter arrays. Raises for laws the compiled
    samplers cannot reproduce (custom providers): those rosters need
    `engine="batched"`, whose per-key fallback streams handle any law."""
    from repro.core.transient.revocation import LifetimeModel
    from repro.providers.aws import PriceSignalLifetime
    from repro.providers.azure import TieredEvictionLifetime

    laws = [sim.provider.lifetime_model(region, gpu)
            for _, gpu, region, _ in sim._roster]
    if all(isinstance(l, LifetimeModel) for l in laws):
        import math
        raw24 = [1.0 - math.exp(-((_GCP_CAP_H / l.lam) ** l.k))
                 for l in laws]
        return "gcp", {
            "law_p24": np.array([l.p24 for l in laws]),
            "law_k": np.array([l.k for l in laws]),
            "law_lam": np.array([l.lam for l in laws]),
            "law_raw24": np.array(raw24),
            "law_code": np.array([_GPU_CODES.get(l.gpu, 2) for l in laws],
                                 np.int32)}
    if all(isinstance(l, PriceSignalLifetime) for l in laws):
        ts_all, cum_all = [], []
        for l in laws:
            grids = [l._grid(kq / 4.0) for kq in range(96)]
            ts_all.append(grids[0][0])
            cum_all.append(np.stack([c for _, c in grids]))
        return "aws", {"law_ts": np.stack(ts_all),
                       "law_cum": np.stack(cum_all)}
    if all(isinstance(l, TieredEvictionLifetime) for l in laws):
        return "azure", {
            "law_hazard": np.array([l.hazard_per_h for l in laws]),
            "law_horizon": np.array([l.horizon_h for l in laws])}
    raise ValueError(
        "engine='jit' compiles the provider's lifetime law into the "
        "device program and supports the gcp/aws/azure law families; "
        f"this roster's laws ({sorted({type(l).__name__ for l in laws})}) "
        "have no jittable port — use engine='batched' instead")


# ---------------------------------------------------------------------------
# the compiled program
# ---------------------------------------------------------------------------
def _gather_slot(arr2d, slot):
    """arr2d[(i, slot[i])] without a cross-trajectory gather (stays
    elementwise under trajectory sharding)."""
    return jnp.take_along_axis(arr2d, slot[:, None], axis=1)[:, 0]


@functools.lru_cache(maxsize=None)
def _compiled(law_kind: str, handover: bool, graceful: bool,
              replace: bool, resilient: bool):
    """One jitted lockstep program per (law family, chief policy,
    replacement policy, resilience). Shapes (n, S, K, G, F, chaos
    segments) re-trace automatically; every scalar knob is a traced
    operand. `resilient` gates the quorum-degradation/restore-stall
    state and math entirely out of the trace — a run without a
    `ResilienceConfig` compiles the exact pre-resilience program."""

    def simulate(st, ar):
        S = ar["slot_speed"].shape[0]
        G = ar["delays"].shape[0] // S       # pools fold (level, slot)
        P_INF = jnp.inf

        def seg_factors(t):
            seg = jnp.searchsorted(ar["boundaries"], t, side="right")
            return (ar["speed_table"][seg], ar["ps_table"][seg],
                    ar["blk_table"][seg])

        def cluster_speed(t, alive):
            mults, psf, _ = seg_factors(t)
            return jnp.minimum(jnp.sum(alive * mults * ar["slot_speed"],
                                       axis=1), ar["cap"] * psf)

        def join_lifetimes(U, hours, slot):
            if law_kind == "gcp":
                return _sample_gcp(U, hours, ar["law_p24"][slot],
                                   ar["law_k"][slot], ar["law_lam"][slot],
                                   ar["law_raw24"][slot],
                                   ar["law_code"][slot])
            if law_kind == "aws":
                return _sample_aws(U, hours, slot, ar["law_ts"],
                                   ar["law_cum"])
            return _sample_azure(U, ar["law_hazard"][slot],
                                 ar["law_horizon"][slot])

        def chaos_join(lt, Uj, slot, elapsed_h):
            """`FaultTimeline.transform_joins` on the pre-keyed uniform
            matrix: fault windows thin each lifetime in fault order."""
            F = ar["hz_start"].shape[0]
            cols = ar["hz_cols"]                      # (F, S) bool
            for f in range(F):
                a = jnp.maximum(ar["hz_start"][f], elapsed_h)
                b = jnp.minimum(ar["hz_end"][f], elapsed_h + lt)
                tau = -jnp.log1p(-Uj[:, f]) / ar["hz_rate"][f]
                killed = ((b - a) > 0) & (tau < (b - a))
                new = jnp.where(killed,
                                jnp.minimum(lt, a + tau - elapsed_h), lt)
                lt = jnp.where(cols[f][slot], new, lt)
            return lt

        def cond(st):
            act = ~st["done"] & ~st["stalled"]
            w = act.shape[0]
            if w <= COMPACT_MIN:
                return jnp.any(act)
            # wide ensembles hand control back once the active set halves
            # so the host can compact; the body math never sees the width
            a = jnp.sum(act)
            return (a > 0) & (2 * a > w)

        def body(st):
            t, steps = st["t"], st["steps"]
            n = t.shape[0]
            act = ~st["done"] & ~st["stalled"]
            ev_all = jnp.concatenate([st["revoke_t"], st["join_t"]],
                                     axis=1)
            ev_all = jnp.where(act[:, None], ev_all, P_INF)
            # next event per trajectory: masked min + lowest-column argmin
            # in plain XLA (f64 state; Mosaic kernels take no f64 operand)
            ev_t = jnp.min(ev_all, axis=1)
            ev_arg = jnp.argmin(ev_all, axis=1).astype(jnp.int32)
            mults, psf, blk = seg_factors(t)
            sp = jnp.minimum(jnp.sum(st["alive"] * mults
                                     * ar["slot_speed"], axis=1),
                             ar["cap"] * psf)
            nb = jnp.append(ar["boundaries"], P_INF)[
                jnp.searchsorted(ar["boundaries"], t, side="right")]
            nb = jnp.where(nb < ar["tmax"], nb, P_INF)
            if resilient:
                # a pending restore-retry stall end is a pure-advancement
                # boundary (the event engine's no-op "resume" heap entry,
                # never clipped at tmax); effective speed is gated to 0
                # meanwhile, and otherwise by the quorum tier on the
                # alive fraction (fleet_batched._degr_factor)
                stall_ev = jnp.where(st["stall_t"] > t, st["stall_t"],
                                     P_INF)
                nb = jnp.minimum(nb, stall_ev)
                frac = jnp.sum(st["alive"], axis=1) / S
                factor = jnp.where(
                    frac < ar["quorum"], 0.0,
                    jnp.where(frac < ar["shrink_below"],
                              ar["shrink_factor"], 1.0))
                sp = jnp.where(jnp.isfinite(stall_ev), 0.0, sp * factor)
            i_c, t_c, total = ar["i_c"], ar["t_c"], ar["total"]
            rel = jnp.where(
                sp > 0,
                (total - steps) / jnp.where(sp > 0, sp, 1.0)
                + jnp.where(blk, 0.0, (jnp.floor(total / i_c)
                                       - jnp.floor(steps / i_c)) * t_c),
                P_INF)
            t_fin = t + rel
            stuck = act & jnp.isinf(ev_t) & (sp <= 0) & jnp.isinf(nb)
            nxt = jnp.minimum(ev_t, nb)
            ev = act & ~stuck & (nxt < t_fin)      # strict: event first
            fin = act & ~stuck & ~ev
            slot = (ev_arg % S).astype(jnp.int32)
            real = ev & (ev_t <= nxt)              # vs a chaos boundary
            is_rev = real & (ev_arg < S)
            gen_at = _gather_slot(st["gen"], slot)
            # level paging: a revoke whose replacement needs a pool level
            # beyond G freezes the trajectory BEFORE any mutation; the
            # host grows the pools and re-enters
            if replace:
                stall_now = is_rev & (gen_at + 1 > G)
            else:
                stall_now = jnp.zeros_like(is_rev)
            stalled = st["stalled"] | stall_now
            move = (ev | fin) & ~stall_now
            target = jnp.where(ev, jnp.maximum(nxt, t), t_fin)
            # ---- closed-form advance to `target` (fleet_batched._advance)
            span = jnp.where(move, target - t, 0.0)
            if resilient:
                # exclusive accrual per span: a stall span is restore
                # delay; a quorum pause (not stalled, factor 0) is
                # paused time. `sp` is already gated above, so the
                # stepping math below produces nothing for either.
                seg_stall = st["stall_t"] > t
                restore_s = (st["restore_s"]
                             + jnp.where(seg_stall, span, 0.0))
                paused = (st["paused"]
                          + jnp.where(~seg_stall & (factor == 0.0),
                                      span, 0.0))
            alive_seconds = (st["alive_seconds"]
                             + st["alive"] * span[:, None])
            pos = move & (sp > 0) & (span > 1e-12)
            spp = jnp.where(sp > 0, sp, 1.0)
            s0 = steps
            b0 = i_c - s0 % i_c
            b0 = jnp.where(b0 <= 1e-9, i_c, b0)
            d0 = b0 / spp
            cycle = i_c / spp + t_c
            k = jnp.where(span >= d0,
                          jnp.floor((span - d0) / cycle) + 1.0, 0.0)
            r = span - d0 - (k - 1.0) * cycle
            pause = jnp.minimum(t_c, r)
            boundary = s0 + b0 + (k - 1.0) * i_c
            stepped = jnp.where(k > 0,
                                boundary + spp * jnp.maximum(0.0, r - pause),
                                s0 + spp * span)
            new_ck = jnp.where(k > 0, (k - 1.0) * t_c + pause, 0.0)
            stepped = jnp.where(blk, s0 + spp * span, stepped)
            new_ck = jnp.where(blk, 0.0, new_ck)
            steps = jnp.where(pos, stepped, s0)
            ckpt_time = st["ckpt_time"] + jnp.where(pos, new_ck, 0.0)
            last_ckpt = jnp.where(pos & (k > 0) & ~blk,
                                  jnp.round(boundary), st["last_ckpt"])
            t = jnp.where(move, target, t)
            done = st["done"] | stuck | (fin & ~stall_now)
            # ------------------------------------------------- revokes
            is_rev = is_rev & ~stall_now
            is_join = real & (ev_arg >= S)
            onehot = jnp.arange(S)[None, :] == slot[:, None]
            rev2d = onehot & is_rev[:, None]
            was_chief = jnp.any(st["chief"] & rev2d, axis=1)
            alive = st["alive"] & ~rev2d
            revoke_t = jnp.where(rev2d, P_INF, st["revoke_t"])
            revocations = st["revocations"] + is_rev
            chief, lost, recompute = st["chief"], st["lost"], st["recompute"]
            if resilient:
                stall_t = st["stall_t"]
            if handover:
                chief = chief & ~rev2d
                keys = jnp.where(alive, st["order_key"], P_INF)
                best = jnp.argmin(keys, axis=1)
                promote = (is_rev & was_chief
                           & jnp.isfinite(jnp.min(keys, axis=1)))
                best2d = jnp.arange(S)[None, :] == best[:, None]
                chief = chief | (best2d & promote[:, None])
            elif graceful:
                gm = is_rev & was_chief
                last_ckpt = jnp.where(gm, jnp.round(steps), last_ckpt)
            else:
                sm = is_rev & was_chief
                lost_now = jnp.where(sm, steps - last_ckpt, 0.0)
                steps = jnp.where(sm, last_ckpt, steps)
                lost = lost + lost_now
                sp_after = cluster_speed(t, alive)   # post-revoke fleet
                # raw cluster speed on purpose: recompute happens after
                # the fleet recovers, so degradation never inflates it
                recompute = recompute + jnp.where(
                    sm, lost_now / jnp.maximum(sp_after, 1e-9), 0.0)
                if resilient:
                    # restore-retry stall, keyed on the revoked
                    # occupant's generation (pre-bump — the replace
                    # block below bumps it); a later stall overwrites an
                    # active one, even shortening it
                    lvl_s = jnp.clip(gen_at, 0, G - 1)
                    sdelay = ar["stalls"][lvl_s * S + slot, st["orig"]]
                    stall_t = jnp.where(sm, t + sdelay, stall_t)
            gen, join_t = st["gen"], st["join_t"]
            orig = st["orig"]        # row in the full-width pools
            if replace:
                lvl = jnp.clip(gen_at, 0, G - 1)     # level new_gen - 1
                delay = ar["delays"][lvl * S + slot, orig]
                join_t = jnp.where(rev2d, (t + delay)[:, None], join_t)
                gen = gen + rev2d
            # --------------------------------------------------- joins
            join2d = onehot & is_join[:, None]
            alive = alive | join2d
            join_t = jnp.where(join2d, P_INF, join_t)
            replacements = st["replacements"] + is_join
            order_key = jnp.where(join2d, st["next_key"][:, None],
                                  st["order_key"])
            next_key = st["next_key"] + is_join

            def _sample_joins(revoke_t):
                # one fused (level, slot, trajectory) gather per pool
                # (pools stay full-width and device-resident; compaction
                # only permutes `orig`), then the law sampler — guarded
                # by the `lax.cond` below so rounds with no join (notably
                # the full-width first round, where every event is an
                # initial revocation) skip it entirely
                li = (jnp.clip(gen_at - 1, 0, G - 1) * S + slot)
                U = ar["uniforms"][li, orig, :]              # (n, K)
                lts = join_lifetimes(U, ar["start_hour"] + t / 3600.0,
                                     slot)
                if ar["hz_start"].shape[0]:
                    Uj = ar["join_U"][li, orig, :]           # (n, F)
                    lts = chaos_join(lts, Uj, slot, t / 3600.0)
                return jnp.where(
                    join2d,
                    jnp.where(jnp.isfinite(lts), t + lts * 3600.0,
                              P_INF)[:, None],
                    revoke_t)

            revoke_t = lax.cond(jnp.any(is_join), _sample_joins,
                                lambda r: r, revoke_t)
            done = done | (steps >= total - 1e-6) | (t >= ar["tmax"])
            out = {"t": t, "steps": steps, "last_ckpt": last_ckpt,
                   "ckpt_time": ckpt_time, "recompute": recompute,
                   "lost": lost, "revocations": revocations,
                   "replacements": replacements, "alive": alive,
                   "chief": chief, "gen": gen, "order_key": order_key,
                   "next_key": next_key, "revoke_t": revoke_t,
                   "join_t": join_t, "alive_seconds": alive_seconds,
                   "done": done, "stalled": stalled, "orig": orig}
            if resilient:
                out["stall_t"] = stall_t
                out["paused"] = paused
                out["restore_s"] = restore_s
            return out

        return lax.while_loop(cond, body, st)

    return jax.jit(simulate)


# ---------------------------------------------------------------------------
# host driver: pools, sharding, level paging
# ---------------------------------------------------------------------------
def _shard(n_pad: int):
    """NamedSharding over the trajectory axis when >1 device is visible
    (multi-host-device CPU via xla_force_host_platform_device_count, or
    real accelerators); None on a single device."""
    devs = jax.devices()
    if len(devs) <= 1:
        return None, None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(devs), ("traj",))
    return (NamedSharding(mesh, PartitionSpec("traj")),
            NamedSharding(mesh, PartitionSpec()))


def _put(x, sharding, axis=0):
    if sharding is None:
        return jnp.asarray(x)
    from jax.sharding import NamedSharding, PartitionSpec
    if axis == 0:
        return jax.device_put(jnp.asarray(x), sharding)
    spec = [None] * np.ndim(x)
    spec[axis] = "traj"
    return jax.device_put(jnp.asarray(x), NamedSharding(
        sharding.mesh, PartitionSpec(*spec)))


def _pools(draws: "FleetDraws", G: int, has_chaos: bool, res=None,
           sharding=None):
    """FleetDraws generation levels 1..G as device arrays in the folded
    `(level * S + slot, trajectory, ...)` layout the body's single
    `take_along_axis` per pool expects. Cached on the draws object — the
    pools are pure functions of (draws, G, res), so repeat calls
    (planner re-scoring, `_best_of` benchmark reps) reuse the device
    copies. With a `ResilienceConfig` the restore-retry stall levels
    ride along, indexed by the revoked occupant's generation (0..G-1 —
    level paging freezes any revoke whose occupant reached G before it
    mutates state, so the index never pages off the pool). A build
    runs in a `fleet.pools` span whose `draws` stat counts the chaos
    join uniforms drawn, every level of the build in one array call."""
    key = (G, bool(has_chaos), res, sharding)
    cache = draws.__dict__.setdefault("_jit_pool_cache", {})
    if key in cache:
        return cache[key]
    n, S, K = draws.n, draws.n_slots, draws._K
    F = len(draws.chaos.hazards) if has_chaos else 0
    with TraceAnnotation("fleet.pools", levels=G, draws=G * S * n * F):
        delays = np.empty((G, S, n))
        uniforms = np.empty((G, S, n, K))
        for g in range(1, G + 1):
            d, u = draws._level(g)
            delays[g - 1] = d.T
            uniforms[g - 1] = np.swapaxes(u, 0, 1)
        out = {"delays": delays.reshape(G * S, n),
               "uniforms": uniforms.reshape(G * S, n, K)}
        if res is not None:
            stalls = np.empty((G, S, n))
            for g in range(G):
                stalls[g] = draws.restore_stall_level(res, g).T
            out["stalls"] = stalls.reshape(G * S, n)
        ju = (np.swapaxes(draws.chaos.join_uniform_matrix(
            n, range(1, G + 1)), 1, 2) if F else np.empty((G, S, n, 0)))
        out["join_U"] = ju.reshape(G * S, n, F)
        out = {name: _put(arr, sharding) for name, arr in out.items()}
    cache.clear()            # keep at most one (the deepest) G resident
    cache[key] = out
    return out


def _pow2ceil(x: int) -> int:
    return 1 << (max(1, x) - 1).bit_length()


#: state fields pulled to host at every loop exit (the result fields plus
#: the done/stalled masks driving compaction and pool paging)
_HARVEST = ("t", "steps", "ckpt_time", "recompute", "lost", "revocations",
            "replacements", "alive_seconds", "done", "stalled")


def run_jit(sim: "FleetSim", total_steps: int, n: int,
            max_hours: float = 48.0, start_hour: float = 0.0,
            draws: Optional["FleetDraws"] = None, raw: bool = False):
    """Advance `n` trajectories of `sim`'s roster as one jitted program.

    Same contract as `fleet_batched.run_batched` (which documents the
    round semantics): one `SimResult` per trajectory, exact
    revocation/replacement parity with both other engines under the
    shared `FleetDraws`, times/costs to float tolerance. With
    `raw=True` the per-trajectory stats come back as a dict of arrays
    instead (same keys as `run_batched(raw=True)`) — the
    `bench_jit_engine` engine-core measurement and array consumers skip
    the 65k-`SimResult` Python object construction.

    Above `COMPACT_MIN` trajectories the driver pages finished
    trajectories out between `lax.while_loop` entries: the loop hands
    control back once the active set halves, finished rows' stats are
    scattered to host buffers, and the survivors re-enter at the next
    power-of-two width (fresh trace per width, cached across calls).
    Compaction only permutes rows between entries — the body math is
    width-blind and elementwise per trajectory, so results are
    bit-identical whatever the compaction (or shard) schedule.
    """
    from repro.core.transient.fleet import SimResult
    from repro.core.transient.fleet_batched import FleetDraws

    if n < 1:
        raise ValueError(f"need at least one trajectory, got {n}")
    with enable_x64(True):
        # the roster's law, fault tables and trajectory state, built and
        # put on the device
        with TraceAnnotation("fleet.setup"):
            spec_kind, law_arrays = _law_spec(sim)
            if draws is None:
                with TraceAnnotation("fleet.draws"):
                    draws = FleetDraws(sim, n, start_hour)
            roster = sim._roster
            S = len(roster)
            slot_speed = np.array([speed for _, _, _, speed in roster], float)
            cap = PSBottleneckModel(sim.model_bytes, sim.n_ps,
                                    n_tensors=sim.n_tensors,
                                    compression=sim.grad_compression
                                    ).capacity_steps_per_s()
            chaos = getattr(sim, "chaos", None)
            has_chaos = chaos is not None
            has_haz = has_chaos and len(chaos.hazards) > 0
            graceful = (sim.provider.graceful_checkpoint_on_warning
                        and sim.provider.warning_seconds >= sim.t_c)
            resil = getattr(sim, "resilience", None)
            resilient = resil is not None
            fn = _compiled(spec_kind, bool(sim.handover), bool(graceful),
                           bool(sim.replace), resilient)
            traj_sh, rep_sh = _shard(n)
            n_dev = len(jax.devices())
            n_pad = n if traj_sh is None else -(-n // n_dev) * n_dev

            if has_chaos:
                bounds, sp_tab, ps_tab, blk_tab = chaos.factor_tables()
                hz_s, hz_e, hz_r, hz_c = chaos.hazard_tables()
            else:
                bounds = np.zeros(0)
                sp_tab, ps_tab = np.ones((1, S)), np.ones(1)
                blk_tab = np.zeros(1, bool)
                hz_s = hz_e = hz_r = np.zeros(0)
                hz_c = np.zeros((0, S), bool)
            ar = {"slot_speed": _put(slot_speed, rep_sh),
                  "cap": jnp.asarray(float(cap)),
                  "i_c": jnp.asarray(float(sim.i_c)),
                  "t_c": jnp.asarray(float(sim.t_c)),
                  "total": jnp.asarray(float(total_steps)),
                  "tmax": jnp.asarray(max_hours * 3600.0),
                  "start_hour": jnp.asarray(float(start_hour)),
                  "boundaries": _put(bounds, rep_sh),
                  "speed_table": _put(sp_tab, rep_sh),
                  "ps_table": _put(ps_tab, rep_sh),
                  "blk_table": _put(blk_tab, rep_sh),
                  "hz_start": _put(hz_s, rep_sh),
                  "hz_end": _put(hz_e, rep_sh),
                  "hz_rate": _put(hz_r, rep_sh),
                  "hz_cols": _put(hz_c, rep_sh)}
            if resilient:
                ar["quorum"] = jnp.asarray(float(resil.degradation.quorum))
                ar["shrink_below"] = jnp.asarray(
                    float(resil.degradation.shrink_below))
                ar["shrink_factor"] = jnp.asarray(
                    float(resil.degradation.shrink_factor))
            for name, arr in law_arrays.items():
                ar[name] = _put(arr, rep_sh)

            pad = n_pad - n
            init_rt = np.where(np.isfinite(draws.initial),
                               draws.initial * 3600.0, np.inf)
            if pad:
                init_rt = np.pad(init_rt, ((0, pad), (0, 0)),
                                 constant_values=np.inf)
            chief0 = np.zeros((n_pad, S), bool)
            chief0[:, 0] = True                 # FleetSim marks workers[0]
            done0 = np.zeros(n_pad, bool)
            done0[n:] = True                    # padding rows never run
            st = {"t": np.zeros(n_pad), "steps": np.zeros(n_pad),
                  "last_ckpt": np.zeros(n_pad), "ckpt_time": np.zeros(n_pad),
                  "recompute": np.zeros(n_pad), "lost": np.zeros(n_pad),
                  "revocations": np.zeros(n_pad, np.int32),
                  "replacements": np.zeros(n_pad, np.int32),
                  "alive": np.ones((n_pad, S), bool), "chief": chief0,
                  "gen": np.zeros((n_pad, S), np.int32),
                  "order_key": np.tile(np.arange(S, dtype=float), (n_pad, 1)),
                  "next_key": np.full(n_pad, float(S)),
                  "revoke_t": init_rt,
                  "join_t": np.full((n_pad, S), np.inf),
                  "alive_seconds": np.zeros((n_pad, S)),
                  "done": done0, "stalled": np.zeros(n_pad, bool),
                  "orig": np.concatenate([np.arange(n, dtype=np.int32),
                                          np.zeros(pad, np.int32)])}
            if resilient:
                st["stall_t"] = np.zeros(n_pad)
                st["paused"] = np.zeros(n_pad)
                st["restore_s"] = np.zeros(n_pad)
            st = {key: _put(v, traj_sh) for key, v in st.items()}

        if sim.replace:
            # start deep enough for every level a previous call on these
            # draws already materialized — warm calls take one entry
            G = INITIAL_LEVELS
            while G < max(draws._levels, default=0):
                G *= 2
        else:
            G = 1

        # lane -> original trajectory map plus host result buffers rows
        # are scattered into as compaction drops them from the device
        sel = np.concatenate([np.arange(n), np.zeros(pad, np.int64)])
        valid = np.zeros(n_pad, bool)
        valid[:n] = True
        harvest = _HARVEST + (("paused", "restore_s")
                              if resilient else ())
        res = {key: np.zeros(n, np.int64 if key in
                             ("revocations", "replacements") else float)
               for key in harvest if key not in
               ("alive_seconds", "done", "stalled")}
        res["alive_seconds"] = np.zeros((n, S))
        if not resilient:     # raw output always carries both keys
            res["paused"] = np.zeros(n)
            res["restore_s"] = np.zeros(n)
        res_keys = [key for key in harvest
                    if key not in ("done", "stalled")]

        def _scatter(lanes: np.ndarray):
            """Pull `lanes`' stats off the device into the result
            buffers (a device-side gather first, so the transfer is
            proportional to the rows leaving, not the loop width)."""
            if not lanes.size:
                return
            # plain (unsharded) index vector: its length is however many
            # rows happen to finish, rarely divisible by the device count
            idx_d = jnp.asarray(lanes.astype(np.int32))
            sub = jax.device_get({key: jnp.take(st[key], idx_d, axis=0)
                                  for key in res_keys})
            rows = sel[lanes]
            for key in res_keys:
                res[key][rows] = np.asarray(sub[key])

        ar_g = dict(ar, **_pools(draws, G, has_haz, resil, rep_sh))
        regrow = 0
        while True:
            with TraceAnnotation("fleet.loop", regrow=regrow):
                st = fn(st, ar_g)
                h = jax.device_get({"done": st["done"],
                                    "stalled": st["stalled"]})
            regrow = int(np.any(h["stalled"] & valid))
            if regrow:
                # deepest replacement chains outgrew the pools: double
                # them and replay the frozen trajectories' pending rounds
                G *= 2
                ar_g.update(_pools(draws, G, has_haz, resil, rep_sh))
                st = dict(st)
                st["stalled"] = _put(np.zeros(len(sel), bool), traj_sh)
            keep = valid & ~np.asarray(h["done"])
            a = int(keep.sum())
            if a == 0:
                with TraceAnnotation("fleet.compact", rows=int(valid.sum())):
                    _scatter(np.flatnonzero(valid))
                break
            w2 = max(COMPACT_MIN, _pow2ceil(a))
            if n_dev > 1:
                w2 = -(-w2 // n_dev) * n_dev
            if w2 < len(sel):
                leaving = np.flatnonzero(valid & ~keep)
                with TraceAnnotation("fleet.compact", rows=leaving.size):
                    _scatter(leaving)
                    idx = np.zeros(w2, np.int32)
                    idx[:a] = np.flatnonzero(keep)
                    idx_d = _put(idx, traj_sh)
                    padmask = np.zeros(w2, bool)
                    padmask[a:] = True
                    st = {key: _put(jnp.take(v, idx_d, axis=0), traj_sh)
                          for key, v in st.items()}
                    st["done"] = jnp.logical_or(st["done"],
                                                _put(padmask, traj_sh))
                sel = sel[idx]
                valid = ~padmask

    with TraceAnnotation("fleet.results"):
        price = np.array([sim.price_of.get(g, 0.0)
                          for _, g, _, _ in roster])
        cost = (res["alive_seconds"] / 3600.0) @ price
        regions = {region for _, _, region, _ in roster}
        region = regions.pop() if len(regions) == 1 else ""
        if raw:
            return {"total_time_s": res["t"],
                    "steps_done": (res["steps"] + 1e-6).astype(np.int64),
                    "revocations": res["revocations"],
                    "replacements": res["replacements"],
                    "checkpoint_time_s": res["ckpt_time"],
                    "recompute_time_s": res["recompute"],
                    "lost_steps": res["lost"], "monetary_cost": cost,
                    "paused_s": res["paused"],
                    "restore_delay_s": res["restore_s"]}
        return [SimResult(
            total_time_s=float(res["t"][j]),
            steps_done=int(res["steps"][j] + 1e-6),
            revocations=int(res["revocations"][j]),
            replacements=int(res["replacements"][j]),
            checkpoint_time_s=float(res["ckpt_time"][j]),
            recompute_time_s=float(res["recompute"][j]),
            lost_steps=float(res["lost"][j]),
            events=[], monetary_cost=float(cost[j]),
            provider=sim.provider.name, region=region,
            paused_s=float(res["paused"][j]),
            restore_delay_s=float(res["restore_s"][j])) for j in range(n)]
