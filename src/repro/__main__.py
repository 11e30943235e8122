"""`python -m repro` — the CM-DARE command line, one shell over `repro.api`.

    python -m repro train    --arch qwen3-1.7b --steps 5
    python -m repro serve    --arch mamba2-1.3b --tokens 16
    python -m repro plan     [--arch ...] --gpu v100 --workers 4 [--provider aws]
    python -m repro simulate [--arch ...] --gpu v100 --workers 4 [--provider azure]
    python -m repro predict  [--arch ...] --gpu v100 --workers 4 [--provider gcp]
    python -m repro chaos    --scenario all [--engine batched|event|jit] [--live]
    python -m repro bench    --only table1_speed,fig2_stability
    python -m repro dryrun   --arch qwen3-1.7b --shape train_4k

The old module launchers (`python -m repro.launch.train` etc.) remain as
deprecation shims over the same Session facade.
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from repro.core import jit_cache
from repro.launch import cli

_SRC = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the checkout holding src/repro, where the compilation cache lives; None
#: when the package runs from an installed copy, which names no checkout
_CHECKOUT = (os.path.dirname(_SRC) if os.path.basename(_SRC) == "src"
             and os.path.isfile(os.path.join(os.path.dirname(_SRC),
                                             "pyproject.toml")) else None)


def build_parser() -> argparse.ArgumentParser:
    p = cli.make_parser("repro", __doc__.splitlines()[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    t = sub.add_parser("train", help="elastic transient-aware training")
    cli.add_arch_arg(t)
    cli.add_scale_args(t)
    cli.add_batch_args(t)
    cli.add_train_args(t)
    cli.add_resilience_args(t)
    cli.add_recalib_args(t)

    s = sub.add_parser("serve", help="prefill + token-by-token decode, or "
                                     "--fleet SLO-aware serving planning")
    cli.add_arch_arg(s)
    cli.add_scale_args(s)
    cli.add_serve_args(s)
    cli.add_serve_fleet_args(s)
    # resilience flags shape the --fleet plan (drain/handover vs stock)
    cli.add_resilience_args(s)

    for name, hlp in (("plan", "revocation-aware launch planning (§V-C)"),
                      ("simulate", "discrete-event fleet simulation (§VI-A)"),
                      ("predict", "Eq (4)/(5) end-to-end prediction")):
        q = sub.add_parser(name, help=hlp)
        cli.add_arch_arg(q)
        cli.add_scale_args(q)
        cli.add_fleet_args(q)
        if name in ("plan", "simulate"):
            # predict is the Eq (4) closed form: no recovery term
            cli.add_resilience_args(q)
        q.add_argument("--steps", type=int, default=2000)
        q.add_argument("--checkpoint-interval", type=int, default=200)
        # --region defaults to None: `plan` scores every region of the
        # selected provider; simulate/predict fall back to the provider's
        # default region
        if name == "plan":
            q.add_argument("--samples", type=int, default=200,
                           help="Monte-Carlo draws per (region, hour) cell")
            q.add_argument("--score", default="eq4",
                           choices=("eq4", "sim"),
                           help="cell estimator: Eq (4) point estimate "
                                "(default) or a full fleet-simulation "
                                "ensemble per cell with time/cost "
                                "percentiles")
            q.add_argument("--engine", default="batched",
                           choices=("batched", "event", "jit"),
                           help="trajectory stepper for --score sim "
                                "(docs/performance.md)")
            # planning is uncapped unless the user asks for the Fig 4 PS
            # model (--score sim always applies it, with 1 PS by default)
            q.set_defaults(n_ps=None)
        elif name == "simulate":
            q.add_argument("--samples", type=int, default=1,
                           help="trajectories; >1 reports the p50/p90/mean "
                                "ensemble summary (SimStats)")
            q.add_argument("--engine", default="batched",
                           choices=("batched", "event", "jit"),
                           help="ensemble stepper: lockstep array engine "
                                "(default), the per-trajectory event "
                                "loop, or the compiled jit program "
                                "(docs/performance.md)")

    c = sub.add_parser("chaos", help="scripted fault scenarios with "
                                     "ground-truth-scored detection & "
                                     "mitigation (docs/chaos.md)")
    cli.add_arch_arg(c)
    cli.add_scale_args(c)
    c.add_argument("--scenario", default="all",
                   help="registered scenario name, or 'all' (default)")
    c.add_argument("--list", action="store_true",
                   help="list registered scenarios and exit")
    c.add_argument("--engine", default="batched",
                   choices=("batched", "event", "jit"),
                   help="fleet-ensemble stepper (an engine-vs-event "
                        "parity probe runs either way)")
    c.add_argument("--live", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="drive the real trainer through scenarios that "
                        "carry a live plan (--no-live: simulation only)")
    c.add_argument("--samples", type=int, default=32,
                   help="fleet-simulation trajectories per ensemble")
    c.add_argument("--smoke", action="store_true",
                   help="enforce each scenario's expectation gates; "
                        "exit 1 if any fail")
    # recovery flags arm session.run.resilience, which the chaos runner's
    # simulated fleets AND live trainer runs inherit (docs/resilience.md)
    cli.add_resilience_args(c)
    # --recalibrate arms session.run.recalibration the same way: the live
    # runs drift-detect and refit mid-scenario (docs/calibration.md)
    cli.add_recalib_args(c)

    b = sub.add_parser("bench", help="paper table/figure benchmark driver")
    b.add_argument("--only", default="",
                   help="comma-separated benchmark module subset")
    b.add_argument("--list", action="store_true",
                   help="list available benchmark modules and exit")

    # `dryrun` is dispatched before argparse in main(): its flags are owned
    # by repro.launch.dryrun (or repro.launch.sweep under --sweep), whose
    # import must also happen first (it pins the XLA host-device count).
    # Registered here for `--help` only.
    sub.add_parser("dryrun", help="AOT lower/compile on production meshes "
                                  "(512 host devices); --sweep fans out the "
                                  "full arch x shape matrix with resumable "
                                  "artifacts; flags forwarded to "
                                  "repro.launch.dryrun / .sweep",
                   add_help=False)
    return p


# ----------------------------------------------------------------- handlers
def _cmd_train(args) -> int:
    from repro.core.trainer import MembershipEvent

    session = cli.session_from_args(args)
    if args.mode == "async_ps":
        if args.revoke_at or args.checkpoint_dir:
            raise ValueError("--revoke-at/--checkpoint-dir apply to "
                             "--mode sync only (the async-PS emulation "
                             "has no checkpointing or membership events)")
        rep = session.train(args.steps, global_batch=args.global_batch,
                            seq_len=args.seq, members=args.members,
                            mode="async_ps")
        stale = session.bus.of_kind("staleness")[-1].payload
        curve = (f"loss {rep.losses[0]:.3f}->{rep.losses[-1]:.3f} "
                 if rep.losses else "")
        print(f"arch={args.arch} mode=async_ps updates={rep.steps_run} "
              f"{curve}staleness_hist={stale['hist']}")
        return 0
    events = []
    if args.revoke_at and args.members > 1:
        events.append(MembershipEvent(step=args.revoke_at, kind="revoke",
                                      member_id=args.members - 1))
    rep = session.train(args.steps, global_batch=args.global_batch,
                        seq_len=args.seq, members=args.members,
                        events=events, checkpoint_dir=args.checkpoint_dir)
    compressed = [e.payload for e in session.bus.of_kind("step")
                  if "payload_bytes" in e.payload]
    extra = (f" payload={compressed[-1]['payload_bytes']:.0f}B/"
             f"{compressed[-1]['grad_compression']}" if compressed else "")
    print(f"arch={args.arch} steps={rep.steps_run} "
          f"loss {rep.losses[0]:.3f}->{rep.losses[-1]:.3f} "
          f"speed={rep.speed or 0:.2f} steps/s epochs={rep.epochs} "
          f"checkpoints={rep.checkpoints}{extra}")
    return 0


def _cmd_serve(args) -> int:
    # encoder-only archs raise ValueError in serving.generate; main()
    # renders it as a clean error + exit 2
    session = cli.session_from_args(args)
    if args.fleet:
        from repro.serving import ServingSLO, ServingWorkload
        workload = ServingWorkload(n_requests=args.requests,
                                   arrival_rate_per_s=args.rate,
                                   prompt_tokens=args.prompt_len,
                                   max_tokens=args.tokens)
        best, plans = session.plan_serving(
            replica_counts=tuple(int(x) for x in
                                 args.replica_counts.split(",")),
            providers=tuple(args.providers.split(",")),
            gpu=args.gpu, workload=workload,
            slo=ServingSLO(p99_latency_s=args.slo_p99),
            resilience=cli.resilience_from_args(args),
            samples=args.plan_samples, seed=args.seed)
        print(f"# serving plan: arch={args.arch} gpu={args.gpu} "
              f"slo_p99={args.slo_p99}s requests={args.requests} "
              f"@{args.rate}/s")
        for p in plans:
            mark = "*" if p is best else " "
            print(f"{mark} {p.provider:<7s} {p.region:<16s} "
                  f"x{p.replicas:<3d} slo={'ok ' if p.meets_slo else 'MISS'}"
                  f" p50={p.latency_p50_s:7.3f}s p99={p.latency_p99_s:7.3f}s"
                  f" completed={p.completed_frac:5.1%}"
                  f" shed={p.shed_frac:5.1%} drop={p.drop_frac:5.1%}"
                  f" ${p.cost_per_1k:.4f}/1k")
        return 0
    rep = session.serve(args.tokens, batch=args.batch,
                        prompt_len=args.prompt_len,
                        temperature=args.temperature, seed=args.seed)
    print(f"arch={args.arch} batch={rep.batch} "
          f"prefill {rep.prompt_len} tok in {rep.prefill_seconds:.2f}s; "
          f"decode {rep.tokens_generated} tok in {rep.decode_seconds:.2f}s "
          f"({rep.tokens_per_second:.1f} tok/s)")
    print(f"decode latency per token: p50={rep.decode_ms_p50:.2f}ms "
          f"p95={rep.decode_ms_p95:.2f}ms p99={rep.decode_ms_p99:.2f}ms")
    print("sample tokens:", rep.sample_tokens)
    return 0


def _cmd_plan(args) -> int:
    session = cli.session_from_args(args)
    best, plans = session.plan(gpu=args.gpu, n_workers=args.workers,
                               steps=args.steps,
                               checkpoint_interval=args.checkpoint_interval,
                               region=args.region, seed=args.seed,
                               provider=args.provider, samples=args.samples,
                               score=args.score, engine=args.engine,
                               n_ps=args.n_ps)
    where = args.region or "all regions"
    what = ("simulated trajectories" if args.score == "sim" else "samples")
    print(f"arch={session.arch} provider={args.provider} gpu={args.gpu} "
          f"workers={args.workers} "
          f"({where}): scored {len(plans)} (region, hour) cells "
          f"x {args.samples} {what} [score={args.score}]")
    print(f"best: {best.region} @ {best.launch_hour:02d}h  "
          f"E[revocations]={best.expected_revocations:.2f}"
          f"±{best.revocation_stderr:.2f}  "
          f"E[time]={best.expected_time_s:.0f}s  "
          f"E[cost]=${best.expected_cost:.2f}")
    if args.score == "sim":
        print(f"      time p50={best.time_p50_s:.0f}s "
              f"p90={best.time_p90_s:.0f}s  "
              f"cost p50=${best.cost_p50:.2f} p90=${best.cost_p90:.2f}  "
              f"finished={best.finished}/{best.samples}")
    return 0


def _cmd_simulate(args) -> int:
    session = cli.session_from_args(args)
    res = session.simulate(n_workers=args.workers, gpu=args.gpu,
                           region=args.region, steps=args.steps,
                           checkpoint_interval=args.checkpoint_interval,
                           n_ps=args.n_ps, seed=args.seed,
                           provider=args.provider, samples=args.samples,
                           engine=args.engine)
    if args.samples > 1:
        st = res.stats
        print(f"arch={session.arch} {args.workers}x{args.gpu} on "
              f"{res.provider}/{res.region}: {st.n} trajectories")
        if st.finished < st.n:
            print(f"WARNING: only {st.finished}/{st.n} trajectories "
                  f"finished all {args.steps} steps (censored at "
                  f"max_hours or fully revoked) — the time/cost summary "
                  f"understates the true distribution")
        print(f"time  p50={st.time_p50_s:.0f}s p90={st.time_p90_s:.0f}s "
              f"mean={st.time_mean_s:.0f}±{st.time_stderr_s:.0f}s")
        print(f"cost  p50=${st.cost_p50:.2f} p90=${st.cost_p90:.2f} "
              f"mean=${st.cost_mean:.2f}±{st.cost_stderr:.2f}")
        print(f"revocations p50={st.revocations_p50:.1f} "
              f"p90={st.revocations_p90:.1f} "
              f"mean={st.revocations_mean:.2f}")
        return 0
    print(f"arch={session.arch} {args.workers}x{args.gpu} on "
          f"{res.provider}/{res.region}: "
          f"{res.steps_done} steps in {res.total_time_s:.0f}s  "
          f"revocations={res.revocations} replacements={res.replacements} "
          f"ckpt={res.checkpoint_time_s:.0f}s cost=${res.monetary_cost:.2f}")
    return 0


def _cmd_predict(args) -> int:
    session = cli.session_from_args(args)
    rep = session.predict(n_workers=args.workers, gpu=args.gpu,
                          region=args.region, steps=args.steps,
                          checkpoint_interval=args.checkpoint_interval,
                          n_ps=args.n_ps, seed=args.seed,
                          provider=args.provider)
    print(f"arch={rep.arch} {rep.n_workers}x{rep.gpu} on "
          f"{rep.provider}/{rep.region}: "
          f"worker {rep.worker_speed:.2f} steps/s, cluster "
          f"{rep.cluster_speed:.2f} steps/s"
          f"{' (PS-bottlenecked)' if rep.ps_bottlenecked else ''}")
    print(f"Eq(4): {rep.total_time_seconds:.0f}s for {args.steps} steps  "
          f"(T_c={rep.checkpoint_seconds:.2f}s, "
          f"E[revocations]={rep.expected_revocations:.2f})")
    return 0


def _cmd_chaos(args) -> int:
    import json

    from repro.chaos import list_scenarios
    from repro.chaos.runner import run_scenarios

    if args.list:
        print("\n".join(list_scenarios()))
        return 0
    session = cli.session_from_args(args)
    card = run_scenarios(args.scenario, session=session, engine=args.engine,
                         live=args.live, samples=args.samples,
                         seed=args.seed, smoke=args.smoke,
                         progress=lambda m: print(m, file=sys.stderr))
    print(json.dumps(card, indent=2, sort_keys=True))
    if args.smoke and not card["passed"]:
        fails = {name: c["smoke"]["failures"]
                 for name, c in card["scenarios"].items()
                 if not c["smoke"]["passed"]}
        print(f"chaos smoke gates FAILED: {fails}", file=sys.stderr)
        return 1
    return 0


def _cmd_bench(args) -> int:
    try:
        from benchmarks import run as bench_run
    except ImportError as e:
        print("benchmarks package not importable — run from the repo root "
              f"({e})", file=sys.stderr)
        return 2
    if args.list:
        print("\n".join(bench_run.MODULES))
        return 0
    return bench_run.main(["--only", args.only] if args.only else [])


def _cmd_dryrun(rest: List[str]) -> int:
    if "--sweep" in rest:
        # the sweep driver never imports jax itself (each cell runs in a
        # subprocess), so it must not pull in repro.launch.dryrun here
        from repro.launch import sweep
        return sweep.main([a for a in rest if a != "--sweep"])
    from repro.launch import dryrun
    dryrun.main(rest)
    return 0


_HANDLERS = {
    "train": _cmd_train, "serve": _cmd_serve, "plan": _cmd_plan,
    "simulate": _cmd_simulate, "predict": _cmd_predict,
    "chaos": _cmd_chaos, "bench": _cmd_bench,
}


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv[:1] == ["dryrun"]:
        # before anything imports jax: launch.dryrun pins the platform to
        # 512 host CPU devices, which jax reads once, at its import
        return _cmd_dryrun(argv[1:])
    if _CHECKOUT:
        jit_cache.place_compilation_cache(_CHECKOUT)
    args = build_parser().parse_args(argv)
    try:
        return _HANDLERS[args.cmd](args)
    except ValueError as e:
        # domain validation (e.g. a (region, gpu) cell the selected
        # provider never sold) — report cleanly, not as a traceback.
        # Unknown provider/arch never reach here: argparse `choices`
        # rejects them first, and internal KeyErrors stay loud.
        print(f"error: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
