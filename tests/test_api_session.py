"""`repro.api.Session` facade + `python -m repro` CLI smoke coverage:
plan -> simulate -> predict on a reduced config, elastic training through
the event bus, and the shared argparse helpers."""
import jax
import numpy as np
import pytest

from repro.api import EventBus, Session
from repro.configs import RunConfig
from repro.core.trainer import MembershipEvent


@pytest.fixture(scope="module")
def session():
    return Session.from_arch("qwen3-1.7b", total_steps=200,
                             checkpoint_interval=50, zero1=False)


def test_from_arch_resolves_and_describes(session):
    info = session.describe()
    assert info["arch"] == "qwen3-1.7b"
    assert info["params"] > 0
    assert session.model_gflops() > 0
    with pytest.raises(KeyError):
        Session.from_arch("not-an-arch")


def test_plan_scores_region_hour_grid(session):
    best, plans = session.plan(gpu="v100", n_workers=2, steps=500,
                               hours=[0, 12])
    regions = {p.region for p in plans}
    assert len(plans) == 2 * len(regions)
    assert best.expected_cost == min(p.expected_cost for p in plans)
    assert best.n_workers == 2


def test_simulate_transient_run(session):
    res = session.simulate(n_workers=3, gpu="v100", steps=300, seed=0)
    assert res.steps_done == 300
    assert res.total_time_s > 0
    assert res.monetary_cost > 0
    # handover policy never loses steps to recomputation
    assert res.recompute_time_s == 0.0


def test_predict_composes_eq4(session):
    rep = session.predict(n_workers=2, gpu="v100", steps=1000,
                          checkpoint_interval=100)
    assert rep.cluster_speed <= 2 * rep.worker_speed + 1e-9
    # Eq (4) total >= pure compute + checkpoint time
    floor = 1000 / rep.cluster_speed + 10 * rep.checkpoint_seconds
    assert rep.total_time_seconds >= floor - 1e-6
    assert 0 <= rep.expected_revocations <= 2


def test_train_emits_bus_events(tmp_path):
    s = Session.from_arch("qwen3-1.7b", total_steps=12, warmup_steps=1,
                          checkpoint_interval=5, lr=1e-3, zero1=False)
    rep = s.train(12, global_batch=4, seq_len=32, members=2,
                  events=[MembershipEvent(step=4, kind="revoke",
                                          member_id=1)],
                  checkpoint_dir=str(tmp_path))
    assert rep.steps_run == 12
    assert not np.isnan(rep.losses).any()
    steps_seen = [e.payload["step"] for e in s.bus.of_kind("step")]
    assert steps_seen == list(range(12))
    epochs = s.bus.of_kind("epoch")
    assert len(epochs) == 1 and epochs[0].payload["kind"] == "revoke"
    assert len(s.bus.of_kind("checkpoint")) == rep.checkpoints


def test_event_bus_wildcard_and_history():
    bus = EventBus(keep_history=3)
    got = []
    bus.subscribe("*", lambda kind, p: got.append(kind))
    bus.on("a")(lambda kind, p: got.append("only-" + kind))
    for k in ("a", "b", "c", "d"):
        bus.emit(k, x=1)
    assert got == ["only-a", "a", "b", "c", "d"]
    assert [e.kind for e in bus.history] == ["b", "c", "d"]  # bounded


# ------------------------------------------------------------------ CLI
def test_cli_parser_covers_all_subcommands():
    from repro.__main__ import _HANDLERS, build_parser
    p = build_parser()
    for argv in (["train", "--arch", "qwen3-1.7b", "--steps", "3"],
                 ["serve", "--tokens", "4"],
                 ["plan", "--gpu", "k80"],
                 ["simulate", "--workers", "2"],
                 ["predict"],
                 ["bench", "--only", "table1_speed"]):
        args = p.parse_args(argv)
        assert args.cmd == argv[0]
        assert args.cmd in _HANDLERS
    # dryrun dispatches before argparse (its flags belong to launch.dryrun)
    assert "dryrun" not in _HANDLERS


def test_cli_run_config_mapping():
    from repro.launch import cli
    p = cli.make_parser("t", "t")
    cli.add_arch_arg(p)
    cli.add_scale_args(p)
    cli.add_batch_args(p)
    cli.add_train_args(p)
    args = p.parse_args(["--steps", "40", "--lr", "0.01", "--seed", "7"])
    run = cli.run_config_from_args(args)
    assert isinstance(run, RunConfig)
    assert (run.total_steps, run.lr, run.seed) == (40, 0.01, 7)
    assert run.warmup_steps == 4
    session = cli.session_from_args(args)
    assert session.arch == "qwen3-1.7b" and session.run.total_steps == 40


def test_bench_driver_exit_codes():
    from benchmarks import run as bench_run
    assert bench_run.main(["--list"]) == 0
    assert bench_run.main(["--only", "definitely_not_a_module"]) == 2


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compilation_cache_placed_in_checkout_unless_env_names_one(
        env_dir, tmp_path, monkeypatch):
    """Entry points keep JAX's compile cache at `<checkout>/.jax_cache`;
    where JAX_COMPILATION_CACHE_DIR is set, JAX reads it and no code
    sets another path."""
    from repro.core import jit_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    try:
        jit_cache.place_compilation_cache(str(tmp_path))
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_dir is None:
        assert after == str(tmp_path / ".jax_cache")
    else:
        assert after == before


def test_cli_dryrun_pins_cpu_before_jax_is_imported():
    """`python -m repro dryrun` hands over to launch.dryrun before anything
    imports jax, so its CPU platform and 512 host devices hold even where
    JAX_PLATFORMS is unset (a TPU host). The cell is one valid_cells skips,
    so nothing compiles."""
    import json
    import os
    import subprocess
    import sys

    code = ("import json, sys\n"
            "from repro.__main__ import main\n"
            "rc = main(['dryrun', '--arch', 'qwen3-1.7b', "
            "'--shape', 'long_500k'])\n"
            "import jax\n"
            "print(json.dumps({'rc': rc, "
            "'platforms': jax.config.jax_platforms, "
            "'backend': jax.default_backend(), "
            "'devices': jax.device_count()}))\n")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + [p for p in [env.get("PYTHONPATH")] if p])
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[0])["skipped"] is True
    assert json.loads(lines[-1]) == {"rc": 0, "platforms": "cpu",
                                     "backend": "cpu", "devices": 512}
