"""§VI-B bandwidth-mitigation path, end to end: gradient compression with
error feedback in the train step (payload telemetry, checkpointable
residual), PS-capacity recalibration by `compression_ratio`, the
controller's detect -> act -> recalibrate loop, the async-PS Session mode,
and the satellite fixes (mitigate_ps golden, restores counter, profiler
step_time)."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Session
from repro.configs import RunConfig, get_config
from repro.core.controller import Action, Controller
from repro.core.perf_model.cluster_model import (PSBottleneckModel,
                                                 WorkerSpec, cluster_speed)
from repro.core.profiler import PerformanceProfiler
from repro.core.ps_async import ps_queue_sim
from repro.core.scheduler import plan_launch
from repro.core.trainer import TransientTrainer
from repro.data.pipeline import ShardedLoader, SyntheticTokenSource
from repro.dist.compression import compression_ratio, payload_bytes


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen3-1.7b", smoke=True)


def _run(cfg, scheme, steps=10, ckpt_dir=None, interval=0):
    run = RunConfig(total_steps=steps, warmup_steps=1, lr=1e-3, zero1=False,
                    checkpoint_interval=interval,
                    checkpoint_dir=ckpt_dir or tempfile.mkdtemp(),
                    grad_compression=scheme)
    src = SyntheticTokenSource(cfg.vocab_size, 24)
    tr = TransientTrainer(cfg, run, ShardedLoader(src, 8))
    state, _ = tr.restore_or_init()
    return tr, *tr.run_steps(state, steps)


# --------------------------------------------------- compressed train step
def test_compressed_step_reports_payload_bytes(cfg):
    s = Session.from_arch("qwen3-1.7b", total_steps=4, warmup_steps=1,
                          checkpoint_interval=0, lr=1e-3, zero1=False,
                          grad_compression="int8")
    rep = s.train(4, global_batch=4, seq_len=32,
                  checkpoint_dir=tempfile.mkdtemp())
    assert rep.steps_run == 4 and not np.isnan(rep.losses).any()
    steps = s.bus.of_kind("step")
    # payload telemetry is the measured wire size, not a config echo:
    # int8 = 1 byte per gradient value = the live parameter tree's size
    n_values = sum(int(l.size)
                   for l in jax.tree.leaves(s._last_state.params))
    assert all(e.payload["grad_compression"] == "int8" for e in steps)
    assert all(e.payload["payload_bytes"] == n_values for e in steps)


def test_error_feedback_convergence_parity(cfg):
    """Fixed-seed loss trajectories under bf16/int8 stay within tolerance
    of the uncompressed run — the error-feedback guarantee."""
    finals = {}
    for scheme in ("none", "bf16", "int8"):
        _, _, rep = _run(cfg, scheme, steps=10)
        assert not np.isnan(rep.losses).any()
        finals[scheme] = rep.final_loss
    for scheme in ("bf16", "int8"):
        assert finals[scheme] == pytest.approx(finals["none"], rel=0.05)


def test_payload_bytes_helper(cfg):
    tree = {"a": jnp.zeros((3, 4)), "b": jnp.zeros((5,))}
    assert payload_bytes(tree, "none") == 17 * 4
    assert payload_bytes(tree, "bf16") == 17 * 2
    assert payload_bytes(tree, "int8") == 17 * 1
    with pytest.raises(ValueError):
        compression_ratio("int4")


# -------------------------------------------------- residual checkpointing
def test_residual_survives_checkpoint_restore(cfg):
    ckpt = tempfile.mkdtemp()
    tr, state, rep = _run(cfg, "int8", steps=8, ckpt_dir=ckpt, interval=4)
    assert rep.checkpoints == 2
    # a fresh worker (new trainer) restores the same residual tree
    run = tr.run
    tr2 = TransientTrainer(cfg, run,
                           ShardedLoader(SyntheticTokenSource(
                               cfg.vocab_size, 24), 8), holder="worker-9")
    tr2.ckpt.lease.notify_revoked()
    state2, start = tr2.restore_or_init()
    assert start == 8
    saved = jax.tree.leaves(state.residual)
    back = jax.tree.leaves(state2.residual)
    assert len(saved) == len(back) > 0
    assert any(np.abs(np.asarray(a)).max() > 0 for a in back)
    for a, b in zip(saved, back):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_legacy_checkpoint_restores_with_zero_residual(cfg):
    """A checkpoint written before compression was on restores into a
    compressed run with a freshly zeroed residual (no KeyError)."""
    ckpt = tempfile.mkdtemp()
    _run(cfg, "none", steps=4, ckpt_dir=ckpt, interval=4)
    run = RunConfig(total_steps=4, warmup_steps=1, lr=1e-3, zero1=False,
                    checkpoint_interval=4, checkpoint_dir=ckpt,
                    grad_compression="int8")
    tr = TransientTrainer(cfg, run, ShardedLoader(
        SyntheticTokenSource(cfg.vocab_size, 24), 8), holder="worker-9")
    tr.ckpt.lease.notify_revoked()
    state, start = tr.restore_or_init()
    assert start == 4 and tr.restores == 1
    leaves = jax.tree.leaves(state.residual)
    assert leaves and all(np.abs(np.asarray(l)).max() == 0 for l in leaves)


# ------------------------------------------------ capacity recalibration
def test_ps_capacity_scales_with_compression_ratio():
    # net-bound model (few tensors): int8 payload -> 4x capacity
    ps = PSBottleneckModel(1.25e9, 1)
    for scheme in ("bf16", "int8"):
        scaled = PSBottleneckModel(1.25e9, 1, compression=scheme)
        assert scaled.capacity_steps_per_s() == pytest.approx(
            ps.capacity_steps_per_s() / compression_ratio(scheme))
    # RPC-bound model: compression shrinks bytes, not per-tensor RPCs
    rpc = PSBottleneckModel(1.87e6, 1, n_tensors=97)
    rpc8 = PSBottleneckModel(1.87e6, 1, n_tensors=97, compression="int8")
    assert rpc8.capacity_steps_per_s() == pytest.approx(
        rpc.capacity_steps_per_s())


def test_mitigate_ps_preserves_n_tensors_golden():
    """Golden: ResNet-32-like RPC-bound PS (97 tensors, ~41 updates/s per
    PS). Adding a PS must double capacity, not drop the RPC term (which
    silently inflated it to the network-only ~668 updates/s)."""
    ps = PSBottleneckModel(1.87e6, 1, n_tensors=97)
    before = ps.capacity_steps_per_s()
    assert before == pytest.approx(40.9, abs=0.1)
    after = Controller().mitigate_ps(ps)
    assert after.n_tensors == 97
    assert after.rpc_per_tensor == ps.rpc_per_tensor
    assert after.compression == ps.compression
    assert after.capacity_steps_per_s() == pytest.approx(2 * before)


def test_plan_launch_threads_compression_through_ps_cap():
    """A PS-capped plan under int8 predicts a faster run than the same
    plan uncompressed (the §VI-B recalibration reaching §V-C)."""
    kw = dict(n_w=100_000, i_c=4_000, t_c=3.84, hours=[0], seed=0,
              samples=16)
    ps_none = PSBottleneckModel(1.25e9, 1)           # capacity 0.5 steps/s
    ps_int8 = PSBottleneckModel(1.25e9, 1, compression="int8")
    best_none, _ = plan_launch("v100", 4, 10.0, ps=ps_none, **kw)
    best_int8, _ = plan_launch("v100", 4, 10.0, ps=ps_int8, **kw)
    best_flat, _ = plan_launch("v100", 4, 10.0, **kw)  # uncapped baseline
    assert best_int8.expected_time_s < best_none.expected_time_s
    assert best_flat.expected_time_s < best_int8.expected_time_s


def test_session_predict_reflects_compression():
    from repro.core.perf_model.cluster_model import PS_RPC_PER_TENSOR_S
    base = Session.from_arch("qwen3-1.7b")
    comp = Session.from_arch("qwen3-1.7b", grad_compression="int8")
    p0 = base.predict(n_workers=2, gpu="v100")
    p8 = comp.predict(n_workers=2, gpu="v100")
    assert p0.grad_compression == "none" and p8.grad_compression == "int8"
    assert p8.payload_bytes == pytest.approx(p0.payload_bytes / 4)
    # the smoke model is RPC-bound (rpc term > network term), so the
    # ceiling is set by its tensor count and compression can NOT raise it
    assert base.n_tensors() * PS_RPC_PER_TENSOR_S \
        > 2 * base.model_bytes() / 1.25e9
    assert p0.ps_capacity == pytest.approx(
        1.0 / (base.n_tensors() * PS_RPC_PER_TENSOR_S))
    assert p8.ps_capacity == pytest.approx(p0.ps_capacity)
    # a network-bound payload DOES gain the full ratio (unit-level check
    # in test_ps_capacity_scales_with_compression_ratio)


def test_session_plan_accepts_ps_cap():
    s = Session.from_arch("qwen3-1.7b", total_steps=500,
                          checkpoint_interval=100)
    best_uncapped, _ = s.plan(gpu="v100", n_workers=2, hours=[0],
                              samples=16)
    best_capped, _ = s.plan(gpu="v100", n_workers=2, hours=[0],
                            samples=16, n_ps=1)
    # the smoke model's payload is small: the cap may or may not bind,
    # but the capped plan can never be faster than the uncapped one
    assert best_capped.expected_time_s >= best_uncapped.expected_time_s


# --------------------------------------------------- controller mitigation
def _stalled_profiler(measured: float, n: int = 12) -> PerformanceProfiler:
    prof = PerformanceProfiler(window=2, warmup_steps=0, warmup_seconds=0.0)
    t = 0.0
    for s in range(n):
        prof.record(s, t=t)
        t += 1.0 / measured
    return prof


def test_controller_escalates_compression_then_ps():
    ps = PSBottleneckModel(1.25e9, 1)                # capacity 0.5 steps/s
    workers = [WorkerSpec("v100", 2.0)] * 4          # demand 8 steps/s
    ctrl = Controller()
    prof = _stalled_profiler(measured=0.5)
    det = ctrl.check(prof, predicted_speed=8.0, ps_model=ps, workers=workers)
    assert det.bottleneck and det.action is Action.ENABLE_COMPRESSION
    ps = ctrl.mitigate_compression(ps, "int8")
    assert ps.compression == "int8"
    # still saturated (8 > 2.0): the next rung is sparser compression
    det2 = ctrl.check(prof, predicted_speed=8.0, ps_model=ps,
                      workers=workers)
    assert det2.action is Action.ENABLE_COMPRESSION
    ps = ctrl.mitigate_compression(ps, "topk")
    assert ps.compression == "topk"


def test_controller_adds_ps_when_topk_is_not_enough():
    ps = PSBottleneckModel(1.25e9, 1, compression="topk")  # capacity 25
    workers = [WorkerSpec("v100", 10.0)] * 4               # demand 40
    ctrl = Controller()
    prof = _stalled_profiler(measured=20.0)
    # the compression ladder is exhausted: the only lever left is more PS
    det = ctrl.check(prof, predicted_speed=40.0, ps_model=ps,
                     workers=workers)
    assert det.bottleneck and det.action is Action.ADD_PARAMETER_SERVER
    ps = ctrl.mitigate_ps(ps)
    assert (ps.n_ps, ps.compression) == (2, "topk")


def test_synthetic_bottleneck_mitigation_raises_measured_speed():
    """The acceptance scenario: a saturated PS measured by the queueing
    emulation, the controller's mitigation applied, and the re-measured
    cluster speed going up."""
    compute_times = [0.25] * 4                       # demand 16 steps/s
    model_bytes = 1.25e9                             # capacity 0.5 steps/s
    before = ps_queue_sim(compute_times, model_bytes, steps=60)
    ctrl = Controller()
    ps = PSBottleneckModel(model_bytes, 1)
    workers = [WorkerSpec("v100", 1.0 / 0.25)] * 4
    det = ctrl.check(_stalled_profiler(before.cluster_speed, n=24),
                     predicted_speed=cluster_speed(workers),
                     ps_model=ps, workers=workers)
    assert det.bottleneck and det.action is Action.ENABLE_COMPRESSION
    ps = ctrl.mitigate_compression(ps, "int8")
    after = ps_queue_sim(compute_times, model_bytes, steps=60,
                         grad_compression=ps.compression)
    assert after.cluster_speed > 3 * before.cluster_speed
    # second lever, same loop: one more PS doubles it again
    ps = ctrl.mitigate_ps(ps)
    more = ps_queue_sim(compute_times, model_bytes, n_ps=ps.n_ps, steps=60,
                        grad_compression=ps.compression)
    assert more.cluster_speed > 1.5 * after.cluster_speed


def test_trainer_applies_mitigation_mid_run(cfg):
    """End to end: the controller detects PS saturation mid-run, the
    trainer flips the train step to int8 (new residual, payload telemetry
    on later steps) and re-derives its prediction from the recalibrated
    capacity."""
    ps = PSBottleneckModel(5e9, 1)                   # capacity 0.125
    workers = [WorkerSpec("v100", 1e4)] * 4
    run = RunConfig(total_steps=16, warmup_steps=1, checkpoint_interval=0,
                    checkpoint_dir=tempfile.mkdtemp(), lr=1e-3, zero1=False)
    evs = []
    tr = TransientTrainer(cfg, run,
                          ShardedLoader(SyntheticTokenSource(
                              cfg.vocab_size, 24), 8),
                          ps_model=ps, workers=workers, predicted_speed=4e4,
                          on_event=lambda k, p: evs.append((k, p)))
    state, _ = tr.restore_or_init()
    state, rep = tr.run_steps(state, 16, check_every=5)
    assert [m["action"] for m in rep.mitigations] == ["enable_compression"]
    assert tr.run.grad_compression == "int8"
    assert tr.ps_model.capacity_steps_per_s() == pytest.approx(0.5)
    assert tr.predicted_speed == pytest.approx(
        cluster_speed(workers, tr.ps_model))
    mitigated_at = rep.mitigations[0]["step"]
    compressed = [p for k, p in evs
                  if k == "step" and "payload_bytes" in p]
    assert compressed and all(p["step"] > mitigated_at for p in compressed)
    assert jax.tree.leaves(state.residual)           # residual attached
    assert not np.isnan(rep.losses).any()


def test_mitigated_compression_sticks_across_restore(cfg):
    """A mid-run ENABLE_COMPRESSION outlives the process: a restart whose
    config still says "none" resumes compressed with its residual (the
    scheme is run state, recorded in checkpoint metadata)."""
    ckpt = tempfile.mkdtemp()
    _run(cfg, "int8", steps=8, ckpt_dir=ckpt, interval=4)
    run = RunConfig(total_steps=4, warmup_steps=1, lr=1e-3, zero1=False,
                    checkpoint_interval=4, checkpoint_dir=ckpt)
    assert run.grad_compression == "none"
    tr = TransientTrainer(cfg, run, ShardedLoader(
        SyntheticTokenSource(cfg.vocab_size, 24), 8), holder="worker-9")
    tr.ckpt.lease.notify_revoked()
    state, start = tr.restore_or_init()
    assert start == 8
    assert tr.run.grad_compression == "int8"
    leaves = jax.tree.leaves(state.residual)
    assert leaves and any(np.abs(np.asarray(l)).max() > 0 for l in leaves)


def test_mitigation_guard_respects_cap(cfg):
    run = RunConfig(total_steps=16, warmup_steps=1, checkpoint_interval=0,
                    checkpoint_dir=tempfile.mkdtemp(), lr=1e-3, zero1=False)
    tr = TransientTrainer(cfg, run,
                          ShardedLoader(SyntheticTokenSource(
                              cfg.vocab_size, 24), 8),
                          ps_model=PSBottleneckModel(5e9, 1),
                          workers=[WorkerSpec("v100", 1e4)] * 4,
                          predicted_speed=4e4, max_mitigations=0)
    state, _ = tr.restore_or_init()
    state, rep = tr.run_steps(state, 12, check_every=5)
    assert rep.mitigations == []                     # detected but capped
    assert any(d.bottleneck for d in rep.detections)
    assert tr.run.grad_compression == "none"


# ------------------------------------------------------- async-PS mode
def test_session_async_ps_mode_emits_staleness_histogram():
    s = Session.from_arch("qwen3-1.7b", total_steps=10, lr=1e-3,
                          zero1=False)
    rep = s.train(10, global_batch=4, seq_len=32, members=3,
                  mode="async_ps")
    assert rep.steps_run == 10
    assert not np.isnan(rep.losses).any()
    assert len(s.bus.of_kind("async_step")) == 10
    stale = s.bus.of_kind("staleness")
    assert len(stale) == 1
    payload = stale[0].payload
    assert sum(payload["hist"].values()) == 10
    assert max(payload["hist"]) >= 1                 # staleness occurred
    assert set(payload["worker_updates"]) == {0, 1, 2}
    assert set(payload["worker_step_time"]) == {0, 1, 2}
    assert all(t > 0 for t in payload["worker_step_time"].values())
    with pytest.raises(ValueError):
        s.train(2, mode="definitely-not-a-mode")
    # serve() after an async train uses the trained weights, like sync
    assert s._last_state is not None
    assert jax.tree.leaves(s._last_state.params)
    # sync-only arguments are rejected loudly, not silently dropped
    with pytest.raises(ValueError, match="checkpoint_dir"):
        s.train(2, mode="async_ps", checkpoint_dir=tempfile.mkdtemp())
    with pytest.raises(ValueError, match="worker_step_times"):
        s.train(2, mode="sync", worker_step_times=[0.1, 0.2])


# ------------------------------------------------------- satellite fixes
def test_restores_counter_reported(cfg):
    ckpt = tempfile.mkdtemp()
    _run(cfg, "none", steps=8, ckpt_dir=ckpt, interval=4)
    run = RunConfig(total_steps=4, warmup_steps=1, lr=1e-3, zero1=False,
                    checkpoint_interval=4, checkpoint_dir=ckpt)
    tr = TransientTrainer(cfg, run, ShardedLoader(
        SyntheticTokenSource(cfg.vocab_size, 24), 8), holder="worker-9")
    tr.ckpt.lease.notify_revoked()
    state, start = tr.restore_or_init()
    assert start == 8
    _, rep = tr.run_steps(state, 2)
    assert rep.restores == 1                         # was always 0


def test_profiler_step_time_distinguishes_stall_from_no_data():
    prof = PerformanceProfiler(window=2, warmup_steps=0, warmup_seconds=0.0)
    assert prof.step_time() is None                  # genuinely no data
    prof.record(5, t=0.0)
    prof.record(5, t=1.0)                            # stalled: 0.0 steps/s
    assert prof.speed() == 0.0
    assert prof.step_time() == float("inf")          # data, not None
    prof.record(6, t=1.5)
    assert prof.step_time() == pytest.approx(1.5 / 1)


# ------------------------------------------------------- perf gate (CI)
def test_bench_regression_gate(tmp_path):
    import importlib.util
    import json
    import pathlib
    spec = importlib.util.spec_from_file_location(
        "check_bench_regression",
        pathlib.Path(__file__).parent.parent / "scripts"
        / "check_bench_regression.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def write(name, speedup):
        p = tmp_path / name
        p.write_text(json.dumps({
            "planner_grid": {"speedup": speedup, "batched_s": 0.01},
            "ensemble": {"traj_per_s": 100.0}}))
        return str(p)

    base = write("base.json", 50.0)
    assert mod.main(["--baseline", base,
                     "--current", write("ok.json", 45.0)]) == 0
    assert mod.main(["--baseline", base,                      # >20% slower
                     "--current", write("bad.json", 30.0)]) == 1
    # a current file missing the planner-grid metric fails the gate
    (tmp_path / "nogrid.json").write_text(json.dumps({"ensemble": {}}))
    assert mod.main(["--baseline", base,
                     "--current", str(tmp_path / "nogrid.json")]) == 1
    (tmp_path / "empty.json").write_text("{}")
    assert mod.main(["--baseline", str(tmp_path / "empty.json"),
                     "--current", base]) == 1


# ------------------------------------------------------------------ CLI
def test_cli_mode_and_compression_flags():
    from repro.launch import cli
    p = cli.make_parser("t", "t")
    cli.add_arch_arg(p)
    cli.add_scale_args(p)
    cli.add_batch_args(p)
    cli.add_train_args(p)
    args = p.parse_args(["--steps", "5", "--mode", "async_ps",
                         "--grad-compression", "int8"])
    assert args.mode == "async_ps"
    run = cli.run_config_from_args(args)
    assert run.grad_compression == "int8"
    with pytest.raises(SystemExit):
        p.parse_args(["--grad-compression", "fp4"])
