"""The comparison that decides `correct` fails its control and every
fault a cell can have, at a tiny size on the CPU."""
import os
import time

import pytest

from chipbench import harness
from chipbench.drivers import fleet, train
from chipbench.tests.conftest import TINY


def test_float8_control_and_half_batch_fail_where_the_program_passes(
        tiny_tree):
    rows = train.readings(tiny_tree, "train-steady", [3], [4],
                                  require_tpu=False, emit=lambda s: None)
    limits = TINY["limits"]
    fails = lambda row: any(row[k] > v for k, v in limits.items())  # noqa
    by = {r["reading"]: r for r in rows}
    assert not fails(by["program"]), by["program"]
    assert fails(by["fp8"]), by["fp8"]
    assert fails(by["half_batch"]), by["half_batch"]


def _unchanged(step):
    def broken(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return broken


def _half_batch(step):
    def broken(state, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return step(state, half)
    return broken


@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_a_broken_train_step_reads_not_correct(tiny_tree, monkeypatch,
                                               fault):
    import jax

    from repro.core import jit_cache
    from repro.core.trainer import TransientTrainer

    real = TransientTrainer._build_step

    def build(cfg, run):
        step, opt, _ = real(cfg, run)
        broken = fault(step)
        return broken, opt, jax.jit(broken)

    jit_cache.clear()
    monkeypatch.setattr(TransientTrainer, "_build_step",
                        staticmethod(build))
    try:
        r = harness.run_cell(tiny_tree, "train-steady", 7, 2.0, False,
                             time.monotonic(), require_tpu=False,
                             log=lambda s: None)
    finally:
        jit_cache.clear()
    assert r["correct"] is False, r["checks"]


def test_float32_fleet_control_fails_where_the_program_passes(tiny_tree):
    from chipbench.tests.conftest import load_json
    rows = fleet.readings(tiny_tree, "fleet-chaos", [3], [4],
                                  require_tpu=False, emit=lambda s: None)
    limits = load_json(os.path.join(tiny_tree, "chipbench", "configs",
                                    "fleet-resnet32-4xk80.json"))["limits"]
    fails = lambda row: any(row[k] > v for k, v in limits.items())  # noqa
    by = {r["reading"]: r for r in rows}
    assert not fails(by["program"]), by["program"]
    assert fails(by["float32"]), by["float32"]


def _answers_altered(run_jit):
    """Every trajectory's wall time moved by a second where the engine
    produces it."""
    def broken(*a, **kw):
        out = run_jit(*a, **kw)
        for r in out:
            r.total_time_s += 1.0
        return out
    return broken


def _half_the_ensemble(run_jit):
    """Only the first half of the trajectories simulated, the second half
    copied from it."""
    def broken(sim, total_steps, n, *a, **kw):
        out = run_jit(sim, total_steps, n, *a, **kw)
        return out[: n // 2] + out[: n - n // 2]
    return broken


@pytest.mark.parametrize("fault", [_answers_altered, _half_the_ensemble])
def test_a_broken_fleet_engine_reads_not_correct(tiny_tree, monkeypatch,
                                                 fault):
    from repro.core.transient import fleet_jit

    monkeypatch.setattr(fleet_jit, "run_jit", fault(fleet_jit.run_jit))
    r = harness.run_cell(tiny_tree, "fleet-chaos", 7, 2.0, False,
                         time.monotonic(), require_tpu=False,
                         log=lambda s: None)
    assert r["correct"] is False, r["checks"]
