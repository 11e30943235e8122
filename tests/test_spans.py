"""The program's profiler spans: the trainer loop, the fleet engine, the
checkpointer and Python's garbage collections, read back from a CPU trace
(`.xplane.pb`) with `jax.profiler.ProfileData`."""
import gc
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from repro.chaos.injectors import FaultTimeline, PreemptionWave
from repro.configs import RunConfig, get_config
from repro.core import profiler
from repro.core.trainer import TransientTrainer
from repro.core.transient.fleet import FleetSim, SimWorker
from repro.data.pipeline import ShardedLoader, SyntheticTokenSource
from repro.resilience import ResilienceConfig

PREFIXES = ("train.", "fleet.", "ckpt.", "python.gc")


def traced(tmp_path, fn):
    """Run `fn` under the profiler; the program's spans it wrote, as
    (name, start, end, stats), in start order."""
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith(PREFIXES)]
    return sorted(out, key=lambda e: e[1])


def inside(outer, events, name):
    return [e for e in events if e[0] == name
            and outer[1] <= e[1] and e[2] <= outer[2]]


def names(events, name):
    return [e for e in events if e[0] == name]


@pytest.fixture(scope="module")
def trainer(tmp_path_factory):
    cfg = get_config("qwen3-1.7b", smoke=True)
    run = RunConfig(total_steps=40, warmup_steps=2, checkpoint_interval=0,
                    checkpoint_dir=str(tmp_path_factory.mktemp("ckpt")),
                    lr=1e-3, zero1=False)
    tr = TransientTrainer(cfg, run, ShardedLoader(
        SyntheticTokenSource(cfg.vocab_size, 24), 8))
    state, _ = tr.restore_or_init()
    state, _ = tr.run_steps(state, 1)            # compiles outside traces
    # the step donates its state: tests hand the newest one on here
    return {"trainer": tr, "state": state}


def test_each_train_step_holds_data_dispatch_sync_observe(trainer,
                                                          tmp_path):
    tr = trainer["trainer"]
    ev = traced(tmp_path, lambda: trainer.update(
        state=tr.run_steps(trainer["state"], 3)[0]))
    steps = names(ev, "train.step")
    assert len(steps) == 3
    for s in steps:
        for part in ("train.data", "train.dispatch", "train.sync",
                     "train.observe"):
            assert len(inside(s, ev, part)) == 1, (part, s)
        data, = inside(s, ev, "train.data")
        assert data[3]["tokens"] == 8 * 24
    assert len(names(ev, "train.data")) == 3


def test_an_expert_model_puts_its_routing_counters_on_the_sync_span(
        tmp_path):
    """DeepSeek-V2's smoke block: 2 expert layers, every expert held,
    top-2, so each step computes 2 x tokens x 2 assignments. The counters
    ride the loss's transfer onto `train.sync` and into the `step` event;
    a dense model's `train.sync` carries none."""
    cfg = get_config("deepseek-v2-lite-16b", smoke=True)
    run = RunConfig(total_steps=40, warmup_steps=2, checkpoint_interval=0,
                    checkpoint_dir=str(tmp_path / "ckpt"), zero1=False)
    steps = []
    tr = TransientTrainer(cfg, run, ShardedLoader(
        SyntheticTokenSource(cfg.vocab_size, 16), 4),
        on_event=lambda k, p: steps.append(p) if k == "step" else None)
    state, _ = tr.restore_or_init()
    state, _ = tr.run_steps(state, 1)
    ev = traced(tmp_path, lambda: tr.run_steps(state, 2))
    syncs = names(ev, "train.sync")
    assert len(syncs) == 2
    for sync in syncs:
        assert sync[3]["moe_routed_held"] == 2 * 4 * 16 * 2
        assert 0 < sync[3]["moe_max_load"] <= 4 * 16
    assert [p["moe_routed_held"] for p in steps] == [2 * 4 * 16 * 2] * 3


def test_a_dense_model_sync_span_has_no_counters(trainer, tmp_path):
    tr = trainer["trainer"]
    ev = traced(tmp_path, lambda: trainer.update(
        state=tr.run_steps(trainer["state"], 1)[0]))
    sync, = names(ev, "train.sync")
    assert not set(sync[3]) & {"moe_routed_held", "moe_max_load"}


def test_save_and_restore_write_checkpoint_spans(trainer, tmp_path):
    tr, state = trainer["trainer"], trainer["state"]
    got = {}
    ev = traced(tmp_path, lambda: got.update(
        sizes=tr.ckpt.save(int(state.step), state)))
    write, = names(ev, "ckpt.write")
    assert write[3]["bytes"] == got["sizes"].total
    assert len(inside(write, ev, "ckpt.crc32")) == len(jax.tree.leaves(state))
    assert [e[0] for e in ev if e[0].startswith("ckpt.")
            and e[0] != "ckpt.crc32"] == ["ckpt.copy", "ckpt.write",
                                          "ckpt.commit"]

    fresh = TransientTrainer(tr.cfg, tr.run, ShardedLoader(
        SyntheticTokenSource(tr.cfg.vocab_size, 24), 8), holder="worker-1",
        resilience=ResilienceConfig())
    ev = traced(tmp_path / "restore", fresh.restore_or_init)
    assert [e[0] for e in ev if e[0].startswith("ckpt.")] == [
        "ckpt.validate", "ckpt.read", "ckpt.put"]


def test_a_jit_ensemble_under_a_wave_writes_the_fleet_spans(tmp_path):
    roster = [(i, "k80", "us-central1", 4.56) for i in range(4)]
    wave = FaultTimeline([PreemptionWave(0.5, 1.0, 6.0,
                                         region="us-central1")],
                         roster, seed=3)
    sim = FleetSim([SimWorker(*w) for w in roster], model_gflops=1.54,
                   model_bytes=1866856.0, step_speed_of=lambda gpu: 4.56,
                   checkpoint_interval_steps=4000, checkpoint_time_s=3.84,
                   seed=11, replace=True, handover=True, chaos=wave)
    ev = traced(tmp_path, lambda: sim.run_many(64000, 32, engine="jit"))
    call, = names(ev, "fleet.run_many")
    assert call[3]["n"] == 32
    for part in ("fleet.draws", "fleet.setup"):
        assert len(inside(call, ev, part)) == 1, part
    # the engine's `SimResult`s, then the ensemble's statistics
    assert len(inside(call, ev, "fleet.results")) == 2
    pools = inside(call, ev, "fleet.pools")
    assert pools
    for p in pools:
        levels = p[3]["levels"]
        assert levels & (levels - 1) == 0
        assert p[3]["draws"] == levels * 4 * 32       # one wave
    loops = inside(call, ev, "fleet.loop")
    assert loops and loops[0][3]["regrow"] == 0
    # every doubling of the pools is followed by a regrown entry
    assert sum(e[3]["regrow"] for e in loops) == len(pools) - 1
    compact, = inside(call, ev, "fleet.compact")
    assert compact[3]["rows"] == 32


def test_trace_gc_writes_a_collection_span(tmp_path):
    def collect():
        loops = [[] for _ in range(100)]
        for x in loops:
            x.append(x)
        del loops
        gc.collect()

    profiler.trace_gc()
    profiler.trace_gc()
    try:
        assert gc.callbacks.count(profiler._gc_callback) == 1
        ev = traced(tmp_path, collect)
    finally:
        gc.callbacks.remove(profiler._gc_callback)
    full = [e for e in names(ev, "python.gc") if e[3]["generation"] == 2]
    assert full and full[-1][3]["collected"] >= 100
