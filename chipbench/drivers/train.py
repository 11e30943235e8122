"""Training traffic: the program's transient trainer at one configuration.

Set-up builds one trainer, wired as `Session.train` wires it (the
program's `TransientTrainer` with one member, holder `worker-0`, the run's
resilience setting and no recalibration; the program's synthetic token
source for the configuration), but with weights the benchmark makes on
the device from the seed. It drives that trainer from the seed through
its first steps and hands the same trainer and state to the window, which
trains until the window closes. The first three steps are compared with
the plain reference once the window has closed.

`Session.train` itself is not the entry: it takes its weights from the
program's own initialiser or from a checkpoint, and the reference may
take no weights the program made; a checkpoint of the seed's weights
would cost every run a write and a restore of the whole state.

The configuration file maps onto the program's `ModelConfig` by its
`program` block: `model_config` holds fields given as they are, and
`from_config` names, for each other field, the configuration key (a dotted
path into nested groups) that it takes its value from.

`train_tokens_per_s` counts the tokens of the window's steps over the
window's elapsed time.
"""
from __future__ import annotations

import gc
import math
import shutil
import tempfile
import time
from typing import List

import jax
import jax.numpy as jnp

from chipbench.harness import Check, Outcome, memory_peak_bytes

# the host spans this driver writes into a traced window
SPANS = ("train_call",)

# set-up steps that the reference follows
REFERENCE_STEPS = 3
# `Session.train`'s default for the controller's check interval
CHECK_EVERY = 10


def _lookup(c: dict, path: str):
    for key in path.split("."):
        c = c[key]
    return c


def model_config(c: dict):
    """The program's `ModelConfig` for a configuration file."""
    from repro.configs.base import ModelConfig
    p = c["program"]
    kw = dict(p["model_config"])
    kw.update({f: _lookup(c, k) for f, k in p["from_config"].items()})
    return ModelConfig(**kw)


def run_config(c: dict, seed: int, ckpt_dir: str):
    from repro.configs.base import RunConfig
    tr = c["training"]
    return RunConfig(optimizer=tr["optimizer"], lr=tr["lr"],
                     weight_decay=tr["weight_decay"],
                     warmup_steps=tr["warmup_steps"],
                     total_steps=tr["total_steps"],
                     grad_clip=tr["grad_clip"], checkpoint_interval=0,
                     checkpoint_dir=ckpt_dir, zero1=False, seed=seed)


class Run:
    """The state of one training window, and the readings it leaves."""

    def __init__(self, cell):
        from repro.api import Session
        from repro.core.trainer import TransientTrainer
        from repro.data.pipeline import ShardedLoader, source_for_config
        from repro.dist.elastic import Member
        from repro.launch.steps import TrainState
        from repro.models import api

        cell.mark("import_program")
        self.cell, self.c, self.tf = cell, cell.config, cell.traffic
        self.ref = cell.reference()
        self.seed = cell.seed
        self.batch, self.seq = self.tf["global_batch"], self.tf["seq_len"]
        self.cfg = model_config(self.c)
        self.ckpt_dir = tempfile.mkdtemp(prefix="chipbench_ckpt_")
        run = run_config(self.c, self.seed, self.ckpt_dir)
        self.stamps: List[tuple] = []        # (step, monotonic, loss)
        self.session = Session(self.cfg, run, arch=self.c["name"])
        self.session.bus.subscribe("step", lambda k, p: self.stamps.append(
            (p["step"], time.monotonic(), p["loss"])))
        loader = ShardedLoader(source_for_config(self.cfg, self.seq,
                                                 seed=run.seed), self.batch)
        self.trainer = TransientTrainer(
            self.cfg, run, loader, members=[Member(0)], holder="worker-0",
            on_event=lambda k, p: self.session.bus.emit(k, **p),
            resilience=run.resilience)
        cell.mark("trainer")
        want = jax.tree.structure(api.param_shapes(self.cfg))
        params = self.ref.init_params(self.c, self.seed)
        if jax.tree.structure(params) != want:
            raise ValueError("the program's weight tree is not the "
                             f"reference's layout: {want}")
        cell.mark("weights")
        self.state = TrainState(params, jax.jit(self.trainer.opt.init)(
            params), jnp.zeros((), jnp.int32), ())
        cell.mark("optimizer_state")

    def train(self, n: int) -> None:
        self.state, _ = self.trainer.run_steps(self.state, n,
                                               check_every=CHECK_EVERY)

    def setup(self) -> dict:
        """The first steps, through the window's own call and feed, with
        the readings the reference is compared on."""
        b1 = self.c["training"]["b1"]
        self.train(1)
        self.cell.mark("first_step")
        m_norms = self.ref.leaf_norms(self.state.opt["m"])
        grad = {k: v / (1 - b1) for k, v in m_norms.items()}
        self.train(REFERENCE_STEPS - 1)
        self.cell.mark("steps")
        change = self.ref.change_norms(self.c, self.seed, self.state.params)
        self.cell.mark("change_norms")
        losses = [s[2] for s in self.stamps[:REFERENCE_STEPS]]
        # the window's step count is set from a warm step's time
        self.step_s = self.stamps[-1][1] - self.stamps[-2][1]
        return {"loss": losses, "grad": grad, "change": change}

    def close(self) -> None:
        self.state = self.trainer = self.session = None
        gc.collect()
        shutil.rmtree(self.ckpt_dir, ignore_errors=True)


def run(cell) -> Outcome:
    tf = cell.traffic
    r = Run(cell)
    prog = r.setup()
    n0 = len(r.stamps)
    with cell.window():
        n = max(1, int(cell.remaining() / r.step_s))
        with cell.span("train_call"):
            r.train(n)
        jax.block_until_ready(r.state)
    elapsed = cell.window_end - cell.window_start
    stamps = r.stamps[n0:]
    losses = [s[2] for s in stamps]
    steps = len(stamps)
    end_to_end = {"train_tokens_per_s": steps * r.batch * r.seq / elapsed}
    facts = {"steps": steps,
             "flops_per_step": r.ref.train_flops(cell.config, r.batch,
                                                 r.seq),
             "window_s": elapsed, "train_call_steps": steps}
    failed = sum(not math.isfinite(x) for x in losses)
    peak = memory_peak_bytes()
    r.close()
    del r
    gc.collect()
    ref = cell.reference()
    batches = [ref.TokenFeed(cell.config["vocab_size"], tf["seq_len"],
                             cell.seed).batch(t, 0, 1, tf["global_batch"])
               for t in range(REFERENCE_STEPS)]
    want = ref.train_readings(cell.config, cell.config["training"],
                              cell.seed, batches)
    gaps = ref.compare(prog, want)
    limits = cell.config["limits"]
    checks = [Check(k, v, limits[k]) for k, v in gaps.items()]
    return Outcome(attempted=steps, failed=failed, end_to_end=end_to_end,
                   facts=facts, checks=checks, memory_peak_bytes=peak)


def worst_leaves(got: dict, want: dict) -> dict:
    """The leaf behind each leaf gap, to see what sets the number."""
    out = {}
    for k in ("grad", "change"):
        med = sorted(want[k].values())[len(want[k]) // 2]
        out[k] = max(want[k], key=lambda leaf: abs(
            got[k][leaf] - want[k][leaf]) / max(want[k][leaf], med))
    return out


def readings(root: str, workload: str, seeds, control_seeds,
             require_tpu: bool = True, emit=print):
    """Program (lower) readings: one trainer's set-up steps per seed
    against the float32 reference. Control and fault (upper) readings:
    the reference in the program's place, with float8 matmul operands,
    and with half of each batch left out."""
    from chipbench import harness

    harness.place_compile_cache(root)
    cell = harness.Cell(root, workload, 0, 0.0, False)
    harness.device_info(require_tpu, cell.workload["chips"])
    c, tf = cell.config, cell.traffic
    ref = cell.reference()
    out = []

    def batches(seed):
        feed = ref.TokenFeed(c["vocab_size"], tf["seq_len"], seed)
        return [feed.batch(t, 0, 1, tf["global_batch"])
                for t in range(REFERENCE_STEPS)]

    for kind, seed_list in (("program", seeds), ("control", control_seeds)):
        for seed in seed_list:
            cell.seed = seed
            prog = None
            if kind == "program":
                r = Run(cell)
                prog = r.setup()
                r.close()
                del r
                gc.collect()
            want = ref.train_readings(c, c["training"], seed, batches(seed))
            rows = []
            if kind == "program":
                rows.append(("program", prog))
            else:
                rows.append(("fp8", ref.train_readings(
                    c, c["training"], seed, batches(seed), rounding="fp8")))
                rows.append(("half_batch", ref.train_readings(
                    c, c["training"], seed, batches(seed),
                    rows=tf["global_batch"] // 2)))
            for name, got in rows:
                line = {"reading": name, "seed": seed,
                        **ref.compare(got, want),
                        "worst": worst_leaves(got, want)}
                out.append(line)
                emit(line)
    return out
