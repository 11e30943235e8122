"""Run the system's main path once on a TPU and check what comes out.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # jit fleet engine over every chip

One process, through the same `Session` entry points as `python -m repro`:

  kernels  each Pallas kernel once at real widths, against its
           `kernels/ref.py` oracle, with the Mosaic call in the program
  fleet    `Session.simulate` (the jit fleet engine) under the
           regional_wave chaos timeline, and exact revocation/replacement
           parity with the event loop on the same draws
  train    qwen3-1.7b at its published widths (d_model 2048, 16 q / 8 kv
           heads x 128, d_ff 6144, vocab 151936, tied embeddings), depth
           cut to LAYERS; AdamW with f32 state; a few steps, a checkpoint
  resume   a fresh Session restores that checkpoint and trains on
  serve    `Session.serve` through `GatewayEngine`, then the logits of
           prefill-then-decode through the cache against one forward pass

`--four-chips` runs only the fleet phase, with the trajectory axis sharded
over all visible devices. A failed check raises and nothing falls back to
the CPU. Every phase prints one JSON line; the last line of stdout is
`{"ok": true, "device": {...}}`.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen3-1.7b"
# Depth is the only cut; every width is as published. At 4 x 512 tokens
# per step the v5e compiler counts 14.1e9 bytes for the train step at 5
# layers (params and AdamW state 6.8e9, temporaries 7.3e9) and 15.5e9 at
# 6, too close to the chip's 16 GiB (tests/test_tpu_compile.py).
LAYERS = 5
BATCH, SEQ = 4, 512           # tokens per training step
STEPS, RESUME_STEPS = 4, 3    # the checkpoint lands after STEPS
SLOTS, PROMPT, NEW_TOKENS = 8, 128, 32
FLEET_N, PARITY_N = 65536, 4096
SCENARIO = "regional_wave"
# Prefill-then-decode against one forward pass, as max|diff| / max|logit|
# at each position. Both run bf16 activations (unit roundoff 2**-8)
# through different attention code, so rounding alone gives a few 1e-3;
# a cache that loses its writes moves the logits by a large share of
# their scale, which the run shows by decoding with the writes dropped.
DECODE_TOL = 2e-2
# kernel inputs at real widths: rmsnorm over qwen3-1.7b activations,
# its attention (batch, seq, q heads, kv heads, head dim), the SSD scan of
# mamba2-1.3b (batch, seq, heads, head dim, groups, d_state, chunk), and
# the fleet engine's event matrix (trajectories, candidate events)
RMS_SHAPE = (8, 2048, 2048)
ATTN = (1, 2048, 16, 8, 128)
SSD = (1, 2048, 64, 64, 1, 128, 256)
EVENTS = (65536, 16)
# kernels against their f32 oracles, as max|diff| / max|oracle|: bf16
# operands and outputs (unit roundoff 2**-8), f32 accumulation
KERNEL_TOL = 2e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def import_repro() -> None:
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro", "api")):
        sys.exit(f"chip_smoke.py: no src/repro beside {HERE}; run it from "
                 "a checkout of the repository")
    sys.path.insert(0, src)


def model_config():
    from repro.configs import get_config
    return get_config(ARCH).with_(n_layers=LAYERS)


def run_config(seed: int = 0):
    from repro.configs import RunConfig
    return RunConfig(lr=3e-4, warmup_steps=1,
                     total_steps=STEPS + RESUME_STEPS,
                     checkpoint_interval=STEPS, zero1=False, seed=seed)


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


# ---------------------------------------------------------------- kernels
def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    bf = jnp.bfloat16
    normal = lambda k, shape, dt=jnp.float32: jax.random.normal(  # noqa
        k, shape, jnp.float32).astype(dt)

    def one(name, fn, oracle, args, exact=False):
        compiled = jax.jit(fn).lower(*args).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no Mosaic kernel in the compiled program")
        got = jax.tree.leaves(jax.block_until_ready(compiled(*args)))
        with jax.default_matmul_precision("highest"):
            want = jax.tree.leaves(jax.jit(oracle)(*args))
        for g, w in zip(got, want):
            check(g.shape == w.shape, f"{name}: shape {g.shape} != {w.shape}")
        if exact:
            check(all(bool(jnp.all(g == w)) for g, w in zip(got, want)),
                  f"{name}: differs from its oracle")
            return report("kernels", kernel=name, exact=True)
        err = max(rel_err(g, w) for g, w in zip(got, want))
        check(err < KERNEL_TOL, f"{name}: rel err {err}")
        report("kernels", kernel=name, rel_err=err)

    x = normal(ks[0], RMS_SHAPE, bf)
    scale = normal(ks[1], RMS_SHAPE[-1:])
    one("rmsnorm", ops.rmsnorm, ref.rmsnorm_ref, (x, scale))

    b, s, h, kv, hd = ATTN
    q = normal(ks[2], (b, s, h, hd), bf)
    k = normal(ks[3], (b, s, kv, hd), bf)
    v = normal(ks[4], (b, s, kv, hd), bf)
    one("flash_attention_fwd",
        lambda q, k, v: ops.flash_attention(q, k, v, True),
        lambda q, k, v: ref.flash_attention_ref(q, k, v, True), (q, k, v))
    w = jnp.cos(jnp.arange(hd, dtype=jnp.float32))

    def grads(attn):
        return jax.grad(lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * w), argnums=(0, 1, 2))
    one("flash_attention_bwd",
        grads(lambda q, k, v: ops.flash_attention(q, k, v, True)),
        grads(lambda q, k, v: ref.flash_attention_ref(q, k, v, True)),
        (q, k, v))

    b, s, h, p, g, n, chunk = SSD
    xs = normal(ks[5], (b, s, h, p), bf)
    dt = jax.nn.softplus(normal(ks[6], (b, s, h))).astype(bf)
    A = -jnp.exp(normal(ks[7], (h,)) * 0.5)
    B = normal(ks[0], (b, s, g, n), bf)
    C = normal(ks[1], (b, s, g, n), bf)
    one("ssd_scan", lambda *a: ops.ssd_scan(*a, chunk),
        lambda *a: ref.ssd_scan_ref(*a, chunk=chunk), (xs, dt, A, B, C))

    rng = np.random.default_rng(seed)
    ev = rng.uniform(0.0, 1e6, EVENTS).astype(np.float32)
    ev[rng.random(ev.shape) < 0.3] = np.inf
    one("event_select", ops.event_select, ref.event_select_ref,
        (jnp.asarray(ev),), exact=True)


# ------------------------------------------------------------------ fleet
def fleet_simulate():
    """A session, its `simulate` under the SCENARIO chaos timeline (the
    fleet sized for the smoke model's step time), and the event-loop
    oracle: `run_many(n, engine="event")` on the sim `simulate` builds,
    under the same timeline."""
    from repro.api import Session
    from repro.chaos.scenarios import get_scenario

    sc = get_scenario(SCENARIO)
    s = Session.from_arch(ARCH, smoke=True)
    kw = dict(n_workers=sc.n_workers, gpu=sc.gpu, region=sc.region,
              steps=sc.total_steps, seed=0, handover=sc.handover,
              provider=sc.provider)
    sim, n_steps = s._fleet_sim(**kw)
    sim.chaos = sc.timeline(sim._roster, seed=0)
    return (s, functools.partial(s.simulate, **kw, max_hours=sc.max_hours,
                                 chaos=sim.chaos),
            functools.partial(sim.run_many, n_steps, max_hours=sc.max_hours,
                              engine="event"))


def phase_fleet(n: int, phase: str = "fleet") -> None:
    s, run, oracle = fleet_simulate()
    counts = {}
    for engine, ens in (("jit", run(samples=PARITY_N)),
                        ("event", oracle(PARITY_N))):
        counts[engine] = [(r.revocations, r.replacements)
                          for r in ens.results]
    check(counts["jit"] == counts["event"],
          "the jit engine and the event loop disagree on "
          "revocation/replacement counts over the same draws")
    check(sum(r for r, _ in counts["jit"]) > 0,
          f"{SCENARIO} revoked no worker in {PARITY_N} trajectories")

    walls = []
    for _ in range(2):                 # cold (compiles), then warm
        t0 = time.monotonic()
        ens = run(samples=n)
        walls.append(time.monotonic() - t0)
    times = np.array([r.total_time_s for r in ens.results])
    check(len(times) == n and bool(np.all(np.isfinite(times))),
          "non-finite fleet trajectory times")
    check(s.bus.handler_errors == 0, "event-bus handler raised")
    report(phase, scenario=SCENARIO, parity_n=PARITY_N, parity="exact",
           n=n, cold_s=walls[0], warm_s=walls[1],
           trajectories_per_s=n / walls[1],
           revocations_mean=ens.stats.revocations_mean,
           time_p50_s=ens.stats.time_p50_s)


def phase_fleet_sharded() -> None:
    import jax

    from repro.core.transient import fleet_jit

    devs = jax.devices()
    check(len(devs) > 1, "--four-chips needs more than one device")
    n = len(devs) * FLEET_N
    # the placement run_jit gives the trajectory state
    traj_sh, _ = fleet_jit._shard(n)
    check(traj_sh is not None, "fleet_jit did not shard the trajectories")
    probe = fleet_jit._put(np.zeros(n, np.float32), traj_sh)
    rows = {sh.device.id: sh.data.shape[0] for sh in probe.addressable_shards}
    check(sorted(rows) == sorted(d.id for d in devs)
          and set(rows.values()) == {n // len(devs)},
          f"trajectory rows per device: {rows}")
    del probe
    phase_fleet(n, phase="fleet_sharded")
    peaks = [peak_bytes(d) for d in devs]
    check(min(peaks) > 0.25 * max(peaks),
          f"sharded run left devices idle: peak bytes {peaks}")
    report("fleet_sharded", rows_per_device=rows, peak_bytes=peaks)


# ---------------------------------------------------- train, resume, serve
def phase_train(cfg, run, ckpt: str):
    import jax

    from repro.api import Session

    s = Session(cfg, run, arch=ARCH)
    stamps = []
    s.bus.subscribe("step", lambda k, p: stamps.append(time.monotonic()))
    t0 = time.monotonic()
    rep = s.train(STEPS, global_batch=BATCH, seq_len=SEQ,
                  checkpoint_dir=ckpt)
    # each step ends on float(loss), which waits for the step program
    step_s = np.diff([t0] + stamps)
    ln_v = math.log(cfg.vocab_size)
    check(len(rep.losses) == STEPS
          and all(math.isfinite(x) for x in rep.losses),
          f"losses {rep.losses}")
    check(abs(rep.losses[0] - ln_v) < 0.1 * ln_v,
          f"first loss {rep.losses[0]} is not near ln(vocab) {ln_v}")
    check(rep.checkpoints == 1, f"{rep.checkpoints} checkpoints saved")
    check(s.bus.handler_errors == 0, "event-bus handler raised")
    report("train", arch=ARCH, layers=cfg.n_layers,
           params=cfg.param_count(), tokens_per_step=BATCH * SEQ,
           losses=rep.losses, init_and_first_step_s=step_s[0],
           step_s=step_s[1:].tolist(),
           tokens_per_s=BATCH * SEQ / float(np.median(step_s[1:])),
           save_s=s.trainer.ckpt.last_save_seconds,
           memory_stats=jax.devices()[0].memory_stats(),
           **compiled_step(s, cfg, run))


def compiled_step(s, cfg, run) -> dict:
    """The compiler's memory and FLOP counts for the program the trainer
    ran: its own jitted step, lowered at the batch it was given. The FLOP
    count takes the body of the layer scan once, whatever the depth (see
    `models/transformer.py`), so it is below the step's real FLOPs."""
    import jax
    import jax.numpy as jnp

    from repro.launch import steps as st

    tok = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    compiled = s.trainer._jit_step.lower(
        st.train_state_specs(cfg, run),
        {"tokens": tok, "labels": tok}).compile()
    mem = compiled.memory_analysis()
    return {"step_flops": compiled.cost_analysis()["flops"],
            "step_argument_bytes": mem.argument_size_in_bytes,
            "step_output_bytes": mem.output_size_in_bytes,
            "step_alias_bytes": mem.alias_size_in_bytes,
            "step_temp_bytes": mem.temp_size_in_bytes}


def phase_resume(cfg, run, ckpt: str):
    from repro.api import Session

    s = Session(cfg, run, arch=ARCH)
    marks, steps = {}, []
    s.bus.subscribe("restore", lambda k, p: marks.update(
        step=p["step"], t=time.monotonic()))
    s.bus.subscribe("step", lambda k, p: steps.append(p["step"]))
    t0 = time.monotonic()
    rep = s.train(RESUME_STEPS, global_batch=BATCH, seq_len=SEQ,
                  checkpoint_dir=ckpt)
    check(marks.get("step") == STEPS,
          f"restored step {marks.get('step')}, saved {STEPS}")
    check(steps == list(range(STEPS, STEPS + RESUME_STEPS)),
          f"resumed run took steps {steps}")
    check(all(math.isfinite(x) for x in rep.losses), f"losses {rep.losses}")
    check(s.bus.handler_errors == 0, "event-bus handler raised")
    report("resume", restored_step=marks["step"],
           resume_s=marks["t"] - t0, losses=rep.losses)
    return s


def phase_serve(s, seed: int) -> None:
    rep = s.serve(NEW_TOKENS, batch=SLOTS, prompt_len=PROMPT, seed=seed + 1)
    gen = np.asarray(rep.generated)
    check(gen.shape == (SLOTS, NEW_TOKENS), f"generated {gen.shape}")
    check(gen.min() >= 0 and gen.max() < s.cfg.vocab_size,
          "token ids outside the vocabulary")
    check(s.bus.handler_errors == 0, "event-bus handler raised")
    report("serve", slots=SLOTS, prompt_len=PROMPT, new_tokens=NEW_TOKENS,
           tokens_per_s=rep.tokens_per_second,
           prefill_s=rep.prefill_seconds, decode_s=rep.decode_seconds,
           decode_ms_p50=rep.decode_ms_p50, decode_ms_p99=rep.decode_ms_p99)


def phase_decode_vs_forward(cfg, params, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import api

    toks = jax.random.randint(jax.random.PRNGKey(seed + 2), (SLOTS, PROMPT),
                              0, cfg.vocab_size)
    full = jax.jit(lambda p, t: api.prefill(p, cfg, {"tokens": t}))(
        params, toks)
    state0, _ = api.init_decode_state(cfg, SLOTS, PROMPT)

    @jax.jit
    def decode_err(params, state, toks, full, i):
        """Decode position i through `state`; max|diff| / max|logit|
        against the forward pass's logits there."""
        lg, state = api.decode_step(params, cfg, state, toks[:, i],
                                    jnp.full((SLOTS,), i, jnp.int32))
        want = full[:, i].astype(jnp.float32)
        return (jnp.max(jnp.abs(lg.astype(jnp.float32) - want))
                / jnp.max(jnp.abs(want))), state

    def errors(keep_cache: bool) -> np.ndarray:
        state, out = state0, []
        for i in range(PROMPT):
            e, nxt = decode_err(params, state, toks, full, jnp.int32(i))
            state = nxt if keep_cache else state0
            out.append(e)
        return np.asarray(jnp.stack(out))

    through_cache = errors(True)
    dropped = errors(False)            # every earlier cache write lost
    check(float(through_cache.max()) < DECODE_TOL,
          f"decode vs forward: max rel err {through_cache.max()}")
    check(float(dropped[1:].max()) > DECODE_TOL,
          "decoding with the cache writes dropped passes the tolerance")
    report("decode_vs_forward", positions=PROMPT, tol=DECODE_TOL,
           max_rel_err=float(through_cache.max()),
           dropped_writes_max_rel_err=float(dropped[1:].max()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the jit fleet engine with its "
                         "trajectories sharded over all devices")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for data, prompts and kernel inputs")
    args = ap.parse_args(argv)
    import_repro()
    from repro.core import jit_cache
    jit_cache.place_compilation_cache(HERE)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke.py: JAX found no TPU ({device}); nothing is run "
              "elsewhere", file=sys.stderr)
        return 1
    report("device", **device)

    if args.four_chips:
        phase_fleet_sharded()
    else:
        phase_kernels(args.seed)
        phase_fleet(FLEET_N)
        cfg, run = model_config(), run_config(args.seed)
        with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
            phase_train(cfg, run, ckpt)
            gc.collect()               # drop the first session's state
            s = phase_resume(cfg, run, ckpt)
        phase_serve(s, args.seed)
        phase_decode_vs_forward(cfg, s.params, args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
