"""Transient-aware elastic training loop — the TPU-native CM-DARE runtime.

Integrates: sharded train_step (launch/steps.py), resumable data pipeline,
lease-based checkpointing, performance profiler, bottleneck controller, and
a revocation schedule (from the fleet simulator or injected by tests).

Loop contract per step:
  1. drain membership events (revocations / joins) -> roll epoch, re-split
     batch, possibly steal the checkpoint-writer lease;
  2. fetch the epoch's data shards (deterministic in (seed, step, shard));
  3. jit'd train_step;
  4. profiler.record; controller.check on a cadence;
  5. checkpoint on the interval (writer-lease holder only).

Steps 2-4 run inside `jax.profiler` spans (`train.step` holding
`train.data`, `train.dispatch`, `train.sync`, `train.observe`), which a
profile of the run shows beside the device operations; the names are
listed in docs/performance.md.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.checkpoint import Checkpointer
from repro.checkpoint.checkpointer import CheckpointCorruptError
from repro.configs.base import ModelConfig, RunConfig
from repro.resilience import (ResilienceConfig, RetryExhausted,
                              call_with_retries)
from repro.core import jit_cache
from repro.core.controller import Action, Controller, Detection
from repro.core.perf_model.cluster_model import (PSBottleneckModel,
                                                 WorkerSpec, cluster_speed)
from repro.core.profiler import PerformanceProfiler
from repro.data.pipeline import ShardedLoader
from repro.dist import sharding as sh
from repro.dist.elastic import ElasticMembership, Member
from repro.launch import steps as st
from repro.models import api
from repro.models.layers import MOE_COUNTERS


@dataclasses.dataclass
class MembershipEvent:
    step: int
    kind: str            # revoke | join
    member_id: int
    gpu: str = "v5e"


@dataclasses.dataclass
class TrainReport:
    steps_run: int
    final_loss: float
    losses: List[float]
    speed: Optional[float]
    epochs: int
    checkpoints: int
    restores: int
    detections: List[Detection]
    wall_seconds: float
    #: §VI-B mitigations applied mid-run (see `apply_mitigation` payloads)
    mitigations: List[dict] = dataclasses.field(default_factory=list)
    #: checkpoint saves that failed (chaos checkpoint-store outage)
    checkpoint_failures: int = 0
    #: chaos faults injected mid-run (see `inject_fault` payloads)
    faults: List[dict] = dataclasses.field(default_factory=list)
    #: recovery accounting (resilience enabled; docs/resilience.md)
    retries: int = 0                    # backoff retries beyond attempt 1
    recovered_saves: int = 0            # saves that landed after failures
    fallback_depth: int = 0             # checkpoint generations skipped
    paused_steps: int = 0               # step slots skipped below quorum
    degradations: List[dict] = dataclasses.field(default_factory=list)
    #: online-recalibration ledgers (recalibration armed; docs/calibration.md)
    drift_events: List[dict] = dataclasses.field(default_factory=list)
    refits: List[dict] = dataclasses.field(default_factory=list)


class TransientTrainer:
    def __init__(self, cfg: ModelConfig, run: RunConfig, loader: ShardedLoader,
                 members: Optional[List[Member]] = None,
                 holder: str = "worker-0",
                 predicted_speed: Optional[float] = None,
                 on_event: Optional[Callable[[str, dict], None]] = None,
                 ps_model: Optional[PSBottleneckModel] = None,
                 workers: Optional[List[WorkerSpec]] = None,
                 auto_mitigate: bool = True,
                 mitigation_scheme: str = "int8",
                 max_mitigations: int = 8,
                 clock: Optional[Callable[[], float]] = None,
                 resilience: Optional[ResilienceConfig] = None,
                 recalibrator: Optional[object] = None):
        self.cfg = cfg
        self.run = run
        self.loader = loader
        self._emit = on_event or (lambda kind, payload: None)
        self.members = ElasticMembership(
            members or [Member(0)], loader.global_batch)
        self.profiler = PerformanceProfiler(window=10, warmup_steps=5,
                                            warmup_seconds=0.0)
        self.controller = Controller()
        # the writer lease shares the trainer's clock, so chaos
        # VirtualClock scenarios exercise lease expiry without sleeping
        self.ckpt = Checkpointer(run.checkpoint_dir, holder=holder,
                                 clock=clock or time.time)
        self.predicted_speed = predicted_speed
        # §VI-B mitigation loop state: a PS capacity model + worker specs
        # let the controller attribute a slowdown to PS saturation and let
        # the trainer *act* on it mid-run (apply_mitigation)
        if ps_model is not None and ps_model.compression != run.grad_compression:
            ps_model = dataclasses.replace(ps_model,
                                           compression=run.grad_compression)
        self.ps_model = ps_model
        self.workers = workers
        self.auto_mitigate = auto_mitigate
        self.mitigation_scheme = mitigation_scheme
        # backstop against mitigation loops: adding a PS is self-limiting
        # (the controller stops once capacity exceeds demand), but a badly
        # mis-set prediction could otherwise re-fire on every check
        self.max_mitigations = max_mitigations
        # chaos hooks: an injectable profiler clock (virtual time makes
        # detection latency deterministic across machines) and live fault
        # state the chaos driver toggles via `inject_fault`
        self.clock = clock
        self.ckpt_outage = False
        self.ckpt_failures = 0
        self.faults: List[dict] = []
        self.restores = 0
        self.mitigations: List[dict] = []
        # recovery layer (docs/resilience.md): None keeps every legacy
        # code path byte-identical
        self.resilience = resilience
        # under a virtual clock a backoff sleep must not block the host
        self._sleep: Callable[[float], None] = (
            (lambda s: None) if clock is not None else time.sleep)
        self.retries = 0
        self.recovered_saves = 0
        self.fallback_depth = 0
        self.paused_steps = 0
        self.degradations: List[dict] = []
        # online recalibration (docs/calibration.md): None keeps the
        # static-prediction path byte-identical (golden contract)
        self.recalibrator = recalibrator
        if recalibrator is not None:
            recalibrator.bind(self._emit)
            if predicted_speed:
                recalibrator.seed(predicted_speed)
            self.controller.model_version = recalibrator.version
        self._rebuild_step()
        self.detections: List[Detection] = []

    def _rebuild_step(self) -> None:
        # jit/lower artifacts are memoized across trainers/Sessions keyed
        # on (cfg, trace-relevant run fields, mesh, rules) — rebuilding a
        # Session no longer re-traces an identical step (jit_cache); the
        # key includes run.grad_compression, so the quantized step and the
        # plain step cache separately
        cfg, run = self.cfg, self.run
        self.train_step, self.opt, self._jit_step = jit_cache.cached(
            "train_step",
            (cfg, jit_cache.normalized_run(run), None, sh.MEGATRON_RULES),
            lambda: self._build_step(cfg, run))

    @staticmethod
    def _build_step(cfg: ModelConfig, run: RunConfig):
        train_step, opt = st.make_train_step(cfg, run)
        return train_step, opt, jax.jit(train_step, donate_argnums=(0,))

    # ------------------------------------------------------------------ state
    def init_state(self, key=None) -> st.TrainState:
        params, _ = api.init(self.cfg, key)
        return st.TrainState(params, self.opt.init(params),
                             jnp.zeros((), jnp.int32),
                             st.init_residual(params, self.run))

    def restore_or_init(self, key=None) -> Tuple[st.TrainState, int]:
        # a mid-run ENABLE_COMPRESSION must outlive the process: the
        # scheme is run *state* recorded in the checkpoint metadata, so a
        # restart whose config still says "none" resumes compressed (and
        # keeps its error-feedback residual) instead of silently reverting
        try:
            saved = self.ckpt.read_meta().get("grad_compression", "none")
        except (FileNotFoundError, ValueError):
            saved = "none"
        if saved != "none" and self.run.grad_compression == "none":
            self.run = dataclasses.replace(self.run, grad_compression=saved)
            self._rebuild_step()
            if self.ps_model is not None:
                self.ps_model = dataclasses.replace(self.ps_model,
                                                    compression=saved)
        shapes = jax.eval_shape(self.init_state, key)
        try:
            try:
                state, step = self._restore_validated(shapes)
                residual = state.residual
            except KeyError:
                # checkpoint predates compression (no residual leaves):
                # restore the legacy (params, opt, step) triple and start
                # the error-feedback residual from zero
                legacy = st.TrainState(shapes.params, shapes.opt, shapes.step)
                state, step = self.ckpt.restore(legacy)
                residual = jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), shapes.residual)
            with TraceAnnotation("ckpt.put"):     # host to device
                state = jax.tree.map(jnp.asarray, state)
                residual = jax.tree.map(jnp.asarray, residual)
            self.loader.step = step
            self.restores += 1
            self._emit("restore", {"step": step, "restores": self.restores})
            return st.TrainState(state.params, state.opt,
                                 jnp.asarray(step, jnp.int32), residual), step
        except FileNotFoundError:
            return self.init_state(key), 0
        except CheckpointCorruptError as exc:
            # every committed generation failed validation: surface it and
            # restart clean rather than load torn state
            self._emit("restore_failed", {"error": str(exc)})
            return self.init_state(key), 0

    def _restore_validated(self, shapes):
        """Restore under the resilience policy: retry the read, validate
        checksums, and fall back generation-by-generation past torn or
        corrupt checkpoints (``restore_fallback`` events record each skip).
        With resilience disabled this is the legacy strict restore."""
        res = self.resilience
        if res is None:
            return self.ckpt.restore(shapes)

        def on_fallback(step, exc):
            self.fallback_depth += 1
            self._emit("restore_fallback", {"step": step,
                                            "depth": self.fallback_depth,
                                            "error": str(exc)})

        def attempt():
            tree, step, _depth = self.ckpt.restore_latest_valid(
                shapes, on_fallback=on_fallback)
            return tree, step

        try:
            (tree, step), attempts = call_with_retries(
                attempt, res.retry, op="restore", seed=self.run.seed,
                key=-1, sleep=self._sleep, emit=self._emit,
                retry_on=(CheckpointCorruptError,))
        except RetryExhausted as exc:
            self.retries += exc.attempts - 1
            raise exc.last
        self.retries += attempts - 1
        return tree, step

    # ------------------------------------------------------------------- run
    def run_steps(self, state: st.TrainState, n_steps: int,
                  events: Optional[List[MembershipEvent]] = None,
                  check_every: int = 10) -> Tuple[st.TrainState, TrainReport]:
        events = sorted(events or [], key=lambda e: e.step)
        ev_i = 0
        losses: List[float] = []
        checkpoints = 0
        t0 = time.monotonic()
        start_step = int(state.step)
        steps_run = 0
        base_global_batch = self.loader.global_batch
        tier = "continue"
        for local in range(n_steps):
            step = start_step + local
            # 1. membership events at this step boundary
            while ev_i < len(events) and events[ev_i].step <= step:
                ev = events[ev_i]
                ev_i += 1
                if ev.kind == "revoke":
                    if ev.member_id not in self.members:
                        # stale schedule entry (member already gone — e.g. a
                        # replayed fleet timeline after a restore): ignore
                        continue
                    epoch = self.members.revoke(ev.member_id)
                    # revoked writer: lease handover (Fig 11 fix)
                    if not self.ckpt.lease.held_by_me():
                        self.ckpt.lease.notify_revoked()
                        if self.ckpt.lease.try_acquire():
                            self._emit("lease_handover",
                                       {"step": step,
                                        "holder": self.ckpt.lease.holder,
                                        "revoked_member": ev.member_id})
                else:
                    if ev.member_id in self.members:
                        continue  # stale join (already present)
                    epoch = self._join_member(ev)
                self._emit("epoch", {"step": step, "kind": ev.kind,
                                     "member_id": ev.member_id,
                                     "epoch": epoch.number,
                                     "n_alive": len(epoch.members)})
                if not epoch.members:
                    raise RuntimeError("all members revoked")
            # 1b. quorum degradation tier (docs/resilience.md): pause skips
            # this step slot entirely (future joins can restore quorum),
            # shrink temporarily scales the global batch down
            new_tier = ("continue" if self.resilience is None else
                        self.resilience.degradation.tier(
                            self.members.n_alive, self.members.roster_size))
            if new_tier != tier:
                tier = new_tier
                record = {"step": step, "tier": tier,
                          "n_alive": self.members.n_alive,
                          "roster_size": self.members.roster_size}
                self.degradations.append(record)
                self._emit("degradation", record)
            if tier == "pause":
                self.paused_steps += 1
                if ev_i >= len(events):
                    break  # no future join can restore quorum
                continue
            if tier == "shrink_batch":
                self.loader.global_batch = max(
                    self.members.n_alive,
                    int(round(base_global_batch
                              * self.resilience.degradation.shrink_factor)))
            else:
                self.loader.global_batch = base_global_batch
            # 2-4. one step, in profiler spans (docs/performance.md)
            with TraceAnnotation("train.step"):
                # 2. data (global batch stays constant across membership
                # changes)
                with TraceAnnotation("train.data") as data:
                    n_shards = max(1, self.members.n_alive)
                    batch_np = self.loader.next_global(n_shards)
                    batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
                    data.set_metadata(tokens=int(batch_np["labels"].size))
                # 3. step: enqueue, then wait on the device for the loss
                with TraceAnnotation("train.dispatch"):
                    state, metrics = self._jit_step(state, batch)
                steps_run += 1
                with TraceAnnotation("train.sync") as sync:
                    # the loss and the expert layers' counters, in one
                    # transfer; the counters go on this span as stats
                    host = jax.device_get(
                        {k: metrics[k] for k in ("loss",) + MOE_COUNTERS
                         if k in metrics})
                    loss = float(host.pop("loss"))
                    counters = {k: int(v) for k, v in host.items()}
                    if counters:
                        sync.set_metadata(**counters)
                    payload = {"step": step, "loss": loss, **counters}
                    if "payload_bytes" in metrics:
                        # §VI-B telemetry: the actual compressed wire size
                        # of this step's gradient push, not a config echo
                        payload["payload_bytes"] = float(
                            metrics["payload_bytes"])
                        payload["grad_compression"] = \
                            self.run.grad_compression
                losses.append(loss)
                # 4. profile + detect (+ §VI-B mitigation)
                with TraceAnnotation("train.observe"):
                    state = self._observe(state, step, payload, check_every)
            # 5. checkpoint
            if self.run.checkpoint_interval and \
                    (step + 1) % self.run.checkpoint_interval == 0:
                checkpoints += self._save_checkpoint(step + 1, state)
        self.loader.global_batch = base_global_batch
        report = TrainReport(
            steps_run=steps_run,
            final_loss=losses[-1] if losses else float("nan"),
            losses=losses, speed=self.profiler.speed(),
            epochs=self.members.epoch_no + 1, checkpoints=checkpoints,
            restores=self.restores, detections=self.detections,
            wall_seconds=time.monotonic() - t0,
            mitigations=self.mitigations,
            checkpoint_failures=self.ckpt_failures, faults=self.faults,
            retries=self.retries, recovered_saves=self.recovered_saves,
            fallback_depth=self.fallback_depth,
            paused_steps=self.paused_steps, degradations=self.degradations,
            drift_events=(list(self.recalibrator.drift_events)
                          if self.recalibrator is not None else []),
            refits=(list(self.recalibrator.refits)
                    if self.recalibrator is not None else []))
        return state, report

    def _observe(self, state: st.TrainState, step: int, payload: dict,
                 check_every: int) -> st.TrainState:
        """Emit the step, record it, and on the check cadence run the
        controller with its mitigation and recalibration. With an injected
        clock (chaos), the "step" emit lets the driver advance virtual
        time for this step before it is recorded."""
        self._emit("step", payload)
        self.profiler.record(
            step, t=self.clock() if self.clock is not None else None,
            loss=payload["loss"])
        if not (self.predicted_speed and step % check_every == 0
                and step > 0):
            return state
        det = self.controller.check(self.profiler, self.predicted_speed,
                                    ps_model=self.ps_model,
                                    workers=self.workers)
        self.detections.append(det)
        self._emit("detection", {"step": step,
                                 "bottleneck": det.bottleneck,
                                 "action": det.action.value,
                                 "deviation": det.deviation,
                                 "model_version": det.model_version})
        mitigated = False
        if self.auto_mitigate and det.action in (
                Action.ADD_PARAMETER_SERVER, Action.ENABLE_COMPRESSION) \
                and len(self.mitigations) < self.max_mitigations:
            state = self.apply_mitigation(det.action, state, step=step)
            mitigated = True
        if self.recalibrator is not None:
            if mitigated:
                # mitigation changed the cluster; deviation against the
                # pre-mitigation prediction is void drift input
                self.recalibrator.notify_mitigation(step)
            else:
                dev = det.deviation if det.measured is not None else None
                new_speed = self.recalibrator.observe(step, dev,
                                                      self.profiler)
                if new_speed is not None:
                    self._apply_refit(new_speed, step)
        return state

    def _join_member(self, ev: "MembershipEvent"):
        """Replacement join, retried under the resilience policy: a join
        that races a membership epoch roll is transient, so it gets the
        same bounded backoff as a checkpoint save."""
        join = lambda: self.members.join(Member(ev.member_id, ev.gpu))
        if self.resilience is None:
            return join()
        epoch, attempts = call_with_retries(
            join, self.resilience.retry, op="join", seed=self.run.seed,
            key=ev.member_id, sleep=self._sleep, emit=self._emit,
            retry_on=(RuntimeError,))
        self.retries += attempts - 1
        return epoch

    def _save_checkpoint(self, step: int, state) -> int:
        """One interval save. Legacy path (no resilience): an outage
        fails fast and silently drops the save. Resilience path: the save
        is retried under the policy (``retry`` events per attempt); only
        once attempts/deadline are exhausted does it count as a
        ``checkpoint_failed`` — and that event carries the attempt count,
        so no failure is silent. Returns 1 if a checkpoint committed."""
        metadata = {**self.loader.state(),
                    "grad_compression": self.run.grad_compression}
        if self.resilience is None:
            if self.ckpt_outage:
                # chaos checkpoint-store outage: the save fails fast
                # and the run continues on its last good checkpoint
                self.ckpt_failures += 1
                self._emit("checkpoint_failed",
                           {"step": step, "failures": self.ckpt_failures})
                return 0
            sizes = self.ckpt.save(step, state, metadata=metadata)
            if sizes is None:
                return 0
            self._emit("checkpoint", {"step": step, "sizes": sizes})
            return 1

        def attempt():
            if self.ckpt_outage:
                raise OSError("checkpoint store unavailable (ckpt_outage)")
            return self.ckpt.save(step, state, metadata=metadata)

        had_failures = self.ckpt_failures > 0
        try:
            sizes, attempts = call_with_retries(
                attempt, self.resilience.retry, op="checkpoint_save",
                seed=self.run.seed, key=step, sleep=self._sleep,
                emit=self._emit)
        except RetryExhausted as exc:
            self.retries += exc.attempts - 1
            self.ckpt_failures += 1
            self._emit("checkpoint_failed",
                       {"step": step, "failures": self.ckpt_failures,
                        "attempts": exc.attempts,
                        "error": type(exc.last).__name__})
            return 0
        self.retries += attempts - 1
        if sizes is None:
            return 0
        if attempts > 1 or had_failures:
            self.recovered_saves += 1
        self._emit("checkpoint", {"step": step, "sizes": sizes})
        return 1

    # ------------------------------------------------------------- refit
    def _apply_refit(self, new_speed: float, step: int) -> None:
        """Adopt a drift-triggered refit: the controller now compares
        against the refit prediction (and stamps its new version), and
        the measurement window restarts so the next check is refit-vs-
        post-drift data, not refit-vs-straddled history."""
        self.predicted_speed = new_speed
        self.controller.model_version = self.recalibrator.version
        self.profiler.records.clear()
        self.profiler._win.clear()

    # ---------------------------------------------------- chaos injection
    def inject_fault(self, kind: str, step: int = 0, **payload) -> None:
        """Flip one live fault on/off mid-run (the chaos driver's hook).

        Kinds:
          * ``ckpt_outage`` / ``ckpt_recover`` — fail checkpoint saves
            fast (``checkpoint_failed`` events) / resume saving. The one
            fault the trainer itself enacts, since it owns the save path.
          * ``ps_crash`` / ``ps_recover`` and ``straggler`` /
            ``straggler_end`` — bookkeeping only. These faults are
            *silent*: the trainer's capacity model and prediction stay
            healthy (a silently degraded cluster is exactly what the
            controller must notice from measurement alone), while the
            chaos driver's virtual clock prices every step at the truly
            degraded cluster speed.
        """
        if kind == "ckpt_outage":
            self.ckpt_outage = True
        elif kind == "ckpt_recover":
            self.ckpt_outage = False
        elif kind not in ("ps_crash", "ps_recover",
                          "straggler", "straggler_end"):
            raise ValueError(f"unknown fault kind {kind!r}")
        record = {"step": step, "fault": kind, **payload}
        self.faults.append(record)
        self._emit("fault", record)

    # ------------------------------------------------------- §VI-B mitigate
    def apply_mitigation(self, action: Action, state: st.TrainState,
                         step: int = 0) -> st.TrainState:
        """Act on a PS-bottleneck detection mid-run and re-derive the
        prediction the controller compares against.

        * ``ADD_PARAMETER_SERVER`` — provision one more PS in the capacity
          model (Li et al.'s first mitigation lever);
        * ``ENABLE_COMPRESSION`` — walk the compression ladder one rung:
          an uncompressed run flips to ``mitigation_scheme`` (the dense
          quantizer, attaching a zero error-feedback residual), a
          dense-compressed run escalates to ``topk`` sparsification
          (keeping its residual — the trees are shaped alike). Either
          way the jitted step is rebuilt (cache-keyed on the scheme) and
          the PS capacity model recalibrated with ``compression_ratio``.

        Either way ``predicted_speed`` is recomputed from the new capacity
        so subsequent `Controller.check` calls measure against the
        mitigated cluster, and a ``mitigation`` event is emitted.
        """
        if self.ps_model is None:
            return state
        if action is Action.ADD_PARAMETER_SERVER:
            self.ps_model = self.controller.mitigate_ps(self.ps_model)
        elif action is Action.ENABLE_COMPRESSION:
            current = self.run.grad_compression
            target = (self.mitigation_scheme if current == "none"
                      else "topk")
            if current != target and current != "topk":
                self.run = dataclasses.replace(
                    self.run, grad_compression=target)
                self._rebuild_step()
                if current == "none":
                    state = state._replace(
                        residual=st.init_residual(state.params, self.run))
                # dense -> topk keeps the residual: same tree shape, and
                # the accumulated quantization error still belongs in the
                # next push
            self.ps_model = self.controller.mitigate_compression(
                self.ps_model, self.run.grad_compression)
        else:
            return state
        if self.workers:
            self.predicted_speed = cluster_speed(self.workers, self.ps_model)
        # restart the measurement window: `speed()` averages the whole
        # post-warmup history, so pre-mitigation records would keep the
        # measured speed depressed for many steps and re-trigger the
        # controller against the already-mitigated cluster
        self.profiler.records.clear()
        self.profiler._win.clear()
        record = {"step": step, "action": action.value,
                  "n_ps": self.ps_model.n_ps,
                  "grad_compression": self.run.grad_compression,
                  "ps_capacity": self.ps_model.capacity_steps_per_s(),
                  "predicted_speed": self.predicted_speed}
        self.mitigations.append(record)
        self._emit("mitigation", record)
        return state
