"""Epoch-level training loop with the reference's observable behavior.

Reproduces the reference driver's loop shape — tqdm progress over batches
(src/main.py:68), wall-clock bracketing the epoch (src/main.py:65, 81), and
the printed elapsed time (src/main.py:84) — on top of the jitted step.  Adds
what the reference computes but never surfaces (loss logging, SURVEY.md §5)
and per-epoch throughput in the BASELINE.json metric (examples/sec).

Telemetry rides the loop through one spine (obs/): an optional
``MetricsEmitter`` gets a per-step structured event (host-side dispatch
interval + the configured per-step counters; the loss joins at log points,
where the host syncs anyway, and so does the step time the device closed),
anomalies route through the flight recorder, and the loop's host boundaries
are on the profiler's clock: every step dispatch carries an xprof step
annotation (``train``), the batch pull is ``train/input_wait`` and every
loss fetch ``train/host_sync``, so a capture lays the device's idle gaps
against what the host was doing.  Profiling can bracket a step
window (``TrainerConfig.profile_steps``) instead of a whole epoch — the
steady-state capture — with the supervisor heartbeat beaten every captured
step so a long capture is never mistaken for a hang.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time
from typing import Any, Callable, Iterable

import jax
import numpy as np
from jax.sharding import Mesh

from ..obs.cost import mfu
from ..obs.trace import phase_span, step_annotation
from ..parallel.sharding import shard_batch
from ..utils.compile_cache import compile_events, compile_phase, compile_totals
from .state import TrainState
from .step import STEP_COUNTERS, STEP_LOSS_PARTS, note_capture


@dataclasses.dataclass
class TrainerConfig:
    epochs: int = 1
    log_every: int = 50
    progress: bool = True  # tqdm bar, as the reference (src/main.py:68)
    check_nan: bool = False  # debug mode: halt on non-finite loss (SURVEY.md §5)
    prefetch: int = 2  # batches kept in flight on device (0 disables)
    sequence_sharded: bool = False  # shard batch dim 1 over `sequence` (SP runs)
    profile_dir: str | None = None  # jax.profiler trace destination
    # (start, stop) GLOBAL step window to capture, [start, stop): trace a
    # few steady-state steps instead of the whole first epoch.  None with
    # profile_dir set = the caller brackets the epoch itself (CLI default).
    profile_steps: tuple[int, int] | None = None
    # Mid-epoch checkpoint cadence (global steps): an async step-granular
    # save through ``checkpoint_fn`` every N steps, so a preemption or
    # crash loses at most N steps instead of an epoch (None = epoch-end
    # saves only, the caller's job).
    checkpoint_every_steps: int | None = None


class Trainer:
    """Drives the jitted step over a data iterator on a mesh.

    ``emitter`` (obs.MetricsEmitter, optional) is the telemetry spine: the
    trainer emits phase/step/anomaly events through it and routes per-step
    metric checks through a flight recorder.  A disabled emitter (or None)
    costs nothing on the step path.
    """

    def __init__(
        self,
        state: TrainState,
        train_step: Callable[[TrainState, Any], tuple[TrainState, dict]],
        mesh: Mesh,
        config: TrainerConfig | None = None,
        *,
        emitter=None,
        spans=None,
        anatomy=None,
        faults=None,
        recovery=None,
        preemption=None,
        checkpoint_fn=None,
        slo=None,
        ledger=None,
    ):
        self.state = state
        self.train_step = train_step
        self.mesh = mesh
        self.config = config or TrainerConfig()
        self.history: list[dict] = []
        self.emitter = emitter
        # Live SLO plane (obs/slo.py): the burn-rate policy is evaluated
        # at every step boundary — the trainer is the host control loop
        # a training run has, the way the scheduler tick is for serving.
        # step_flops/peak_flops (set by the CLI's compiled-cost probe)
        # turn the step time each host sync closes into a live MFU gauge.
        self.slo = slo
        self.step_flops: float | None = None
        self.peak_flops: float | None = None
        # Span recorder (obs/spans.py): every optimizer step records a
        # ``train/step`` host span (corr = global step, sampled per step)
        # bracketing the batch pull through the step's host bookkeeping,
        # with ``train/input_wait`` / ``train/host_sync`` /
        # ``train/snapshot`` / ``train/checkpoint`` children at the
        # boundaries where the host actually waits.
        # ``anatomy`` attrs ride every step span: what ONE compiled step
        # contains (grad-accum microbatches, grad-sync tiers, pipeline
        # ticks) — those phases run inside a single program, so their
        # measured sub-timelines are xprof's job (obs/trace.scope), never
        # a host clock's (graftcheck: host-clock-in-trace).
        self.spans = spans
        self.anatomy = dict(anatomy) if anatomy else {}
        # Resilience plane (resilience/): deterministic fault injection at
        # step boundaries, host-side snapshot/rollback, the SIGTERM
        # preemption latch, and the step-checkpoint hook
        # ``checkpoint_fn(state, wait=...)`` the preemption/cadence paths
        # save through.  All optional; None costs nothing on the step path.
        self.faults = faults
        self.recovery = recovery
        self.preemption = preemption
        self.checkpoint_fn = checkpoint_fn
        # Goodput ledger (obs/ledger.py, --goodput): exhaustive wall-clock
        # attribution.  The loop feeds it at the boundaries it already
        # crosses — the ``train/input_wait`` pull, step dispatch,
        # checkpoint calls — so the hooks add clock reads, not
        # synchronization.
        self.ledger = ledger
        self.recorder = None
        if emitter is not None and emitter.enabled:
            from ..obs import FlightRecorder

            self.recorder = FlightRecorder(emitter)
        # Host-side global step count (across epochs): tags step events and
        # drives the profile window without a per-step device fetch.
        # Seeded from the (possibly restored) optimizer step so a resumed
        # run's telemetry and --profile-steps windows stay globally
        # numbered instead of restarting at 0 — one scalar fetch, before
        # any training work.
        self._global_step = int(state.step)
        self._profiling = False
        self._profile_done = False  # a window captures once, ever
        # Skips seen so far (host mirror of the device counter, updated at
        # log points): the DELTA since the last log point is what the
        # flight recorder flags, so skips between log points are never
        # silently absorbed.
        self._skipped_seen = 0

    # ---- profile window (profile_steps) --------------------------------

    def _profile_tick(self, heartbeat) -> None:
        """Start/stop the step-window trace at the current global step;
        beat the heartbeat on every captured step so capture time is never
        read as a hang."""
        cfg = self.config
        if cfg.profile_dir is None or cfg.profile_steps is None \
                or self._profile_done:
            return
        start, stop = cfg.profile_steps
        if not self._profiling and start <= self._global_step < stop:
            jax.profiler.start_trace(cfg.profile_dir)
            self._profiling = True
            note_capture(True)     # the step that runs now is the trace's (train/step.py)
            if self.emitter is not None:
                self.emitter.phase(
                    "profile_start", step=self._global_step
                )
        if self._profiling and heartbeat is not None:
            heartbeat.beat()

    def _profile_stop_if_done(self, metrics) -> None:
        cfg = self.config
        if not self._profiling or cfg.profile_steps is None:
            return
        if self._global_step + 1 >= cfg.profile_steps[1]:
            # Close the capture on completed device work: fetch the step's
            # loss so the traced window contains the steps it brackets,
            # not just their dispatch.
            if metrics is not None:
                with phase_span(
                    self.spans, "train/host_sync", corr=self._global_step,
                ):
                    float(metrics["loss"])
            jax.profiler.stop_trace()
            note_capture(False)
            self._profiling = False
            self._profile_done = True
            if self.emitter is not None:
                self.emitter.phase("profile_stop", step=self._global_step)

    def _finalize_profile(self) -> None:
        # Window ran past the epoch's data (or an exception landed here):
        # close the capture and retire the window — restarting it next
        # epoch would fragment one requested bracket into several
        # partial xprof sessions.
        if self._profiling:
            jax.profiler.stop_trace()
            note_capture(False)
            self._profiling = False
            self._profile_done = True
            if self.emitter is not None:
                self.emitter.phase(
                    "profile_stop", step=self._global_step, truncated=True
                )

    # ---- the epoch loop -------------------------------------------------

    def run_epoch(self, loader: Iterable, *, epoch: int = 0) -> dict:
        cfg = self.config
        it = loader
        if cfg.progress:
            try:
                from tqdm import tqdm

                it = tqdm(loader, desc=f"epoch {epoch}")
            except ImportError:
                pass

        examples = 0
        losses = []
        last_metrics: dict = {}
        metrics: dict | None = None
        last_logged_step = -1
        # (host time, step index) of the last loss fetch.  Between two
        # fetches the device finished exactly the steps between them, so
        # their quotient is a step time the DEVICE closed — unlike the
        # dispatch interval ``dt``, which is the host's pace and runs
        # ahead of the device until the queue is full.
        last_sync: tuple[float, int] | None = None

        def close_on_sync(step_idx: int) -> float | None:
            """Call right after a loss fetch: seconds per step since the
            fetch before it (None at the epoch's first), and the live MFU
            gauge from it."""
            nonlocal last_sync
            now = time.perf_counter()
            step_s = None
            if last_sync is not None and step_idx > last_sync[1]:
                step_s = (now - last_sync[0]) / (step_idx - last_sync[1])
                if self.emitter is not None and self.step_flops \
                        and self.peak_flops:
                    live = mfu(self.step_flops, step_s, self.peak_flops)
                    if live is not None:
                        self.emitter.gauge("mfu_live", live)
            last_sync = (now, step_idx)
            return step_s

        # (global step, device scalar) of every step since the last host
        # sync: fetched in one go WHERE the host syncs anyway (log points,
        # epoch end) and written as a ``step_losses`` record, so the log
        # holds every step's loss without a per-step device wait.
        pending_losses: list = []

        def flush_losses():
            if self.emitter is not None and self.emitter.enabled \
                    and pending_losses:
                steps, vals = zip(*pending_losses)
                self.emitter.emit("record", {
                    "record": "step_losses", "first_step": steps[0],
                    "losses": [float(v) for v in jax.device_get(list(vals))],
                })
            pending_losses.clear()

        # Liveness for the elastic supervisor (utils/supervisor.py): beat at
        # epoch start (covers compile + first-batch load) and at every log
        # point, so a hung collective is detectable by wall clock without
        # healthy compiles being mistaken for hangs.
        from ..utils.supervisor import Heartbeat

        heartbeat = Heartbeat.from_env()
        if heartbeat is not None:
            heartbeat.beat()
        if self.emitter is not None:
            self.emitter.phase("epoch_start", epoch=epoch)
        t0 = time.perf_counter()
        prev_tick = t0
        compiled_before = len(compile_events())
        try:
            with self.mesh, compile_phase("train/epoch", epoch=epoch):
                if cfg.prefetch > 0:
                    # Keep N sharded batches in flight so the next batch's
                    # H2D transfer rides under the current step's compute.
                    from ..data.loader import prefetch_to_device

                    it = prefetch_to_device(
                        it, self.mesh, size=cfg.prefetch,
                        sequence_sharded=cfg.sequence_sharded,
                    )
                it = iter(it)
                for step_idx in itertools.count():
                    sspan = (
                        self.spans.start_span(
                            "train/step", corr=self._global_step,
                            **self.anatomy,
                        ) if self.spans is not None else None
                    )
                    # What the host does for input, timed once: the
                    # loader's next, the shard and the host-to-device
                    # enqueue (of the batch AFTER this one when the
                    # prefetch wrap is on).  Outside the prefetch wrap, so
                    # a pull that blocks here means the input pipeline
                    # (even prefetched) could not hide the load — exactly
                    # what the ledger's data_wait should charge too.
                    with phase_span(
                        self.spans, "train/input_wait",
                        corr=self._global_step, parent=sspan,
                    ) as wspan:
                        if self.ledger is not None:
                            self.ledger.begin_pull()
                        batch = next(it, None)
                        if batch is not None:
                            batch = shard_batch(  # idempotent if placed
                                batch, self.mesh,
                                sequence_sharded=cfg.sequence_sharded,
                            )
                        elif wspan is not None:
                            # The pull that finds the loader empty is input
                            # time all the same, but no step follows it:
                            # the step span opened above is never recorded.
                            wspan.parent = None
                        if self.ledger is not None:
                            self.ledger.end_pull(exhausted=batch is None)
                    if batch is None:
                        break
                    self._profile_tick(heartbeat)
                    if self.faults is not None:
                        # Deterministic fault plane: may corrupt the batch,
                        # stall without beating, SIGTERM self, or kill the
                        # process outright (resilience/faults.py).
                        batch = shard_batch(
                            self.faults.on_step(self._global_step, batch),
                            self.mesh, sequence_sharded=cfg.sequence_sharded,
                        )
                    with step_annotation(self._global_step):
                        self.state, metrics = self.train_step(self.state, batch)
                    examples += int(next(iter(batch.values())).shape[0])
                    pending_losses.append(
                        (self._global_step, metrics["loss"])
                    )
                    now = time.perf_counter()
                    if self.ledger is not None:
                        # Classify the batch-ready..dispatch interval (the
                        # host blocked on XLA's async queue — device time
                        # at steady state) and the host tail that follows:
                        # compile for the first dispatched step, rework
                        # under a restart watermark, else the grad_sync/
                        # step_compute quota split.
                        self.ledger.begin_step(self._global_step)
                    step_fields: dict = {"dt": now - prev_tick}
                    prev_tick = now
                    if cfg.check_nan or step_idx % cfg.log_every == 0:
                        if heartbeat is not None:
                            heartbeat.beat()
                        # Host sync only when we actually look at the value —
                        # otherwise steps stay fully async (dispatch runs
                        # ahead).  The sync is a child span: a trace that
                        # shows fat host_sync bars at log points and thin
                        # dispatch bars between them is HEALTHY async
                        # dispatch, not a slow step.
                        with phase_span(
                            self.spans, "train/host_sync",
                            corr=self._global_step, parent=sspan,
                        ):
                            loss = float(metrics["loss"])
                        step_s = close_on_sync(step_idx)
                        flush_losses()
                        step_fields["loss"] = loss
                        if step_s is not None:
                            step_fields["steps_per_sec"] = 1.0 / step_s
                        # A step's counters (the dropless MoE layer's,
                        # the block-diffusion objective's) and the parts of
                        # a multi-part loss: device scalars beside the
                        # loss, read where the host syncs anyway.
                        for name in STEP_COUNTERS + STEP_LOSS_PARTS:
                            if name in metrics:
                                step_fields[name] = float(metrics[name])
                                if self.emitter is not None:
                                    self.emitter.gauge(name, step_fields[name])
                        skipped_delta = None
                        if "skipped_total" in metrics:
                            total_skips = int(metrics["skipped_total"])
                            skipped_delta = total_skips - self._skipped_seen
                            self._skipped_seen = total_skips
                            step_fields["skipped_total"] = total_skips
                        if self.recorder is not None:
                            self.recorder.check_step(self._global_step, {
                                "loss": loss,
                                "grad_norm": metrics.get("grad_norm"),
                                "skipped": skipped_delta,
                                # Host step wall time: the self-skew
                                # straggler detector's input (a step far
                                # over its own rolling median is a
                                # host/link hiccup worth an alert).
                                "dt": step_fields["dt"],
                            })
                        if self.ledger is not None \
                                and self.emitter is not None:
                            # Live goodput gauges at log cadence (the
                            # host syncs here anyway): /metrics scrapes
                            # goodput_fraction + per-category badput.
                            self.ledger.emit_gauges(self.emitter)
                        if self.recovery is not None \
                                and "bad_streak" in metrics:
                            # Rollback/abort reacts at log cadence — the
                            # host syncs here anyway, and every bad step
                            # in between was a no-op update by
                            # construction (the jit-safe skip gate).
                            self.state = self.recovery.observe(
                                self.state, self._global_step,
                                int(metrics["bad_streak"]),
                            )
                        if cfg.check_nan and not np.isfinite(loss):
                            raise FloatingPointError(
                                f"non-finite loss {loss} at epoch {epoch} "
                                f"step {step_idx}"
                            )
                        losses.append(loss)
                        last_logged_step = step_idx
                        last_metrics = {
                            k: float(v) for k, v in metrics.items()
                        }
                    if self.emitter is not None:
                        # Rolling histogram of the dispatch interval: the
                        # live plane's step_time_p* objectives window
                        # these samples.
                        self.emitter.observe(
                            "step_time_s", step_fields["dt"]
                        )
                        self.emitter.step(self._global_step, **step_fields)
                    if self.slo is not None:
                        self.slo.evaluate()
                    self._profile_stop_if_done(metrics)
                    self._global_step += 1
                    if self.ledger is not None:
                        # Restart-rework watermark for the NEXT attempt:
                        # a crash before the next dispatch re-executes
                        # steps from the last committed checkpoint up to
                        # exactly this completed step.
                        self.ledger.note_progress(self._global_step)
                    if self.recovery is not None:
                        # Host snapshot at its own cadence: device_get
                        # blocks on the state's in-flight computation:
                        # the staging bubble.
                        snap = (
                            self.spans.start_span(
                                "train/snapshot", parent=sspan,
                            ) if sspan is not None else None
                        )
                        self.recovery.maybe_stage(
                            self.state, self._global_step
                        )
                        if self.spans is not None:
                            self.spans.end_span(snap)
                    if self.preemption is not None \
                            and self.preemption.triggered:
                        # SIGTERM landed during this step: commit a
                        # synchronous step checkpoint at this boundary,
                        # then exit with the distinct preemption code
                        # (the CLI converts Preempted -> exit 75; the
                        # supervisor relaunches without charging
                        # max_restarts).
                        if heartbeat is not None:
                            heartbeat.beat()  # cover the blocking save
                        saved = False
                        if self.checkpoint_fn is not None:
                            with (
                                self.ledger.bracket("ckpt_save")
                                if self.ledger is not None
                                else contextlib.nullcontext()
                            ):
                                self.checkpoint_fn(self.state, wait=True)
                            saved = True
                        if self.emitter is not None:
                            self.emitter.anomaly(
                                "preemption", step=self._global_step,
                                checkpointed=saved,
                            )
                        from ..resilience.preemption import Preempted

                        raise Preempted(self._global_step, saved)
                    if (
                        cfg.checkpoint_every_steps
                        and self.checkpoint_fn is not None
                        and self._global_step % cfg.checkpoint_every_steps == 0
                    ):
                        # Async step checkpoint: staging is synchronous,
                        # serialization overlaps the following steps.
                        ckpt_span = (
                            self.spans.start_span(
                                "train/checkpoint", parent=sspan,
                            ) if sspan is not None else None
                        )
                        with (
                            self.ledger.bracket("ckpt_save")
                            if self.ledger is not None
                            else contextlib.nullcontext()
                        ):
                            self.checkpoint_fn(self.state, wait=False)
                        if self.spans is not None:
                            self.spans.end_span(ckpt_span)
                        if heartbeat is not None:
                            heartbeat.beat()
                    if self.spans is not None:
                        self.spans.end_span(sspan)
                # Fetch the final step's loss to close the timing window:
                # the donated state chains every step, so this read
                # completes only after all device work has — which also
                # lets a capture still open hold the steps it brackets.
                if examples:
                    with phase_span(
                        self.spans, "train/host_sync",
                        corr=self._global_step - 1,
                    ):
                        final_loss = float(metrics["loss"])
                    close_on_sync(step_idx - 1)
                    # Dedupe: when the epoch length lands exactly on a log
                    # point the final loss is already the last logged value
                    # — appending it again would double-count it in the
                    # record.
                    if last_logged_step != step_idx - 1:
                        losses.append(final_loss)
                    flush_losses()
        finally:
            self._finalize_profile()
            if self.spans is not None:
                self.spans.flush()
        if heartbeat is not None:
            heartbeat.beat()  # cover the epoch-end checkpoint/eval window
        elapsed = time.perf_counter() - t0
        compiled = compile_totals(compile_events(compiled_before))

        summary = {
            "epoch": epoch,
            # Global optimizer steps completed by epoch end (host-side
            # mirror of state.step, seeded from it at construction — no
            # per-epoch device fetch).
            "step": self._global_step,
            "elapsed_s": elapsed,
            "examples": examples,
            "examples_per_sec": examples / elapsed if elapsed > 0 else 0.0,
            # What JAX compiled (or loaded from its cache) inside this
            # epoch: > 0 for the first epoch of a new step, 0 after it —
            # anything else is a recompile (utils/compile_cache.py).
            "compiles": compiled["compiles"],
            "compile_s": compiled["compile_s"],
            "loss": losses[-1] if losses else float("nan"),
            **{k: v for k, v in last_metrics.items() if k != "loss"},
        }
        self.history.append(summary)
        # The epoch's logged-loss series (log points + the closing fetch,
        # deduped when the last step was itself a log point) — the record a
        # mean/curve consumer should read instead of re-deriving it.
        self.last_epoch_losses = losses
        if self.emitter is not None:
            self.emitter.phase(
                "epoch_end", epoch=epoch, examples=examples,
                elapsed_s=elapsed, compiles=compiled["compiles"],
                compile_s=compiled["compile_s"],
            )
        return summary

    def fit(self, loader_fn: Callable[[int], Iterable]) -> list[dict]:
        """Train ``config.epochs`` epochs; ``loader_fn(epoch)`` yields batches."""
        return [
            self.run_epoch(loader_fn(epoch), epoch=epoch)
            for epoch in range(self.config.epochs)
        ]
