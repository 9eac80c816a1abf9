"""Host-side recovery: last-good snapshots, rollback, escalation.

The skip policy (``anomaly.guarded_apply``) guarantees no *detected*-bad
update is ever applied — so the live params are always "last good" at the
moment they were written.  What it cannot undo is a state that went bad
*undetected* (a spike under the threshold that saturated the optimizer
moments, after which every subsequent gradient trips the gate) or make
progress when every step is being skipped.  That escalation path is
host-side:

1. **snapshot**: every ``snapshot_every_steps`` global steps the manager
   stages a host-numpy copy of the learned state (params, optimizer
   slots, batch stats, EF residuals).  Staging blocks on the state's
   in-flight computation — that pipeline bubble is the cost
   (<1% step-time target).
2. **rollback**: when the device-side bad-streak counter reaches
   ``rollback_after`` (read at trainer log points, where the host syncs
   anyway), the snapshot is restored into the live shardings, the streak
   resets, and training continues on fresh data — recorded as a
   ``rollback`` anomaly.
3. **abort**: after ``max_rollbacks`` rollbacks in one process the run
   raises :class:`RecoveryAborted` — a nonzero exit the supervisor
   relaunches from the last committed checkpoint, charging
   ``max_restarts`` (a run that cannot hold a good state is a crash, not
   a blip).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np


class RecoveryAborted(RuntimeError):
    """Raised after the rollback budget is exhausted."""


@dataclasses.dataclass(frozen=True)
class RecoveryConfig:
    rollback_after: int = 8       # K consecutive skipped steps -> rollback
    max_rollbacks: int = 2        # R rollbacks -> abort
    snapshot_every_steps: int = 200


# The learned TrainState fields a snapshot must cover; step stays live
# (a rollback keeps the current step so the data schedule marches on).
SNAPSHOT_FIELDS = ("params", "opt_state", "batch_stats", "grad_sync_residual")


class RecoveryManager:
    def __init__(self, config: RecoveryConfig | None = None, *, emitter=None,
                 ledger=None):
        self.config = config or RecoveryConfig()
        self.emitter = emitter
        # Goodput ledger (obs/ledger.py, --goodput): a rollback discards
        # the updates since the snapshot, so the ledger re-classifies
        # those steps' recorded wall time as rework; a snapshot retires
        # the window below it.
        self.ledger = ledger
        self.rollbacks = 0
        self._snapshot: dict | None = None
        self._snapshot_step: int | None = None
        self._last_stage_step: int | None = None

    # ---- snapshot -------------------------------------------------------

    def maybe_stage(self, state, global_step: int) -> None:
        """Stage a host copy at the configured cadence (and at the first
        opportunity).  The skip gate means live params are always
        applied-good, so no health check is needed before staging."""
        if self._last_stage_step is not None and (
            global_step - self._last_stage_step
            < self.config.snapshot_every_steps
        ):
            return
        self.stage(state, global_step)

    def stage(self, state, global_step: int) -> None:
        self._snapshot = {
            field: jax.tree_util.tree_map(np.asarray, getattr(state, field))
            for field in SNAPSHOT_FIELDS
        }
        self._snapshot_step = global_step
        self._last_stage_step = global_step
        if self.ledger is not None:
            self.ledger.note_snapshot(global_step)

    # ---- rollback / abort ----------------------------------------------

    def observe(self, state, global_step: int, bad_streak: int):
        """React to the device-side streak counter (read at a log point).
        Returns the (possibly rolled-back) state; raises
        :class:`RecoveryAborted` past the rollback budget."""
        if bad_streak < self.config.rollback_after or self._snapshot is None:
            return state
        if self.rollbacks >= self.config.max_rollbacks:
            if self.emitter is not None:
                self.emitter.anomaly(
                    "recovery_abort", step=global_step,
                    rollbacks=self.rollbacks, bad_streak=bad_streak,
                )
            raise RecoveryAborted(
                f"{bad_streak} consecutive bad steps at step {global_step} "
                f"after {self.rollbacks} rollbacks — aborting for a "
                "supervised restart from the last committed checkpoint"
            )
        self.rollbacks += 1
        if self.emitter is not None:
            self.emitter.anomaly(
                "rollback", step=global_step, bad_streak=bad_streak,
                snapshot_step=self._snapshot_step, rollback=self.rollbacks,
            )
        if self.ledger is not None:
            # The updates of [snapshot_step, global_step] are discarded:
            # their already-charged wall time moves to rework, and the
            # restore itself is a ckpt_restore interval.
            self.ledger.note_rollback(self._snapshot_step, global_step)
            with self.ledger.bracket("ckpt_restore"):
                return self._restore(state)
        return self._restore(state)

    def _restore(self, state):
        def place(host, live):
            if hasattr(live, "sharding"):
                return jax.device_put(host, live.sharding)
            return jax.numpy.asarray(host)

        restored = {
            field: jax.tree_util.tree_map(
                place, self._snapshot[field], getattr(state, field)
            )
            for field in SNAPSHOT_FIELDS
        }
        # Reset ONLY the streak: the restored state is good by
        # construction, and a stale streak would re-trip the next check.
        # ``skipped_total`` is the run-cumulative counter the trainer
        # diffs against its host mirror — zeroing it would drive the next
        # delta negative and mask every skip until the mirror catches up.
        resilience = state.resilience.replace(
            bad_streak=jax.numpy.zeros_like(state.resilience.bad_streak)
        )
        return state.replace(resilience=resilience, **restored)
