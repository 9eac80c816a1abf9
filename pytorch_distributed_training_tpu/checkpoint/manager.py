"""Checkpoint manager over orbax, with verified restores.

Saves the *array* portion of a TrainState (params, opt_state, batch_stats,
step); the static fields (apply_fn, tx) are code, reconstructed by the
caller, so a checkpoint is portable across framework versions that preserve
the pytree structure.

Integrity story (resilience PR): orbax's tmp-dir + commit rename already
makes a save atomic, but nothing protected a COMMITTED checkpoint — a
truncated or bit-rotted file crashed every supervised relaunch in the
restart loop, turning one bad disk block into a dead run.  ``save`` now
writes a per-leaf crc32 manifest next to the step, and ``restore_latest``
verifies restored bytes against it, falling back to the next-older step
(reporting through ``on_anomaly``) instead of crashing; only when every
committed step fails does it return None (fresh start).
"""

from __future__ import annotations

import glob
import json
import os
import zlib
from typing import Any, Callable

import jax
import numpy as np
import orbax.checkpoint as ocp

from ..train.state import TrainState


def _arrays_of(state: TrainState) -> dict[str, Any]:
    return {
        "step": state.step,
        "params": state.params,
        "opt_state": state.opt_state,
        "batch_stats": state.batch_stats,
    }


def _staged_arrays_of(state: TrainState) -> dict[str, Any]:
    """Async-stable view of the state for saving.

    On accelerator backends orbax's async checkpointer stages a D2H copy
    before ``save`` returns, so background serialization reads stable
    bytes.  On the CPU backend the "device" buffer IS host memory and no
    copy happens — the serializer reads the LIVE training buffers, which
    the next donated train step overwrites mid-write.  Observed as torn
    committed checkpoints in the chaos harness (caught by the manifest
    checksums; invisible before them, since garbage floats still train).
    Copy CPU-resident addressable leaves to stable host arrays here;
    accelerator leaves keep orbax's own staging.
    """
    def stable(x):
        if isinstance(x, jax.Array) and x.is_fully_addressable and all(
            d.platform == "cpu" for d in x.devices()
        ):
            return np.array(x, copy=True)
        return x

    return jax.tree_util.tree_map(stable, _arrays_of(state))


def checksum_manifest(arrays: Any) -> dict[str, dict]:
    """Per-leaf crc32/dtype/shape of a pytree's host bytes — the record
    ``restore_latest`` verifies a restored tree against.  Leaf keys are
    ``jax.tree_util.keystr`` paths, stable across save/restore because
    both sides walk the same StandardSave tree structure."""
    flat, _ = jax.tree_util.tree_flatten_with_path(arrays)
    out = {}
    for path, leaf in flat:
        x = np.asarray(leaf)
        out[jax.tree_util.keystr(path)] = {
            "crc32": zlib.crc32(np.ascontiguousarray(x).tobytes()),
            "dtype": str(x.dtype),
            "shape": list(x.shape),
        }
    return out


class CheckpointCorrupted(RuntimeError):
    """A committed checkpoint failed manifest verification."""


class CheckpointManager:
    """Async by default: ``save`` stages device arrays to host memory and
    returns; serialization to disk overlaps the following training epoch
    (orbax's async checkpointer).  Atomicity is orbax's tmp-dir + commit
    rename — a crash mid-save leaves an uncommitted tmp directory that
    ``restore_latest`` ignores, so the previous committed step is what
    restores.  Usable as a context manager; exiting (or ``close``) waits
    for in-flight saves to commit, so every CLI exit path — normal,
    exception, SIGTERM preemption — lands with the final save on disk.

    ``on_anomaly(kind, **fields)`` (optional) receives integrity events
    (``checkpoint_restore_failed``) — the CLI routes it into the flight
    recorder.  ``fault_injector`` (optional, resilience/faults.py) gets
    ``on_checkpoint_saved`` callbacks so ``ckpt_truncate@N`` chaos can
    corrupt a *committed* checkpoint deterministically.
    """

    def __init__(
        self, directory: str, *, max_to_keep: int = 3, async_save: bool = True,
        on_anomaly: Callable[..., None] | None = None,
        fault_injector=None,
    ):
        self.directory = os.path.abspath(directory)
        self.on_anomaly = on_anomaly
        self.fault_injector = fault_injector
        self._last_saved_step: int | None = None
        # Steps that failed to DESERIALIZE during a restore this process
        # ran (not checksum-proven corrupt, so not deleted): a re-save at
        # the same counter replaces them instead of deduping against the
        # unreadable bytes.
        self._bad_steps: set[int] = set()
        self._mgr = ocp.CheckpointManager(
            self.directory,
            # Named up front so a process that never saved (the serving
            # restore) can read a step's metadata.
            item_handlers=ocp.StandardCheckpointHandler(),
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                enable_async_checkpointing=async_save,
            ),
        )

    def _anomaly(self, kind: str, **fields) -> None:
        if self.on_anomaly is not None:
            self.on_anomaly(kind, **fields)

    # ---- save -----------------------------------------------------------

    def save(
        self, state: TrainState, *, step: int | None = None, wait: bool = False
    ) -> None:
        step = int(state.step) if step is None else step
        if step in self._bad_steps:
            # The resumed run re-reached a step whose committed bytes
            # failed to deserialize at restore: replace them.  If even
            # the delete fails, the dedupe below must still see the step
            # (orbax would raise on the duplicate save).
            self._bad_steps.discard(step)
            self._drop_bad_step(step)
        # Dedupe: step-cadence and epoch-end saves can land on the same
        # optimizer step (per_epoch % ckpt_every == 0); orbax raises on a
        # duplicate save, and the bytes would be identical anyway.
        if step == self._last_saved_step or step in set(self._mgr.all_steps()):
            return
        # Pre-save barrier: every process must have finished the step (and
        # any prior restore) before any process starts writing it — a
        # straggler still mutating state while others commit would tear the
        # checkpoint.  Orbax's own commit protocol synchronizes the *end*
        # of the save across hosts.
        if jax.process_count() > 1:
            from ..comm.collectives import barrier

            barrier(f"ckpt_save_{step}")
        arrays = _staged_arrays_of(state)
        self._mgr.save(step, args=ocp.args.StandardSave(arrays))
        self._write_manifest(step, arrays)
        self._last_saved_step = step
        if wait:
            self.wait_until_finished()
        if self.fault_injector is not None:
            self.fault_injector.on_checkpoint_saved(self, step)

    def wait_until_finished(self) -> None:
        """Block until every in-flight async save has committed."""
        self._mgr.wait_until_finished()

    def close(self) -> None:
        """Commit in-flight saves and release orbax's resources; the exit
        half of the context-manager lifecycle."""
        self.wait_until_finished()
        self._mgr.close()

    def __enter__(self) -> "CheckpointManager":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ---- manifest -------------------------------------------------------

    def _manifest_path(self, step: int) -> str:
        return os.path.join(self.directory, f"manifest-{step}.json")

    def _write_manifest(self, step: int, arrays: Any) -> None:
        """Sibling (not in-step-dir: orbax owns that layout) per-leaf
        checksum record, written by rank 0 only — every rank would write
        identical bytes, and the manifest covers the GLOBAL arrays.
        Stale manifests for steps orbax's max_to_keep retired are pruned
        here.  Two coverage limits, both deliberate: multi-host runs skip
        the manifest (checksumming needs the full array bytes, and
        fetching non-addressable shards across hosts is exactly what a
        host-local save must not do), and so do trees with
        accelerator-resident leaves — checksumming those would force a
        SECOND full-state D2H fetch on the save path, re-creating the
        stall async checkpointing exists to hide.  On CPU the staging
        copy (``_staged_arrays_of``) already materialized host arrays,
        so the checksums are free of device traffic.  (TPU manifests
        would belong on orbax's background commit path — ROADMAP.)"""
        if jax.process_count() > 1:
            return
        if any(
            isinstance(leaf, jax.Array)
            and any(d.platform != "cpu" for d in leaf.devices())
            for leaf in jax.tree_util.tree_leaves(arrays)
        ):
            return
        with open(self._manifest_path(step), "w") as f:
            json.dump({"step": step, "leaves": checksum_manifest(arrays)}, f)
        live = set(self._mgr.all_steps()) | {step}
        for path in glob.glob(os.path.join(self.directory, "manifest-*.json")):
            try:
                s = int(os.path.basename(path)[len("manifest-"):-len(".json")])
            except ValueError:
                continue
            if s not in live:
                os.remove(path)

    def _verify(self, step: int, restored: Any) -> None:
        """Compare restored bytes against the step's manifest.  No
        manifest (a pre-manifest checkpoint) verifies vacuously.

        Raises :class:`CheckpointCorrupted` ONLY for bit-rot evidence —
        a leaf present on both sides with matching dtype/shape whose
        bytes changed.  Structural differences (missing/extra leaves,
        dtype/shape drift) mean the CALLER'S template or config changed,
        not the disk — those raise a plain ValueError so the restore
        fallback never treats a good checkpoint as destroyably corrupt."""
        path = self._manifest_path(step)
        if jax.process_count() > 1 or not os.path.exists(path):
            return
        with open(path) as f:
            want = json.load(f)["leaves"]
        got = checksum_manifest(restored)
        structural = sorted(
            key for key in set(want) ^ set(got)
        ) + sorted(
            key for key in set(want) & set(got)
            if (want[key]["dtype"], want[key]["shape"])
            != (got[key]["dtype"], got[key]["shape"])
        )
        if structural:
            raise ValueError(
                f"step {step}: manifest/template structure mismatch on "
                f"{len(structural)} leaves (first: {structural[0]}) — a "
                "config change, not corruption"
            )
        bad = sorted(
            key for key in set(want) & set(got)
            if want[key]["crc32"] != got[key]["crc32"]
        )
        if bad:
            raise CheckpointCorrupted(
                f"step {step}: {len(bad)} leaves fail checksum "
                f"(first: {bad[0]})"
            )

    # ---- restore --------------------------------------------------------

    def restore_latest(self, template: TrainState) -> TrainState | None:
        """Restore the newest VERIFIED checkpoint into ``template``'s
        shardings.

        The checkpoint itself is topology-free: arrays restore into
        WHATEVER mesh/sharding the template's leaves carry, not the
        saving topology's — save under fsdp=2, restore into a
        single-device or tp=2 template and training continues (the
        elastic/preemption path, pinned bitwise by
        tests/test_cli_and_aux.py::test_checkpoint_restore_across_
        topologies).

        Steps are tried newest-first; one that fails to deserialize OR
        fails its manifest checksums is reported (``on_anomaly``
        ``checkpoint_restore_failed``), DELETED (so it stops shadowing
        the good older step as "latest", and the resumed run's re-save of
        that step is not refused by the duplicate-step dedupe), and
        skipped — a corrupt committed step costs at most one checkpoint
        interval of progress instead of crash-looping the supervisor.

        Returns None only when the directory holds no committed step at
        all (a fresh run).  When committed steps exist but EVERY one
        fails, the failure is almost never bit-rot — it is a template
        mismatch (changed model/optimizer config under ``--resume``) or a
        broken filesystem — and silently training from scratch would
        eventually retire the good checkpoints; raise instead.
        """
        steps = sorted(self._mgr.all_steps(), reverse=True)
        errors: list[str] = []
        for step in steps:
            try:
                restored = self._mgr.restore(
                    step, args=ocp.args.StandardRestore(_arrays_of(template))
                )
                self._verify(step, restored)
            except CheckpointCorrupted as e:
                # Checksum-proven bit-rot: independent evidence the disk
                # bytes changed, so the step is safe to drop — it must
                # not shadow the good older step as "latest" or block
                # its own re-save via the duplicate-step dedupe.
                deleted = self._drop_bad_step(step)
                errors.append(f"step {step}: {e}")
                self._anomaly(
                    "checkpoint_restore_failed", step=int(step),
                    error=f"CheckpointCorrupted: {e}", deleted=deleted,
                )
                continue
            except Exception as e:
                # Anything else — truncated files tensorstore refuses to
                # read, template/config mismatches, transient I/O — is
                # NOT proof the checkpoint is bad, so never delete on it
                # (a template mismatch would destroy the whole good
                # history newest-first).  Remember the step so a re-save
                # at the same counter replaces rather than dedupes.
                self._bad_steps.add(step)
                errors.append(f"step {step}: {type(e).__name__}: {e}")
                self._anomaly(
                    "checkpoint_restore_failed", step=int(step),
                    error=f"{type(e).__name__}: {e}", deleted=False,
                )
                continue
            # Re-own the restored buffers: orbax/tensorstore deserializes
            # into memory IT owns (zero-copy views on the CPU backend),
            # and the first donated train step then has XLA free buffers
            # it never allocated — observed as SIGSEGV/heap corruption a
            # couple of steps into any resumed run on the simulated
            # multi-device CPU mesh (pre-existing; the chaos harness
            # flushed it out).  One copy per restore buys XLA-owned,
            # donation-safe leaves with unchanged shardings.
            restored = jax.tree_util.tree_map(
                lambda x: jax.numpy.array(x, copy=True), restored
            )
            return template.replace(
                step=restored["step"],
                params=restored["params"],
                opt_state=restored["opt_state"],
                batch_stats=restored["batch_stats"],
            )
        if steps:
            raise RuntimeError(
                f"no committed checkpoint under {self.directory} could be "
                f"restored ({len(steps)} candidates): " + "; ".join(errors)
            )
        return None

    def _drop_bad_step(self, step: int) -> bool:
        """Remove a bad committed step (+ its manifest) so it cannot
        shadow the good older step or block its own re-save — called for
        checksum-proven corruption at restore, and for a remembered
        deserialize-bad step being replaced by a fresh save.  The
        manifest goes ONLY with the step: removing it while
        the step survives (delete failed — read-only FS, lock) would turn
        a DETECTED-corrupt checkpoint into one that verifies vacuously on
        the next relaunch."""
        try:
            self._mgr.delete(step)
            deleted = True
        except Exception:
            deleted = False
        if deleted:
            manifest = self._manifest_path(step)
            if os.path.exists(manifest):
                os.remove(manifest)
        return deleted

    def restore_params(self):
        """Restore only the ``params`` tree of the newest verified
        checkpoint, as host numpy arrays (None when the directory holds no
        committed step).

        The serving path (cli --serve / serve.ServingEngine) wants the
        trained weights and nothing else — restoring through a TrainState
        template would force the caller to reconstruct the exact optimizer
        (and LR-schedule state shape) the training run used just to throw
        it away.  Every leaf is read as ``np.ndarray``, named so
        explicitly: with no restore type given orbax rebuilds each array
        on the SAVING topology's sharding, which puts a four-chip save
        back on four chips (and fails on a machine that has one).  Host
        arrays land wherever the engine then places them.  Only the
        ``params`` subtree is read; the optimizer state stays on disk.

        Corrupt newer steps fall back like :meth:`restore_latest`
        (params-leaf checksums only — the manifest's other sections cover
        state the serving path never touches), and like it this raises
        when committed steps exist but none restores: serving other
        weights than the ones asked for is not a fallback.
        """
        steps = sorted(self._mgr.all_steps(), reverse=True)
        errors: list[str] = []
        for step in steps:
            try:
                item = {"params": self._mgr.item_metadata(step).tree["params"]}
                params = ocp.PyTreeCheckpointer().restore(
                    # The manager's layout: one "default" item per step.
                    os.path.join(self.directory, str(step), "default"),
                    args=ocp.args.PyTreeRestore(
                        item=item,
                        restore_args=jax.tree_util.tree_map(
                            lambda _: ocp.RestoreArgs(restore_type=np.ndarray),
                            item,
                        ),
                        partial_restore=True,
                    ),
                )["params"]
                self._verify_params(step, params)
            except Exception as e:
                errors.append(f"step {step}: {type(e).__name__}: {e}")
                self._anomaly(
                    "checkpoint_restore_failed", step=int(step),
                    error=f"{type(e).__name__}: {e}",
                )
                continue
            return params
        if steps:
            raise RuntimeError(
                f"no committed checkpoint under {self.directory} could be "
                f"restored ({len(steps)} candidates): " + "; ".join(errors)
            )
        return None

    def _verify_params(self, step: int, params: Any) -> None:
        path = self._manifest_path(step)
        if jax.process_count() > 1 or not os.path.exists(path):
            return
        with open(path) as f:
            want = json.load(f)["leaves"]
        got = checksum_manifest({"params": params})
        bad = sorted(
            key for key, rec in got.items()
            if key in want and want[key] != rec
        )
        if bad:
            raise CheckpointCorrupted(
                f"step {step}: {len(bad)} params leaves fail checksum "
                f"(first: {bad[0]})"
            )

    def all_steps(self) -> list[int]:
        return list(self._mgr.all_steps())
