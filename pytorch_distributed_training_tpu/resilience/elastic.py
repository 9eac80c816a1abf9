"""Elastic world resizing: shrink-to-survivors, peer-RAM state, grow-back.

The supervised ``--elastic`` path treats every failure the same way: kill
the world, back off, relaunch at the SAME world size from a disk
checkpoint.  For a multi-slice data-parallel run that is the wrong shape
— losing one slice leaves a perfectly healthy slice idling through
backoff + restore.  This module is the membership plane that lets the
run keep training instead (``--elastic-resize``):

- **detection** (:class:`SliceHealthMonitor`) — driven from the flight
  recorder's per-rank heartbeat stream, never from exit codes: a rank
  whose heartbeat staleness exceeds the patience takes its slice with it
  (its collectives would hang every survivor), and a short stall below
  patience is flagged as a ``host_stall`` anomaly without a death — the
  false-positive half of the detector's contract, chaos-tested by
  ``host_hang@N:S`` (:data:`~.faults.ELASTIC_FAULT_KINDS`).
- **peer-redundant snapshots** (:class:`PeerSnapshotStore`) — on the
  snapshot cadence every rank's unique state shard (the zero1 optimizer
  shard + EF residuals that die with the rank, arXiv:2004.13336) is
  mirrored to a buddy rank on the OTHER slice over DCN.  The wire cost
  reuses the grad-sync codec accounting (``comm.compress
  .bucket_wire_bytes``); the payload itself rides the raw bytes of each
  leaf — the ONE codec whose restore is bit-identical, which is why the
  lossy grad codecs are rejected for this tier.  Disk remains the
  fallback below it, exactly like the serving KV host tier backs the
  device pool.
- **resize** (:func:`run_elastic_episode`) — on loss the run rolls back
  to the last committed peer snapshot (restored leaves are pinned
  bit-identical), rebuilds the mesh over the survivors (``comm/mesh``),
  re-infers the state shardings (``train.state.infer_state_shardings``),
  and re-partitions the consumed-batch schedule: the global batch is a
  pure function of the GLOBAL step, so preserving it across a resize is
  a matter of scaling per-rank grad accumulation by the world ratio —
  the shrunk run consumes exactly the batch sequence an oracle run at
  the shrunk size would.
- **grow-back** — the returning slice re-enters on the supervisor's
  shared :class:`~..utils.backoff.BackoffPolicy`, receives the current
  state from its buddy over DCN, and the run re-expands at a step
  boundary.

Every transition (shrink, peer_restore, grow) is a schema'd
``elastic_transition`` record, mirrored into ``elastic_*`` counters, and
the goodput ledger's identity ``sum(categories) == wall_clock`` holds in
integer ns through the whole episode: the shrink window's re-executed
steps classify as ``rework`` (both the discarded originals, via
``note_rollback``, and the re-executions, via ``set_rework_until``) and
the peer restore lands under ``ckpt_restore``.  The episode is scripted
against a virtual clock in binary-exact durations (multiples of 2^-3 s),
so every pinned total is ONE exact integer — the same discipline as
``analysis/ledger_audit.py``.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Iterable

import numpy as np

from ..utils.backoff import BackoffPolicy
from .faults import ELASTIC_FAULT_KINDS, Fault, _FiredMarkers, parse_elastic_faults
from .recovery import SNAPSHOT_FIELDS

# The transition kinds an ``elastic_transition`` record may carry.
ELASTIC_TRANSITIONS = ("shrink", "peer_restore", "grow")

# Where a restore's payload came from; stamped on the checkpoint_restore
# record so the provenance survives into the post-mortem.
RESTORE_SOURCES = ("disk", "peer")

# Scripted ledger durations (seconds).  All multiples of 2^-3, so every
# expected category total is one exact integer in ns — the episode's
# pinned numbers depend on this, like analysis/ledger_audit.py's.
COMPILE_S = 2.0          # initial compile of the train step
RESHAPE_COMPILE_S = 0.5  # recompile at the resized world
PULL_S = 0.125           # input pull per step -> data_wait
DISPATCH_S = 0.25        # batch-ready -> dispatch
TAIL_S = 0.125           # post-dispatch host tail
SNAP_S = 0.25            # peer snapshot staging + mirror -> ckpt_save
PEER_RESTORE_S = 0.25    # one-hop RAM restore -> ckpt_restore
DISK_RESTORE_S = 2.0     # the disk fallback's manifest walk (bench leg)
GROW_SYNC_S = 0.25       # buddy -> returning slice state transfer
BACKOFF_BASE_S = 0.5     # BackoffPolicy base for the re-entry wait
EPOCH_TAIL_S = 0.125     # episode-end bookkeeping -> other


class _VirtualClock:
    """Monotonic clock the episode advances explicitly."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, seconds: float) -> None:
        self.t += seconds


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Knobs of the membership plane (CLI ``--elastic-resize``)."""

    n_slices: int = 2
    # Heartbeat staleness (in step boundaries) past which a silent rank
    # takes its slice down.  Staleness at or below it only flags.
    patience_steps: int = 3
    # Staleness that flags a host_stall anomaly without a death.
    stall_flag_after: int = 1
    snapshot_every_steps: int = 2


class SliceHealthMonitor:
    """Slice liveness from per-rank heartbeat staleness — never exit codes.

    The write side of the flight recorder emits one heartbeat event per
    rank per step boundary; :meth:`ingest` consumes exactly those events
    and :meth:`observe` turns staleness into verdicts: a rank more than
    ``patience_steps`` boundaries stale declares its whole slice lost
    (a data-parallel collective with a silent member hangs every
    survivor, so slice granularity is the only safe one), and a rank
    past ``stall_flag_after`` but within patience raises a
    ``host_stall`` anomaly once per stall episode.
    """

    def __init__(
        self,
        world_size: int,
        n_slices: int,
        *,
        patience_steps: int = 3,
        stall_flag_after: int = 1,
        emitter=None,
    ):
        if world_size % n_slices:
            raise ValueError(
                f"world {world_size} not divisible into {n_slices} slices"
            )
        if not 0 < stall_flag_after <= patience_steps:
            raise ValueError(
                f"want 0 < stall_flag_after <= patience_steps, got "
                f"{stall_flag_after}/{patience_steps}"
            )
        self.world_size = world_size
        self.n_slices = n_slices
        self.per_slice = world_size // n_slices
        self.patience_steps = patience_steps
        self.stall_flag_after = stall_flag_after
        self.emitter = emitter
        self._last_beat = {r: -1 for r in range(world_size)}
        self._stall_flagged: set[int] = set()
        self.host_stalls = 0

    def slice_of(self, rank: int) -> int:
        return rank // self.per_slice

    def ingest(self, event: dict[str, Any]) -> None:
        """Consume one heartbeat event (``kind="heartbeat"`` with
        ``step`` and ``hb_rank`` fields, as the episode emits them)."""
        if event.get("kind") != "heartbeat":
            return
        rank, step = int(event["hb_rank"]), int(event["step"])
        if step > self._last_beat[rank]:
            self._last_beat[rank] = step

    def staleness(self, rank: int, step: int) -> int:
        return step - self._last_beat[rank]

    def observe(self, step: int) -> dict[str, Any]:
        """Verdicts at boundary ``step``: ``lost_slices`` (sorted) and
        ``stalled_ranks`` (silent past the flag threshold but within
        patience)."""
        lost: set[int] = set()
        stalled: list[int] = []
        for rank in range(self.world_size):
            stale = self.staleness(rank, step)
            if stale > self.patience_steps:
                lost.add(self.slice_of(rank))
            elif stale > self.stall_flag_after:
                stalled.append(rank)
                if rank not in self._stall_flagged:
                    self._stall_flagged.add(rank)
                    self.host_stalls += 1
                    if self.emitter is not None:
                        self.emitter.anomaly(
                            "host_stall", step=step, stalled_rank=rank,
                            staleness_steps=stale,
                        )
            else:
                self._stall_flagged.discard(rank)
        return {"lost_slices": sorted(lost), "stalled_ranks": stalled}


class PeerSnapshotStore:
    """In-memory snapshots, row-sharded over ranks with cross-slice buddies.

    The committed state's learned fields (:data:`SNAPSHOT_FIELDS` — the
    zero1 optimizer shard + EF residuals included) are serialized leaf-
    by-leaf to raw bytes, concatenated, padded, and split into one equal
    byte row per rank.  Rank ``r`` keeps its own row; its buddy — the
    same position on the NEXT slice — keeps a mirror, so losing any one
    slice loses no row: every dead rank's row survives in a mirror on
    the other slice, one DCN hop away.  Raw bytes (not the grad codecs'
    f32 flatten) because the restore contract is BIT-identity for every
    dtype in the tree; the lossy codecs are structurally rejected.  Wire
    cost per mirror hop is accounted with the same
    ``comm.compress.bucket_wire_bytes`` table the grad sync prices its
    DCN traffic with.
    """

    def __init__(
        self,
        world_size: int,
        n_slices: int,
        *,
        codec: str = "f32",
        emitter=None,
    ):
        if world_size % n_slices:
            raise ValueError(
                f"world {world_size} not divisible into {n_slices} slices"
            )
        if codec != "f32":
            raise ValueError(
                f"peer snapshots require the lossless f32 codec, got "
                f"{codec!r}: the restore contract is bit-identity, which "
                "no lossy grad-sync codec (bf16/int8/int4/topk) can honor"
            )
        self.world_size = world_size
        self.n_slices = n_slices
        self.per_slice = world_size // n_slices
        self.codec = codec
        self.emitter = emitter
        self.committed_step: int | None = None
        self._committed_ranks: list[int] = []
        self._specs: list[tuple[str, tuple[int, ...]]] | None = None
        self._treedef = None
        self._blob_len = 0
        self._digest: str | None = None
        self._ranks: list[int] = list(range(world_size))
        self._primary: dict[int, bytes] = {}
        self._mirror: dict[int, bytes] = {}
        self.total_wire_bytes = 0

    def buddy(self, rank: int, ranks: list[int] | None = None) -> int | None:
        """The rank holding ``rank``'s mirror: same position on the next
        active slice, or None when only one slice is active (degraded —
        no peer tier, disk is the only fallback)."""
        ranks = self._ranks if ranks is None else ranks
        slices = sorted({r // self.per_slice for r in ranks})
        if len(slices) < 2:
            return None
        s, pos = rank // self.per_slice, rank % self.per_slice
        nxt = slices[(slices.index(s) + 1) % len(slices)]
        return nxt * self.per_slice + pos

    # ---- commit ---------------------------------------------------------

    def put(self, step: int, state, *, ranks: list[int] | None = None) -> int:
        """Commit ``state``'s learned fields at boundary ``step`` over the
        ``ranks`` currently in the world; returns the DCN wire bytes the
        mirror hops cost (0 when degraded to one slice)."""
        import jax

        ranks = sorted(ranks) if ranks is not None else list(range(self.world_size))
        tree = {f: getattr(state, f) for f in SNAPSHOT_FIELDS}
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        host = [np.asarray(leaf) for leaf in leaves]
        self._specs = [(a.dtype.str, a.shape) for a in host]
        self._treedef = treedef
        blob = b"".join(a.tobytes() for a in host)
        self._blob_len = len(blob)
        self._digest = hashlib.sha256(blob).hexdigest()
        # Pad so the blob splits into equal rows of whole f32 columns —
        # bucket_wire_bytes prices per-column, like the grad buckets.
        n = len(ranks)
        row = -(-self._blob_len // (4 * n)) * 4
        blob += b"\x00" * (row * n - self._blob_len)
        self._ranks = ranks
        self._primary = {r: blob[i * row:(i + 1) * row]
                         for i, r in enumerate(ranks)}
        self._mirror = {}
        from ..comm.compress import bucket_wire_bytes

        wire = 0
        for r in ranks:
            b = self.buddy(r, ranks)
            if b is not None:
                # Mirror of r's row, physically resident on buddy b.
                self._mirror[r] = self._primary[r]
                wire += bucket_wire_bytes(row // 4, self.codec)
        self.committed_step = step
        self._committed_ranks = ranks
        self.total_wire_bytes += wire
        return wire

    # ---- loss + restore -------------------------------------------------

    def drop_slice(self, lost_slice: int) -> None:
        """Slice death: its ranks' primaries vanish, and so does every
        mirror that was resident on one of them."""
        dead = {r for r in self._ranks if r // self.per_slice == lost_slice}
        for r in dead:
            self._primary.pop(r, None)
        for r in list(self._mirror):
            if self.buddy(r) in dead:
                del self._mirror[r]
        self._ranks = [r for r in self._ranks if r not in dead]

    def restore(self):
        """Reassemble the committed tree from surviving rows (primary
        where the owner lives, its buddy's mirror where it does not) and
        unpack it BIT-identically.  Raises when a row survives nowhere —
        the caller falls back to the disk tier."""
        import jax

        if self.committed_step is None:
            raise RuntimeError("no committed peer snapshot to restore")
        # Every rank of the COMMIT must contribute its row — a rank
        # whose primary and mirror both died is absent from the
        # survivors entirely, not present-but-None.
        owners = self._committed_ranks
        missing = [
            r for r in owners
            if r not in self._primary and r not in self._mirror
        ]
        if missing:
            raise RuntimeError(
                f"peer snapshot rows lost for ranks {missing}: both owner "
                "and buddy died — fall back to the disk tier"
            )
        rows = [self._primary.get(r, self._mirror.get(r)) for r in owners]
        blob = b"".join(rows)[: self._blob_len]
        if hashlib.sha256(blob).hexdigest() != self._digest:
            raise RuntimeError(
                "reassembled peer snapshot does not match the committed "
                "digest — refusing a corrupt restore"
            )
        leaves, off = [], 0
        for dtype_str, shape in self._specs:
            dt = np.dtype(dtype_str)
            nbytes = dt.itemsize * int(np.prod(shape, dtype=np.int64))
            leaves.append(
                np.frombuffer(blob, dt, count=int(np.prod(shape, dtype=np.int64)),
                              offset=off).reshape(shape).copy()
            )
            off += nbytes
        return self.committed_step, jax.tree_util.tree_unflatten(
            self._treedef, leaves
        )


class ElasticWorld:
    """Membership + accounting spine of one elastic run.

    Owns the integer transition counters (the host side of the
    ``counters == telemetry == report`` pin), the transition log, and
    the ``/slo`` ``elastic`` block (:meth:`snapshot`, wired through
    ``obs.http.OpsServer(elastic=...)``).
    """

    def __init__(self, world_size: int, n_slices: int, *, emitter=None):
        self.initial_world_size = world_size
        self.world_size = world_size
        self.n_slices = n_slices
        self.active_slices = sorted(range(n_slices))
        self.emitter = emitter
        self.counters = {
            "elastic_shrinks": 0,
            "elastic_grows": 0,
            "elastic_peer_restores": 0,
            "elastic_peer_snapshot_bytes": 0,
            "elastic_host_stalls": 0,
        }
        self.transitions: list[dict[str, Any]] = []
        self._gauge()

    def _gauge(self) -> None:
        if self.emitter is not None:
            self.emitter.gauge("elastic_world_size", self.world_size)

    def count(self, name: str, value: int = 1) -> None:
        self.counters[name] += value
        if self.emitter is not None:
            self.emitter.counter_add(name, value)

    def transition(self, kind: str, *, step: int, world_to: int,
                   **fields: Any) -> None:
        if kind not in ELASTIC_TRANSITIONS:
            raise ValueError(f"unknown elastic transition {kind!r}")
        # "transition", not "kind": the record payload merges into the
        # event envelope, whose "kind" field is the event kind itself.
        rec = {
            "transition": kind, "step": int(step),
            "world_from": self.world_size, "world_to": int(world_to),
            **fields,
        }
        self.transitions.append(rec)
        self.world_size = int(world_to)
        self._gauge()
        if self.emitter is not None:
            self.emitter.emit("record", {"record": "elastic_transition", **rec})

    def snapshot(self) -> dict[str, Any]:
        """The ``/slo`` payload's ``elastic`` block."""
        return {
            "world_size": self.world_size,
            "initial_world_size": self.initial_world_size,
            "active_slices": list(self.active_slices),
            "counters": dict(self.counters),
            "transitions": [dict(t) for t in self.transitions],
        }


# ---------------------------------------------------------------------- #
# the scripted elastic episode (CLI --elastic-resize, tests, graftcheck)
# ---------------------------------------------------------------------- #


def _global_batch_for(step: int, *, seed: int, rows: int, seq_len: int,
                      vocab: int) -> np.ndarray:
    """The consumed-batch schedule: a pure function of the GLOBAL step,
    so any world size consumes the identical global batch at step N —
    the invariant that makes resize-time re-partitioning a pure
    accumulation-scaling problem."""
    rng = np.random.default_rng(seed * 1_000_003 + step)
    return rng.integers(0, vocab, (rows, seq_len), np.int32)


def batch_digest(tokens: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(tokens).tobytes()).hexdigest()[:16]


def oracle_batch_digests(n_steps: int, *, seed: int = 0, rows: int = 16,
                         seq_len: int = 16, vocab: int = 128) -> list[str]:
    """What ANY correctly re-partitioned run must consume at each global
    step — the oracle the shrunk run's schedule is pinned against."""
    return [
        batch_digest(_global_batch_for(
            g, seed=seed, rows=rows, seq_len=seq_len, vocab=vocab
        ))
        for g in range(n_steps)
    ]


def run_elastic_episode(
    *,
    faults: list[Fault] | str,
    n_steps: int = 10,
    devices: list | None = None,
    config: ElasticConfig | None = None,
    accum: int = 2,
    global_batch: int = 16,
    seq_len: int = 16,
    seed: int = 0,
    emitter=None,
    ledger=None,
    clock: _VirtualClock | None = None,
    backoff: BackoffPolicy | None = None,
    state_dir: str | None = None,
) -> dict[str, Any]:
    """One deterministic elastic episode on the simulated 2-slice mesh.

    Trains the canonical tiny GPT-2 (the ``tools/grad_sync_diag``
    configuration) at the full world, fires the elastic fault plan,
    shrinks to the survivors on detection (peer-RAM restore, rebuilt
    mesh, re-inferred shardings, doubled grad accumulation), grows back
    on ``slice_return``, and returns the audited report: transitions,
    host counters, per-step consumed-batch digests, the bit-identity
    verdict of the peer restore, and the goodput ledger's finalized
    identity-exact attribution.  Everything the report carries is a pure
    function of the arguments — the run-twice determinism pin.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from ..comm.mesh import MeshConfig, make_hybrid_mesh, make_mesh
    from ..models.gpt2 import GPT2, GPT2Config
    from ..parallel.sharding import DDP_RULES, shard_batch
    from ..train import create_train_state, make_train_step
    from ..train.state import infer_state_shardings
    from ..obs.ledger import GoodputLedger

    cfg = config or ElasticConfig()
    if isinstance(faults, str):
        faults = parse_elastic_faults(faults)
    for f in faults:
        if f.kind not in ELASTIC_FAULT_KINDS:
            raise ValueError(
                f"fault {f.name} is not an elastic membership fault "
                f"{ELASTIC_FAULT_KINDS} — training faults belong to "
                "--inject-faults"
            )
    if devices is None:
        devices = jax.devices()
    n_slices = cfg.n_slices
    if len(devices) % n_slices or len(devices) // n_slices < 2:
        raise ValueError(
            f"{len(devices)} devices do not form {n_slices} slices of >= 2"
        )
    world = len(devices)
    per_slice = world // n_slices
    for f in faults:
        if f.kind == "slice_lost" and not 0 <= int(f.arg) < n_slices:
            raise ValueError(
                f"elastic fault {f.name}: slice {int(f.arg)} out of range "
                f"for {n_slices} slices"
            )
    shrink_accum = accum * n_slices // (n_slices - 1) if n_slices > 1 else accum
    if global_batch % world or global_batch % accum \
            or global_batch % shrink_accum:
        raise ValueError(
            f"global batch {global_batch} must divide over {world} ranks, "
            f"{accum} microbatches, and the shrunk-world {shrink_accum} "
            "microbatches — the global batch is preserved across a resize "
            "by scaling accumulation, never by changing the batch"
        )

    clock = clock or _VirtualClock()
    ledger = ledger or GoodputLedger(clock=clock, inherited_backoff_s=0.0)
    backoff = backoff or BackoffPolicy(base_s=BACKOFF_BASE_S, jitter=0.0)
    markers = _FiredMarkers(state_dir)
    monitor = SliceHealthMonitor(
        world, n_slices, patience_steps=cfg.patience_steps,
        stall_flag_after=cfg.stall_flag_after, emitter=emitter,
    )
    store = PeerSnapshotStore(world, n_slices, emitter=emitter)
    eworld = ElasticWorld(world, n_slices, emitter=emitter)

    # ---- model + step at the full world --------------------------------
    full_mesh = make_hybrid_mesh(
        MeshConfig(data=-1), devices=devices, n_slices=n_slices
    )
    model_cfg = GPT2Config(
        vocab_size=128, max_seq_len=seq_len, num_layers=2, num_heads=2,
        hidden_dim=32,
    )
    state = create_train_state(
        GPT2(cfg=model_cfg), jax.random.PRNGKey(seed),
        jnp.zeros((8, seq_len), jnp.int32),
        optax.adam(1e-3), mesh=full_mesh, rules=DDP_RULES,
        init_kwargs={"train": False},
    )

    def build_step(mesh, n_micro):
        shardings = infer_state_shardings(state, mesh)
        return make_train_step(
            kind="lm", num_microbatches=n_micro, state_shardings=shardings,
        ), shardings

    mesh = full_mesh
    cur_accum = accum
    with ledger.bracket("compile"):
        clock.advance(COMPILE_S)
    step_fn, _ = build_step(mesh, cur_accum)

    # ---- membership simulation state ------------------------------------
    lost_slice: int | None = None     # declared-lost slice (shrunk window)
    silent: set[int] = set()          # ranks not beating (slice_lost)
    hang_until: dict[int, int] = {}   # host_hang: rank -> first step it beats
    return_armed = False              # slice_return fired, awaiting grow
    restore_bit_identical: bool | None = None
    committed_copy: dict | None = None
    committed_copy_step: int | None = None
    step_log: list[dict[str, Any]] = []
    active_ranks = list(range(world))

    def host_copy(st):
        return {
            f: jax.tree_util.tree_map(
                lambda x: np.asarray(x).copy(), getattr(st, f)
            )
            for f in SNAPSHOT_FIELDS
        }

    def commit(step_boundary: int, st) -> None:
        nonlocal committed_copy, committed_copy_step
        with ledger.bracket("ckpt_save"):
            clock.advance(SNAP_S)
            wire = store.put(step_boundary, st, ranks=active_ranks)
        committed_copy = host_copy(st)
        committed_copy_step = step_boundary
        if wire:
            eworld.count("elastic_peer_snapshot_bytes", wire)
        ledger.note_snapshot(step_boundary)

    def fire_faults(g: int) -> None:
        nonlocal lost_slice, return_armed
        for f in faults:
            if f.step != g or markers.fired(f.name):
                continue
            markers.mark(f.name)
            if emitter is not None:
                emitter.anomaly(
                    "fault_injected", fault=f.kind, fault_step=f.step,
                )
            if f.kind == "slice_lost":
                k = int(f.arg)
                silent.update(
                    r for r in range(world) if r // per_slice == k
                )
            elif f.kind == "slice_return":
                if silent:
                    silent.clear()
                    return_armed = True
                elif emitter is not None:
                    emitter.anomaly(
                        "slice_return", step=g, ignored=True,
                        reason="no slice is lost or silent",
                    )
            else:  # host_hang
                hang_until[0] = g + int(f.arg)

    def beats(g: int) -> None:
        for r in range(world):
            if r in silent:
                continue
            if r in hang_until and g < hang_until[r]:
                continue
            ev = {"kind": "heartbeat", "step": g, "hb_rank": r}
            if emitter is not None:
                emitter.heartbeat(step=g, hb_rank=r)
            monitor.ingest(ev)

    def place(host_tree, mesh_):
        shardings = infer_state_shardings(state, mesh_)
        placed = {
            f: jax.tree_util.tree_map(
                jax.device_put, host_tree[f], getattr(shardings, f)
            )
            for f in SNAPSHOT_FIELDS
        }
        return placed, shardings

    def shrink(g: int, lost: int) -> int:
        """Shrink to the survivors at detection boundary ``g``; returns
        the resume step (the committed snapshot boundary)."""
        nonlocal mesh, cur_accum, step_fn, lost_slice
        nonlocal restore_bit_identical, active_ranks, state
        lost_slice = lost
        if emitter is not None:
            emitter.anomaly(
                "slice_lost", step=g, lost_slice=lost,
                detected_from="heartbeat_staleness",
            )
        snap_step = store.committed_step
        # The doomed window's already-charged steps move to rework
        # (discarded originals); their re-executions classify as rework
        # too via the watermark.  The detection step itself never
        # dispatched, so its first execution stays fresh.
        if g > snap_step:
            ledger.note_rollback(snap_step, g - 1)
        ledger.set_rework_until(g)
        store.drop_slice(lost)
        active_ranks = [r for r in active_ranks if r // per_slice != lost]
        survivors = [
            d for i, d in enumerate(devices) if i // per_slice != lost
        ]
        eworld.active_slices = [s for s in eworld.active_slices if s != lost]
        eworld.count("elastic_shrinks")
        eworld.transition(
            "shrink", step=g, world_to=len(survivors), lost_slice=lost,
            resumed_from_step=snap_step,
        )
        mesh = make_mesh(MeshConfig(data=-1), devices=survivors)
        with ledger.bracket("ckpt_restore"):
            clock.advance(PEER_RESTORE_S)
            restored_step, host_tree = store.restore()
            placed, shardings = place(host_tree, mesh)
        restore_bit_identical = committed_copy_step == restored_step and all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for f in SNAPSHOT_FIELDS
            for a, b in zip(
                jax.tree_util.tree_leaves(host_tree[f]),
                jax.tree_util.tree_leaves(committed_copy[f]),
            )
        )
        state = state.replace(
            step=jax.device_put(
                jnp.asarray(restored_step, jnp.int32), shardings.step
            ),
            **placed,
        )
        if emitter is not None:
            emitter.emit("record", {
                "record": "checkpoint_restore", "step": restored_step,
                "restore_source": "peer",
            })
        eworld.count("elastic_peer_restores")
        eworld.transition(
            "peer_restore", step=g, world_to=eworld.world_size,
            restore_source="peer", snapshot_step=restored_step,
        )
        # Re-partition: the SAME global batch at the smaller world means
        # proportionally more microbatches per surviving rank.
        cur_accum = accum * (world // len(survivors))
        with ledger.bracket("compile"):
            clock.advance(RESHAPE_COMPILE_S)
        step_fn, _ = build_step(mesh, cur_accum)
        return restored_step

    def grow(g: int) -> None:
        """Re-expand to the full world at boundary ``g``: backoff wait,
        buddy state transfer, recompile, re-armed peer tier."""
        nonlocal mesh, cur_accum, step_fn, lost_slice, return_armed
        nonlocal active_ranks, state
        from ..comm.compress import bucket_wire_bytes

        with ledger.bracket("supervisor_backoff"):
            clock.advance(backoff.delay(1))
        # The returning slice pulls the current state from its buddies
        # over DCN — setup cost, not a restore of THIS run's state.
        with ledger.bracket("other"):
            clock.advance(GROW_SYNC_S)
        grow_wire = bucket_wire_bytes(-(-store._blob_len // 4), store.codec)
        if emitter is not None:
            emitter.anomaly("slice_return", step=g, returned_slice=lost_slice)
        mesh = full_mesh
        active_ranks = list(range(world))
        host_tree = host_copy(state)
        placed, shardings = place(host_tree, mesh)
        state = state.replace(
            step=jax.device_put(jnp.asarray(g, jnp.int32), shardings.step),
            **placed,
        )
        cur_accum = accum
        with ledger.bracket("compile"):
            clock.advance(RESHAPE_COMPILE_S)
        step_fn, _ = build_step(mesh, cur_accum)
        eworld.active_slices = sorted(eworld.active_slices + [lost_slice])
        eworld.count("elastic_grows")
        eworld.transition(
            "grow", step=g, world_to=world, returned_slice=lost_slice,
            wire_bytes=grow_wire,
        )
        lost_slice = None
        return_armed = False
        # Re-arm the peer tier immediately: the re-entered slice's first
        # duty is holding its buddies' mirrors again.
        commit(g, state)

    def pulls(n: int) -> Iterable:
        for _ in range(n):
            clock.advance(PULL_S)
            yield None

    # Initial commit: the peer tier is armed from step 0 (RecoveryManager's
    # first-opportunity staging), so the first loss never needs the disk.
    commit(0, state)

    g = 0
    while g < n_steps:
        # One segment = a contiguous run of steps at one world size,
        # bracketed by wrap_batches so pull time is data_wait and the
        # batch-ready..dispatch interval joins each step's own class —
        # the exact attribution contract analysis/ledger_audit.py pins.
        # A shrink breaks out (rewinding g) and opens a fresh segment.
        for _ in ledger.wrap_batches(pulls(n_steps - g)):
            # Step boundary: faults fire, heartbeats land, verdicts.
            fire_faults(g)
            beats(g)
            verdict = monitor.observe(g)
            if monitor.host_stalls > eworld.counters["elastic_host_stalls"]:
                eworld.count(
                    "elastic_host_stalls",
                    monitor.host_stalls
                    - eworld.counters["elastic_host_stalls"],
                )
            newly_lost = [
                s for s in verdict["lost_slices"]
                if s in eworld.active_slices
            ]
            if newly_lost and lost_slice is None:
                g = shrink(g, newly_lost[0])
                break  # new segment at the shrunk world
            if return_armed and lost_slice is not None:
                grow(g)

            # ---- the step itself ---------------------------------------
            tokens = _global_batch_for(
                g, seed=seed, rows=global_batch, seq_len=seq_len,
                vocab=model_cfg.vocab_size,
            )
            step_log.append({
                "step": g,
                "digest": batch_digest(tokens),
                "world": eworld.world_size,
                "accum": cur_accum,
                "global_rows": int(tokens.shape[0]),
            })
            clock.advance(DISPATCH_S)
            ledger.begin_step(g)
            with mesh:
                state, _metrics = step_fn(
                    state, shard_batch({"tokens": tokens}, mesh)
                )
            clock.advance(TAIL_S)
            g += 1
            ledger.note_progress(g)
            if g % cfg.snapshot_every_steps == 0 and g < n_steps:
                commit(g, state)

    clock.advance(EPOCH_TAIL_S)
    final = ledger.finalize(emitter)
    report = {
        "world": {
            "initial": world,
            "final": eworld.world_size,
            "n_slices": n_slices,
        },
        "counters": dict(eworld.counters),
        "transitions": [dict(t) for t in eworld.transitions],
        "steps": step_log,
        "batch_digests": [row["digest"] for row in step_log],
        "restore_bit_identical": restore_bit_identical,
        "host_stalls": monitor.host_stalls,
        "peer_snapshot_wire_bytes": store.total_wire_bytes,
        "final_step": int(np.asarray(state.step)),
        "ledger": final,
        "elastic": eworld.snapshot(),
    }
    return report
