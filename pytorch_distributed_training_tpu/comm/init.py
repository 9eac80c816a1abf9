"""Multi-process (multi-host) runtime initialization.

TPU-native equivalent of the reference's distributed-init block
(src/main.py:35-42): ``dist.init_process_group(backend='nccl'|'gloo')`` with
env:// rendezvous (MASTER_ADDR/MASTER_PORT/RANK/WORLD_SIZE read by the c10d
TCPStore) becomes ``jax.distributed.initialize`` against a coordinator
address.  Rank/world-size queries (``dist.get_rank``/``dist.get_world_size``,
src/main.py:42) become ``jax.process_index``/``jax.process_count``.

For launcher compatibility we honor the same environment contract the
reference relies on (the torchrun contract visible at src/main.py:38):
``MASTER_ADDR``/``MASTER_PORT``/``WORLD_SIZE``/``RANK`` are accepted as a
fallback spelling of JAX's ``coordinator_address``/``num_processes``/
``process_id``.  On Cloud TPU pods, ``jax.distributed.initialize()`` with no
arguments auto-discovers everything from the pod metadata, so all arguments
are optional.
"""

from __future__ import annotations

import logging
import os

import jax

logger = logging.getLogger(__name__)

_initialized = False


def _env_rendezvous() -> dict:
    """Derive coordinator/num_processes/process_id from torchrun-style env vars.

    Mirrors the env contract the reference depends on (src/main.py:38 reads
    ``WORLD_SIZE``; MASTER_ADDR/MASTER_PORT/RANK are read by c10d's env://
    rendezvous behind src/main.py:39-41).
    """
    kwargs: dict = {}
    addr = os.environ.get("MASTER_ADDR")
    port = os.environ.get("MASTER_PORT")
    if addr and port:
        kwargs["coordinator_address"] = f"{addr}:{port}"
    if "WORLD_SIZE" in os.environ:
        kwargs["num_processes"] = int(os.environ["WORLD_SIZE"])
    if "RANK" in os.environ:
        kwargs["process_id"] = int(os.environ["RANK"])
    return kwargs


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Initialize the multi-host runtime (idempotent).

    Single-process runs (the reference's non-``--distributed`` path,
    src/main.py:55-57) need not call this; calling it with no arguments and
    no env contract is a no-op outside a multi-host environment.
    """
    global _initialized
    if _initialized or jax.distributed.is_initialized():
        _initialized = True
        return

    env = _env_rendezvous()
    if coordinator_address is None:
        coordinator_address = env.get("coordinator_address")
    if num_processes is None:
        num_processes = env.get("num_processes")
    if process_id is None:
        process_id = env.get("process_id")

    # Single-process world (the reference's own degrade path — it *asserts*
    # WORLD_SIZE>1 at src/main.py:38; we no-op instead): nothing to do.
    if num_processes is not None and num_processes <= 1:
        return

    if num_processes is not None and coordinator_address is None:
        raise ValueError(
            f"WORLD_SIZE={num_processes} > 1 but no coordinator address: "
            "set MASTER_ADDR and MASTER_PORT (torchrun contract) or pass "
            "coordinator_address explicitly."
        )

    if coordinator_address is None and num_processes is None:
        # Cloud TPU pod: jax auto-discovers; single host: nothing to do.
        hostnames = [
            h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",") if h
        ]
        if len(hostnames) > 1 or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
            jax.distributed.initialize()
            _initialized = True
        return

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    logger.info(
        "Process group initialized - WORLD_SIZE: %d, RANK: %d",
        jax.process_count(),
        jax.process_index(),
    )


def is_initialized() -> bool:
    return _initialized or jax.distributed.is_initialized()


def process_count() -> int:
    """World size (``dist.get_world_size()`` equivalent, src/main.py:42)."""
    return jax.process_count()


def process_index() -> int:
    """Global rank (``dist.get_rank()`` equivalent, src/main.py:42, 51)."""
    return jax.process_index()


def shutdown() -> None:
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False
