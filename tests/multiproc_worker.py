"""Worker for the real 2-process distributed test (VERDICT r1 item 5).

Each process: torchrun-style env rendezvous (the reference's contract,
/root/reference/src/main.py:38) → ``comm.initialize`` → per-process loader
shard → ``make_array_from_process_local_data`` assembly via ``shard_batch``
→ two DP train steps on a global 2-device CPU mesh → prints a JSON result
line the parent asserts on (identical losses and parameter checksums across
ranks = the DDP broadcast/allreduce contract).

Run: MASTER_ADDR=localhost MASTER_PORT=<p> WORLD_SIZE=2 RANK=<r> python
tests/multiproc_worker.py
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def launch_workers(
    n_procs: int = 2, *, devices_per_proc: int = 1, timeout: float = 280.0
) -> list[dict]:
    """Spawn ``n_procs`` worker processes with torchrun-style env rendezvous
    and return their parsed JSON result lines (rank-ordered).

    ``devices_per_proc > 1`` simulates the real pod host shape (one process
    owning several chips, 8/host on v5e): each worker gets that many CPU
    devices, so ``make_array_from_process_local_data`` assembles a
    multi-device-per-process shard — the actual per-host TPU assembly path.

    Shared by tests/test_multiprocess.py and __graft_entry__.dryrun_multiprocess.
    Kills every still-running worker on any failure so a crashed rank never
    leaves an orphan blocked in the rendezvous.
    """
    import socket
    import subprocess

    worker = os.path.abspath(__file__)
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    procs = []
    try:
        for rank in range(n_procs):
            env = dict(
                os.environ, MASTER_ADDR="localhost", MASTER_PORT=str(port),
                WORLD_SIZE=str(n_procs), RANK=str(rank),
                DEVICES_PER_PROC=str(devices_per_proc),
                # Concurrent workers cannot share a chip: held to the CPU
                # from their first import.
                JAX_PLATFORMS="cpu",
            )
            procs.append(subprocess.Popen(
                [sys.executable, worker], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            ))
        results = {}
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            assert p.returncode == 0, f"worker failed:\nstdout={out}\nstderr={err}"
            line = [ln for ln in out.splitlines() if ln.startswith("{")][-1]
            r = json.loads(line)
            results[r["rank"]] = r
        return [results[r] for r in range(n_procs)]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def main():
    # Worker-process-only config: must NOT run at module import, because the
    # test session imports this module for launch_workers and a 1-device CPU
    # config would clobber the 8-device test mesh.
    import jax

    n_local = int(os.environ.get("DEVICES_PER_PROC", "1"))
    jax.config.update("jax_platforms", "cpu")
    from pytorch_distributed_training_tpu.compat import set_cpu_device_count

    set_cpu_device_count(n_local)

    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax import linen as nn

    from pytorch_distributed_training_tpu import comm
    from pytorch_distributed_training_tpu.data import (
        DataLoader, DataLoaderConfig, SyntheticImages,
    )
    from pytorch_distributed_training_tpu.parallel.sharding import (
        DDP_RULES, shard_batch,
    )
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_train_step,
    )

    comm.initialize()  # env rendezvous (MASTER_ADDR/PORT, WORLD_SIZE, RANK)
    assert comm.process_count() == 2, comm.process_count()
    rank = comm.process_index()
    assert jax.local_device_count() == n_local, jax.local_device_count()

    mesh = comm.make_mesh(comm.MeshConfig(data=-1))
    assert mesh.shape["data"] == 2 * n_local, dict(mesh.shape)

    class TinyNet(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = True):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(32)(x))
            return nn.Dense(10)(x)

    ds = SyntheticImages(n=64, image_size=8, num_classes=10)
    loader = DataLoader(
        ds,
        DataLoaderConfig(batch_size=8, num_workers=0, seed=0),
        shard_index=rank,
        num_shards=comm.process_count(),
    )

    model = TinyNet()
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)), optax.adam(1e-2),
        mesh=mesh, rules=DDP_RULES, init_kwargs={"train": False},
    )
    step_fn = make_train_step(kind="image_classifier")

    losses = []
    with mesh:
        for i, local_batch in enumerate(loader):
            # Per-process local slice must be batch/2.
            assert local_batch["image"].shape[0] == 4, local_batch["image"].shape
            global_batch = shard_batch(local_batch, mesh)
            # Global assembly: full batch size across processes.
            assert global_batch["image"].shape[0] == 8, global_batch["image"].shape
            state, metrics = step_fn(state, global_batch)
            losses.append(float(metrics["loss"]))
            if i == 1:
                break

    # Cross-process barrier (exercises comm.collectives.barrier).
    from pytorch_distributed_training_tpu.comm.collectives import barrier

    barrier("mp_test_done")

    checksum = float(
        sum(jnp.sum(jnp.abs(p)).astype(jnp.float64) for p in jax.tree.leaves(state.params))
    )
    print(json.dumps({
        "rank": rank,
        "world": comm.process_count(),
        "losses": losses,
        "checksum": round(checksum, 6),
    }), flush=True)


if __name__ == "__main__":
    main()
