"""Distributed convergence: the real CLI ``--distributed`` path must
reproduce the single-process loss trajectory (VERDICT r4 #7).

The 2-process tests prove step-level parity (identical losses over 2
steps); this proves the TRAINING path: two OS processes rendezvous
through the torchrun env contract (the reference's launch shape,
/root/reference/src/main.py:35-42), shard the shapes DataLoader per
process, assemble global batches with
``make_array_from_process_local_data``, and train a real recipe for
several epochs through ``python -m pytorch_distributed_training_tpu.cli.main
--distributed`` — then the per-epoch train losses and held-out accuracy
are compared against the identical single-process run.

Writes convergence/distributed.jsonl (rank 0's metrics stream from the
distributed run) and prints a JSON summary; --save merges a
``distributed`` entry into CONVERGENCE.json.

Usage: python tools/distributed_convergence.py [--epochs 3] [--save]
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _cli_args(metrics_path: str, epochs: int, distributed: bool):
    args = [
        sys.executable, "-m", "pytorch_distributed_training_tpu.cli.main",
        "--use-cpu", "--model", "resnet18", "--dataset", "shapes",
        "--model-overrides", "small_stem=true",
        "--batch-size", "64", "--epochs", str(epochs),
        "--steps-per-epoch", "25", "--eval", "--eval-steps", "4",
        "--learning-rate", "1e-3", "--optimizer", "adamw",
        "--weight-decay", "1e-4",
        "--lr-schedule", "constant", "--seed", "0",
        "--metrics-jsonl", metrics_path,
    ]
    if distributed:
        args.append("--distributed")
    return args


def _parse_metrics(path: str):
    rows = [json.loads(ln) for ln in open(path) if ln.strip()]
    train = [r for r in rows if "loss" in r]
    evals = [r for r in rows if "eval_accuracy" in r]
    return (
        [r["loss"] for r in train],
        [r["eval_accuracy"] for r in evals],
    )


def run_single(epochs: int) -> tuple[list, list, str]:
    path = os.path.join(tempfile.mkdtemp(), "single.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run(
        _cli_args(path, epochs, distributed=False),
        check=True, cwd=REPO, env=env, capture_output=True, timeout=3000,
    )
    losses, accs = _parse_metrics(path)
    return losses, accs, path


def run_distributed(epochs: int, n_procs: int = 2) -> tuple[list, list, str]:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    tmp = tempfile.mkdtemp()
    path = os.path.join(tmp, "rank0.jsonl")
    procs = []
    try:
        for rank in range(n_procs):
            env = dict(
                os.environ, MASTER_ADDR="localhost", MASTER_PORT=str(port),
                WORLD_SIZE=str(n_procs), RANK=str(rank),
                # N children at once cannot share one chip: they run
                # --use-cpu, and the environment holds them there from
                # the first import.
                JAX_PLATFORMS="cpu",
            )
            # Rank 0's logger owns the committed stream (rank-0 JSONL
            # contract, utils/metrics.py); other ranks write to a scratch
            # path that is simply ignored.
            mpath = path if rank == 0 else os.path.join(tmp, f"r{rank}.jsonl")
            procs.append(subprocess.Popen(
                _cli_args(mpath, epochs, distributed=True),
                cwd=REPO, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            ))
        for p in procs:
            out, err = p.communicate(timeout=3000)
            if p.returncode != 0:
                raise RuntimeError(
                    f"distributed worker failed:\nstdout={out[-2000:]}\n"
                    f"stderr={err[-2000:]}"
                )
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    losses, accs = _parse_metrics(path)
    return losses, accs, path


def main():
    epochs = 3
    if "--epochs" in sys.argv[1:]:
        epochs = int(sys.argv[sys.argv.index("--epochs") + 1])

    s_losses, s_accs, _ = run_single(epochs)
    d_losses, d_accs, d_path = run_distributed(epochs)

    assert len(s_losses) == len(d_losses) == epochs, (s_losses, d_losses)
    rel = [
        abs(a - b) / max(abs(b), 1e-9) for a, b in zip(d_losses, s_losses)
    ]
    out = {
        "metric": "distributed_convergence",
        "recipe": (
            "resnet18(small_stem) / shapes, adamw 1e-3, batch 64 global, "
            f"25 steps/epoch x {epochs} epochs, eval on 4x64 held-out "
            "batches; 2 OS processes, torchrun env rendezvous, per-process "
            "loader shards, CPU Gloo collectives — the real CLI "
            "--distributed path end to end"
        ),
        "single_process_losses": [round(x, 6) for x in s_losses],
        "distributed_losses": [round(x, 6) for x in d_losses],
        "per_epoch_rel_loss_diff": [round(x, 6) for x in rel],
        "single_process_eval_acc": [round(x, 4) for x in s_accs],
        "distributed_eval_acc": [round(x, 4) for x in d_accs],
        "trains": d_losses[-1] < d_losses[0],
        "eval_note": (
            "train losses are the like-for-like comparison (identical "
            "global batches up to within-batch order); eval accuracy is "
            "looser by construction — each process evaluates its own "
            "loader shard, so rank 0's --eval-steps 4 window covers a "
            "DIFFERENT 256-sample subset than the single-process run, "
            "and 256-sample accuracy at ~0.3 carries ~±0.06 sampling "
            "std — hence the 0.15 band"
        ),
    }
    print(json.dumps(out))

    ok = (
        out["trains"]
        and max(rel) < 0.05
        and abs(d_accs[-1] - s_accs[-1]) < 0.15
    )
    out["reproduces_single_process"] = ok
    if not ok:
        raise SystemExit(f"trajectory mismatch: {out}")

    if "--save" in sys.argv[1:]:
        os.makedirs(os.path.join(REPO, "convergence"), exist_ok=True)
        dst = os.path.join(REPO, "convergence", "distributed.jsonl")
        with open(d_path) as f, open(dst, "w") as g:
            g.write(f.read())
        conv_path = os.path.join(REPO, "CONVERGENCE.json")
        conv = {}
        if os.path.exists(conv_path):  # else tools/convergence_report.py
            with open(conv_path) as f:
                conv = json.load(f)
        conv["distributed"] = out
        with open(conv_path, "w") as f:
            json.dump(conv, f, indent=1)
        print(f"saved {dst} + CONVERGENCE.json entry")


if __name__ == "__main__":
    main()
