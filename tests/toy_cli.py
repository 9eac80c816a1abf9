"""The catalog models' toy CLI run, as their ``test_cli_trains_the_toy_size``
tests make it: ``cli.main`` in a child process on one CPU device."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def train(model, overrides, *flags):
    """``cli.main --use-cpu --model <model>`` on synthetic tokens of 32 at
    ``--model-overrides`` ``overrides``: batches of 4 in two accumulation
    steps, three steps at a rate of 1e-3, and ``flags`` after those.  The
    finished child's ``CompletedProcess``, its output captured."""
    return subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tpu.cli.main", "--use-cpu", "--model", model,
         "--dataset", "synthetic-tokens", "--seq-len", "32", "--model-overrides", overrides,
         "--batch-size", "4", "--accum-steps", "2", "--num-workers", "0", "--steps-per-epoch", "3",
         "--learning-rate", "1e-3", *flags],
        capture_output=True, text=True, timeout=600,
        # one CPU device: the suite's 8-device XLA_FLAGS would want a batch of 8
        env={k: v for k, v in {**os.environ, "JAX_PLATFORMS": "cpu"}.items() if k != "XLA_FLAGS"},
        cwd=ROOT,
    )
