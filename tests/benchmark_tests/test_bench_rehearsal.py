"""Each kind end to end at a toy size on the CPU, through the test-only entry
(which prints no device metric), and the measuring entry's refusal to run
without a chip.  Subprocesses: each sizes its own CPU backend."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(*argv, **env):
    full = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    full.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-m", *argv], cwd=ROOT, env=full,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("workload, devices", [
    ("tiny-gpt2.train", 1),
    ("tiny-vit.train", 1),
    ("tiny-gpt2.train.dp4", 4),       # a four-chip train cell is a workload file, no new code
    ("tiny-gpt2.serve.steady", 1),
    ("tiny-gpt2.serve.saturated", 1),
])
def test_kind_rehearsal(workload, devices):
    out = run("benchmark.rehearse", "--workload", workload, "--seconds", "1",
              "--seed", "3000000019", "--cpu-devices", str(devices))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, out.stdout[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0 and line["devices"] == devices
    assert "metrics" not in line and "device" not in line       # counts only
    for word in ("tokens/s", "images/s", " ms", "mfu"):
        assert word not in out.stdout, f"the rehearsal printed a device metric ({word!r})"


def test_measuring_entry_refuses_to_run_without_a_chip():
    out = run("benchmark.run", "--workload", "gpt2-124m.train.1chip", "--seed", "1",
              "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not any(l.lstrip().startswith("{") for l in out.stdout.splitlines())


def test_measuring_entry_refuses_an_unknown_cell():
    out = run("benchmark.run", "--workload", "no.such.cell", "--seed", "1",
              "--seconds", "1", "--trace", "0")
    assert out.returncode != 0 and out.stdout.strip() == ""
