"""Failure detection / elastic restart (SURVEY.md §5 "failure detection"
row — absent from the reference, whose story is three asserts at
/root/reference/src/main.py:36-38 and a hang on any rank crash)."""

import os
import sys
import textwrap
import time

import pytest

from pytorch_distributed_training_tpu.utils import (
    BackoffPolicy,
    Heartbeat,
    supervise,
)


def _script(tmp_path, body):
    path = tmp_path / "child.py"
    path.write_text(textwrap.dedent(body))
    return [sys.executable, str(path)]


def test_backoff_policy_growth_and_cap():
    """The ONE restart-delay schedule (utils/backoff.py), shared by the
    training supervisor and serving replica respawn: exact doubling from
    base, capped, jitter bounded and deterministic per seed."""
    exact = BackoffPolicy(base_s=1.0, max_s=8.0, jitter=0.0)
    assert [exact.delay(n) for n in range(1, 7)] == [
        1.0, 2.0, 4.0, 8.0, 8.0, 8.0,  # 16/32 capped at 8
    ]
    assert BackoffPolicy(base_s=0.0, jitter=0.5).delay(3) == 0.0
    jittered = BackoffPolicy(base_s=1.0, max_s=8.0, jitter=0.5)
    for n, nominal in ((1, 1.0), (2, 2.0), (3, 4.0), (4, 8.0), (5, 8.0)):
        d = jittered.delay(n)
        assert 0.5 * nominal <= d <= 1.5 * nominal, (n, d)
    # Deterministic per seed: the sequence replays exactly.
    a = BackoffPolicy(base_s=1.0, jitter=0.5, seed=7)
    b = BackoffPolicy(base_s=1.0, jitter=0.5, seed=7)
    assert [a.delay(n) for n in (1, 2, 3)] == [
        b.delay(n) for n in (1, 2, 3)
    ]
    with pytest.raises(ValueError):
        BackoffPolicy(base_s=-1.0)
    with pytest.raises(ValueError):
        BackoffPolicy(jitter=1.0)
    with pytest.raises(ValueError):
        BackoffPolicy().delay(0)


def test_heartbeat_staleness(tmp_path):
    hb = Heartbeat(str(tmp_path / "hb"), timeout_s=0.2)
    assert hb.age_s() is None  # no file yet
    hb.beat()
    assert not hb.is_stale()
    time.sleep(0.3)
    assert hb.is_stale()


def test_supervise_restarts_until_success(tmp_path):
    marker = tmp_path / "attempts"
    argv = _script(tmp_path, f"""
        import os, sys
        path = {str(marker)!r}
        n = int(open(path).read()) if os.path.exists(path) else 0
        open(path, "w").write(str(n + 1))
        # Crash the first two attempts; the relaunches must carry --resume.
        if n < 2:
            sys.exit(3)
        assert "--resume" in sys.argv, sys.argv
        sys.exit(0)
    """)
    result = supervise(
        argv, max_restarts=5, backoff_base_s=0.0, _print=lambda *a: None
    )
    assert result.exit_code == 0
    assert result.restarts == 2
    assert marker.read_text() == "3"


def test_supervise_gives_up(tmp_path):
    argv = _script(tmp_path, "import sys; sys.exit(7)")
    result = supervise(
        argv, max_restarts=2, backoff_base_s=0.0, _print=lambda *a: None
    )
    assert result.exit_code == 7
    assert result.restarts == 2


def test_supervise_backoff_grows_exponentially_with_jitter(tmp_path):
    """Crash relaunches wait base*2^(n-1) (± jitter), capped — a
    crash-looping child cannot burn the restart budget in seconds."""
    argv = _script(tmp_path, "import sys; sys.exit(7)")
    sleeps = []
    result = supervise(
        argv, max_restarts=3, backoff_base_s=1.0, backoff_max_s=3.0,
        backoff_jitter=0.5, _print=lambda *a: None,
        _sleep=lambda s: sleeps.append(s),
    )
    assert result.exit_code == 7
    assert len(sleeps) == 3
    for delay, nominal in zip(sleeps, (1.0, 2.0, 3.0)):  # 4.0 capped at 3.0
        assert 0.5 * nominal <= delay <= 1.5 * nominal, (delay, nominal)


def test_supervise_preemption_exit_not_charged_against_restarts(tmp_path):
    """Exit 75 (SIGTERM -> step checkpoint -> PREEMPTED_EXIT_CODE) is
    relaunched with --resume, immediately, without touching restarts."""
    from pytorch_distributed_training_tpu.utils.supervisor import (
        PREEMPTED_EXIT_CODE,
    )

    marker = tmp_path / "attempts"
    argv = _script(tmp_path, f"""
        import os, sys
        path = {str(marker)!r}
        n = int(open(path).read()) if os.path.exists(path) else 0
        open(path, "w").write(str(n + 1))
        if n == 0:
            sys.exit({PREEMPTED_EXIT_CODE})  # preempted after checkpointing
        assert "--resume" in sys.argv, sys.argv
        sys.exit(0)
    """)
    sleeps = []
    result = supervise(
        argv, max_restarts=0, _print=lambda *a: None,
        _sleep=lambda s: sleeps.append(s),
    )
    assert result.exit_code == 0
    assert result.restarts == 0
    assert result.preemptions == 1
    assert sleeps == []  # no backoff for preemptions
    assert marker.read_text() == "2"


def test_supervise_interleaved_preemptions_and_crashes(tmp_path):
    """Mixed sequence: crash, preempt, crash, preempt, success.  The
    preemptions relaunch free (no backoff, restarts untouched) while the
    crash backoff keeps growing across the interleaving — the schedule
    is a function of the CRASH count, not the attempt count."""
    from pytorch_distributed_training_tpu.utils.supervisor import (
        PREEMPTED_EXIT_CODE,
    )

    marker = tmp_path / "attempts"
    argv = _script(tmp_path, f"""
        import os, sys
        path = {str(marker)!r}
        n = int(open(path).read()) if os.path.exists(path) else 0
        open(path, "w").write(str(n + 1))
        codes = [3, {PREEMPTED_EXIT_CODE}, 3, {PREEMPTED_EXIT_CODE}]
        if n < len(codes):
            sys.exit(codes[n])
        assert "--resume" in sys.argv, sys.argv
        sys.exit(0)
    """)
    sleeps = []
    result = supervise(
        argv, max_restarts=3, max_preemptions=3, backoff_base_s=1.0,
        backoff_jitter=0.0, _print=lambda *a: None,
        _sleep=lambda s: sleeps.append(s),
    )
    assert result.exit_code == 0
    assert result.restarts == 2       # only the exit-3 crashes
    assert result.preemptions == 2    # exit-75s ride free
    assert marker.read_text() == "5"
    # Backoff slept only for the crashes, growing 1.0 -> 2.0 straight
    # through the interleaved preemptions.
    assert sleeps == [1.0, 2.0]


def test_supervise_preemption_loop_capped(tmp_path):
    """A child that exits 75 forever is a bug, not a preemption storm:
    max_preemptions stops the free-relaunch loop."""
    from pytorch_distributed_training_tpu.utils.supervisor import (
        PREEMPTED_EXIT_CODE,
    )

    argv = _script(tmp_path, f"import sys; sys.exit({PREEMPTED_EXIT_CODE})")
    result = supervise(
        argv, max_restarts=0, max_preemptions=3, backoff_base_s=0.0,
        _print=lambda *a: None,
    )
    assert result.exit_code == PREEMPTED_EXIT_CODE
    assert result.preemptions == 3


def test_supervise_kills_hung_child(tmp_path):
    marker = tmp_path / "attempts"
    hb = tmp_path / "hb"
    argv = _script(tmp_path, f"""
        import os, sys, time
        path = {str(marker)!r}
        n = int(open(path).read()) if os.path.exists(path) else 0
        open(path, "w").write(str(n + 1))
        if n == 0:
            time.sleep(600)  # hang without beating
        sys.exit(0)
    """)
    result = supervise(
        argv, max_restarts=2, heartbeat_path=str(hb),
        heartbeat_timeout_s=2.0, poll_s=0.2, backoff_base_s=0.0,
        _print=lambda *a: None,
    )
    assert result.exit_code == 0
    assert result.hung_kills == 1
    assert result.restarts == 1


def test_supervisor_exports_heartbeat_env(tmp_path):
    hb = tmp_path / "hb"
    argv = _script(tmp_path, """
        import os, sys
        sys.exit(0 if os.environ.get("PDT_HEARTBEAT_FILE") else 1)
    """)
    result = supervise(
        argv, max_restarts=0, heartbeat_path=str(hb),
        heartbeat_timeout_s=60.0, _print=lambda *a: None,
    )
    assert result.exit_code == 0


@pytest.mark.slow
def test_cli_elastic_recovers_from_crash(tmp_path):
    """End-to-end: a training run that crashes mid-way is relaunched with
    --resume and completes the remaining epochs from the checkpoint."""
    import subprocess

    ckpt = tmp_path / "ckpt"
    crash_marker = tmp_path / "crashed"
    # Crash injection: run a tiny driver that calls the CLI run() and exits hard after epoch 0 on
    # the first attempt.
    driver = tmp_path / "driver.py"
    driver.write_text(textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {str(os.getcwd())!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        crash = not os.path.exists({str(crash_marker)!r})
        if crash:
            open({str(crash_marker)!r}, "w").write("x")
            # Crash after the first checkpoint exists: run one epoch.
            epochs = 1
        from pytorch_distributed_training_tpu.cli.main import run
        run(
            data_dir=".", distributed=False, use_cpu=True, batch_size=8,
            num_workers=0, learning_rate=1e-3, weight_decay=0.0,
            model="resnet18", dataset="synthetic-images", synthetic_data=True,
            epochs=1 if crash else 3, precision="f32", accum_steps=1, fsdp=1,
            tensor_parallel=1, seed=0, checkpoint_dir={str(ckpt)!r},
            resume="--resume" in sys.argv, steps_per_epoch=2, image_size=32,
            seq_len=32, profile_dir=None,
        )
        if crash:
            os._exit(5)  # simulate a hard crash after epoch 0 checkpointed
    """))
    result = supervise(
        [sys.executable, str(driver)], max_restarts=2,
        _print=lambda *a: None,
    )
    assert result.exit_code == 0
    assert result.restarts == 1
