"""What the chip bring-up added, as far as a CPU can check it: where the
compile cache lives, the CLI refusing to run on a CPU it was not asked to
use, a serving restore that fails instead of serving other weights, and
``chip_smoke.py`` — that it fails without a chip, keeps its own process
off JAX, and that each of its checks reads a real run's output the way it
claims to."""

import copy
import importlib.util
import math
import os
import shutil
import subprocess
import sys

import jax
import pytest
from click.testing import CliRunner

from pytorch_distributed_training_tpu.cli.main import main
from pytorch_distributed_training_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "num_layers=2,hidden_dim=64,num_heads=2,vocab_size=512,max_seq_len=64"
STEPS, REQUESTS, VOCAB = 4, 6, 512


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---- compile cache ----------------------------------------------------


def test_cache_placed_from_outside_is_left_to_jax(monkeypatch):
    calls = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    monkeypatch.setattr(
        jax.config, "update", lambda *a, **k: calls.append(a)
    )
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert calls == []


def test_default_cache_is_one_path_in_the_checkout(tmp_path):
    """Two processes started from two working directories agree on
    ``<checkout>/.jax_cache``."""
    code = (
        "import jax\n"
        "from pytorch_distributed_training_tpu.utils.compile_cache import "
        "enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    seen = []
    for cwd in (str(tmp_path), REPO):
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, env=env, timeout=120,
            capture_output=True, text=True, check=True,
        ).stdout.split()
        seen.append(out)
    want = os.path.join(REPO, ".jax_cache")
    assert seen == [[want, want], [want, want]]


# ---- the CLI does not hide the device ---------------------------------


def test_cli_without_use_cpu_is_a_usage_error_on_a_cpu_backend():
    result = CliRunner().invoke(main, ["--synthetic-data"])
    assert result.exit_code == 2
    assert "--use-cpu" in result.output
    assert "training started" not in result.output


def test_supervised_child_argv_roundtrips_every_option_kind():
    """--elastic re-executes the CLI from its PARSED options: plain flags,
    --x/--no-x toggles, a flag whose name differs from its parameter
    (--eval) and valued options must all survive the trip, and an unset
    flag must vanish rather than become a stray ``False`` argument."""
    from pytorch_distributed_training_tpu.cli.main import _opts_to_argv

    for argv in ([], [
        "--use-cpu", "--synthetic-data", "--zero1", "--remat",
        "--device-cache", "--eval", "--no-serve-failover",
        "--batch-size", "16", "--checkpoint-dir", "/x",
        "--inject-faults", "crash@3",
    ]):
        want = main.make_context("main", list(argv)).params
        again = main.make_context("main", _opts_to_argv(want)).params
        assert again == want


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """One tiny train -> checkpoint -> paged serve through the CLI, with
    stdout and event logs kept: the captured run the smoke's checks read."""
    root = tmp_path_factory.mktemp("tiny_run")
    ckpt = str(root / "ckpt")
    common = ["--use-cpu", "--model", "gpt2", "--model-overrides", TINY,
              "--seq-len", "32"]
    train = CliRunner().invoke(main, common + [
        "--dataset", "synthetic-tokens", "--batch-size", "8",
        "--num-workers", "0", "--steps-per-epoch", str(STEPS),
        "--checkpoint-dir", ckpt, "--metrics-dir", str(root / "m_train"),
    ], catch_exceptions=False)
    assert train.exit_code == 0, train.output
    serve = CliRunner().invoke(main, common + [
        "--serve", "--serve-paged", "--checkpoint-dir", ckpt,
        "--serve-slots", "2", "--serve-requests", str(REQUESTS),
        "--serve-max-new", "8", "--serve-rate", "10",
        "--metrics-dir", str(root / "m_serve"),
    ], catch_exceptions=False)
    assert serve.exit_code == 0, serve.output
    smoke = _chip_smoke()
    return {
        "ckpt": ckpt, "common": common,
        "train_out": train.output, "serve_out": serve.output,
        "train_events": smoke.read_events(str(root / "m_train")),
        "serve_events": smoke.read_events(str(root / "m_serve")),
    }


def test_serve_fails_on_a_checkpoint_dir_with_nothing_restorable(
    tiny_run, tmp_path
):
    serve = tiny_run["common"] + ["--serve", "--serve-requests", "2"]
    empty = tmp_path / "empty"
    empty.mkdir()
    result = CliRunner().invoke(
        main, serve + ["--checkpoint-dir", str(empty)]
    )
    assert result.exit_code != 0
    assert "no committed checkpoint" in result.output
    assert "serving started" not in result.output

    corrupt = tmp_path / "corrupt"
    shutil.copytree(tiny_run["ckpt"], corrupt)
    for dirpath, _, files in os.walk(corrupt):
        if os.path.basename(dirpath) == "d":  # tensorstore data files
            for name in files:
                with open(os.path.join(dirpath, name), "wb") as f:
                    f.write(b"\0" * 64)
    result = CliRunner().invoke(
        main, serve + ["--checkpoint-dir", str(corrupt)]
    )
    assert result.exit_code != 0
    assert isinstance(result.exception, RuntimeError), result.output
    assert "could be restored" in str(result.exception)
    assert "serving started" not in result.output


def test_serving_restore_does_not_need_the_saving_topology(
    devices8, tmp_path
):
    """A save made by a multi-device mesh (jax.Arrays with NamedShardings —
    what a chip writes; the CPU staging path writes numpy and hides this)
    is read by a ONE-device process as host numpy arrays.  On the chip the
    first four-chip save came back on the saving mesh and the one-device
    serving programs refused it."""
    import jax.numpy as jnp
    import numpy as np
    import orbax.checkpoint as ocp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager
    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(data=-1), devices=devices8)
    put = lambda x, *spec: jax.device_put(  # noqa: E731
        x, NamedSharding(mesh, P(*spec))
    )
    mgr = CheckpointManager(str(tmp_path))
    mgr._mgr.save(3, args=ocp.args.StandardSave({
        "params": {"w": put(jnp.arange(32.0).reshape(8, 4), "data"),
                   "b": put(jnp.ones(3))},
        "opt_state": {"mu": put(jnp.zeros((8, 4)), "data")},
        "step": put(jnp.array(3)), "batch_stats": {},
    }))
    mgr.close()
    code = (
        "import jax, numpy as np\n"
        "from pytorch_distributed_training_tpu.checkpoint import "
        "CheckpointManager\n"
        f"p = CheckpointManager({str(tmp_path)!r}).restore_params()\n"
        "assert jax.device_count() == 1\n"
        "assert all(type(x) is np.ndarray for x in p.values()), p\n"
        "print('W', p['w'].ravel().tolist() == list(range(32)), "
        "p['b'].tolist())\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, timeout=300,
        env={**env, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
        capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "W True [1.0, 1.0, 1.0]" in out.stdout


# ---- chip_smoke.py ----------------------------------------------------


def test_chip_smoke_fails_on_cpu_and_never_imports_jax():
    code = (
        "import runpy, sys\n"
        "try:\n"
        "    runpy.run_path('chip_smoke.py', run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    rc = e.code\n"
        "print('PARENT_IMPORTED_JAX', 'jax' in sys.modules)\n"
        "sys.exit(rc)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True,
    )
    assert proc.returncode not in (0, None)
    assert "PARENT_IMPORTED_JAX False" in proc.stdout
    assert '"ok"' not in proc.stdout
    assert "chip_smoke: FAILED: train:" in proc.stderr
    assert "--use-cpu" in proc.stderr  # the child's usage error is shown


def _held(facts):
    return {name for name, (held, _) in facts.items() if held}


def test_smoke_train_checks_read_a_real_run(tiny_run):
    smoke = _chip_smoke()
    out, events = tiny_run["train_out"], tiny_run["train_events"]
    facts = smoke.train_facts(0, out, events, steps=STEPS, vocab=VOCAB)
    # A CPU run holds everything but the four facts only a chip can.
    assert set(facts) - _held(facts) == {
        "platform_tpu", "device_kind_has_peak", "peak_flops_v5e",
        "train_step_mosaic",
    }
    assert smoke.device_of(out) == {
        "platform": "cpu", "kind": "cpu", "count": jax.device_count()
    }
    losses = smoke.step_losses(events)
    assert len(losses) == STEPS
    assert abs(losses[0] - math.log(VOCAB)) < 0.05 * math.log(VOCAB)

    # Each check fails on the evidence it names.
    def facts_with(mutate, code=0):
        evs = copy.deepcopy(events)
        mutate(evs)
        return smoke.train_facts(code, out, evs, steps=STEPS, vocab=VOCAB)

    def nan_loss(evs):
        rec = [e for e in evs if e.get("record") == "step_losses"][-1]
        rec["losses"][-1] = float("nan")

    def cost_error(evs):
        next(e for e in evs if e["kind"] == "compiled_cost")["error"] = "x"

    def like_a_chip(evs):
        cost = next(e for e in evs if e["kind"] == "compiled_cost")
        cost.update(peak_flops=197e12, mosaic_custom_calls=12)

    assert not facts_with(nan_loss)["every_step_loss_finite"][0]
    assert not facts_with(cost_error)["compiled_cost_clean"][0]
    assert not facts_with(lambda evs: None, code=1)["train_exit_0"][0]
    assert not smoke.train_facts(
        0, out, events, steps=STEPS + 1, vocab=VOCAB
    )["every_step_loss_finite"][0]
    assert not smoke.train_facts(
        0, out, events, steps=STEPS, vocab=VOCAB * 100
    )["step1_loss_near_ln_vocab"][0]
    chip_events = copy.deepcopy(events)
    like_a_chip(chip_events)
    chip = smoke.train_facts(
        0, out.replace("platform=cpu | device_kind=cpu",
                       "platform=tpu | device_kind=TPU v5 lite"),
        chip_events, steps=STEPS, vocab=VOCAB,
    )
    assert smoke.failed(chip) == []


def test_smoke_serve_checks_read_a_real_run(tiny_run):
    smoke = _chip_smoke()
    out, events = tiny_run["serve_out"], tiny_run["serve_events"]
    facts = smoke.serve_facts(0, out, events, requests=REQUESTS)
    assert set(facts) - _held(facts) == {"prefill_mosaic", "decode_mosaic"}
    assert not smoke.serve_facts(
        0, out, events, requests=REQUESTS + 1
    )["all_requests_completed"][0]
    assert not smoke.serve_facts(
        0, out + "\nwarning: serving FRESH-INIT weights", events,
        requests=REQUESTS,
    )["params_restored"][0]
    evs = copy.deepcopy(events)
    summary = [e for e in evs if e["kind"] == "summary"][-1]
    summary["gauges"]["mosaic_custom_calls[program=prefill]"] = 12.0
    summary["gauges"]["mosaic_custom_calls[program=decode]"] = 12.0
    assert smoke.failed(
        smoke.serve_facts(0, out, evs, requests=REQUESTS)
    ) == []
    summary["serve"]["failed"] = 1
    assert not smoke.serve_facts(
        0, out, evs, requests=REQUESTS
    )["all_requests_completed"][0]


def test_smoke_multi_chip_checks_read_a_real_run(tiny_run):
    """The tiny run used every simulated device: the placement facts hold,
    the memory fact cannot (the CPU keeps no memory statistics)."""
    smoke = _chip_smoke()
    out, events = tiny_run["train_out"], tiny_run["train_events"]
    n = jax.device_count()
    loss = smoke.step_losses(events)[0]
    facts = smoke.four_chip_facts(out, events, count=n, ref_loss=loss)
    assert set(facts) - _held(facts) == {"memory_in_use_on_every_device"}
    evs = copy.deepcopy(events)
    for dev in next(
        e for e in evs if e.get("record") == "device_memory"
    )["devices"]:
        dev["bytes_in_use"] = 2**30
    assert smoke.failed(
        smoke.four_chip_facts(out, evs, count=n, ref_loss=loss)
    ) == []
    assert not smoke.four_chip_facts(
        out, evs, count=n + 1, ref_loss=loss
    )["mesh_data_axis"][0]
    assert not smoke.four_chip_facts(
        out, evs, count=n, ref_loss=loss * 1.02
    )["step1_loss_equals_one_chip"][0]
    assert not smoke.four_chip_facts(
        out, evs, count=n, ref_loss=None
    )["step1_loss_equals_one_chip"][0]


def test_device_line_parses_a_kind_with_spaces():
    assert _chip_smoke().device_of(
        "process 0/1 | platform=tpu | device_kind=TPU v5 lite | devices=1"
    ) == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


# ---- a Pallas kernel under a multi-device mesh --------------------------


@pytest.mark.parametrize("mesh_kw, heads", [
    ({}, 4),                            # data=8
    ({"fsdp": 2, "tensor": 2}, 4),      # data=2 x fsdp=2, heads over tensor
    ({"tensor": 2}, 3),                 # heads do not divide: stay whole
])
def test_flash_runs_per_shard_under_a_mesh(devices8, mesh_kw, heads):
    """On a chip a Mosaic kernel cannot be partitioned by GSPMD (the first
    four-chip run died on exactly that), so under a multi-device mesh the
    flash kernel must sit inside a shard_map — and still differentiate to
    the single-device answer."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.ops.attention import (
        _xla_attention, flash_attention,
    )

    mesh = make_mesh(MeshConfig(data=-1, **mesh_kw), devices=devices8)
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(8, 128, heads, 8)), jnp.float32)
        for _ in range(3)
    )

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    want = jax.grad(
        loss(lambda q, k, v: _xla_attention(q, k, v, causal=True)),
        (0, 1, 2),
    )(q, k, v)
    placed = [
        jax.device_put(x, NamedSharding(mesh, P(("data", "fsdp"))))
        for x in (q, k, v)
    ]
    with mesh:
        assert "shard_map" in str(jax.make_jaxpr(flash)(*placed))
        got = jax.jit(jax.grad(loss(flash), (0, 1, 2)))(*placed)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
    # No mesh, or already inside a shard_map body: called as it is.
    assert "shard_map" not in str(jax.make_jaxpr(flash)(q, k, v))
    with mesh:
        nested = jax.make_jaxpr(jax.shard_map(
            flash, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False,
        ))(*placed)
    assert str(nested).count("shard_map") == 1
