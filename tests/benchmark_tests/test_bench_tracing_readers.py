"""The readers of what the program itself records (PR 26): its compile log,
its kernel names, its spans on the profiler's clock, and the collective
opcodes — each on a hand-made table whose answers are worked by hand, on the
slice recorded from the four-chip cell's own traced run, and (the compile
log) on a real compile made here."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import tracered
from benchmark.harness import HERE, ROOT, load_json, load_peaks
from benchmark.readers import annotation_share, compile_events, kernel_roofline, op_share

MS = 1e6  # ns
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def layer_args(metric):
    return load_json("layers", metric + ".json")["args"]


# ---- the compile log ---------------------------------------------------


def event(what, t0, t1, phase=None, **attrs):
    return {"what": what, "fun_name": None, "seconds": t1 - t0, "t_end": t1,
            "phase": phase, **attrs}


LOG = [
    event("trace", 0, 4), event("trace", 1, 2),                      # an inner jit's trace, inside the outer's
    event("lower", 4, 5), event("backend_compile", 5, 9), event("cache_miss", 9, 9),
    event("backend_compile", 20, 21, "train/epoch", epoch=0), event("cache_hit", 21, 21, "train/epoch", epoch=0),
    event("cache_retrieval", 20.2, 20.7, "train/epoch", epoch=0),    # not a compile step of its own
    event("trace", 30, 31, "train/epoch", epoch=2), event("backend_compile", 31, 33, "train/epoch", epoch=2),
    event("cache_miss", 33, 33, "train/epoch", epoch=3),
]


@pytest.mark.parametrize("metric, expected", [
    ("startup.compile_s", 10.0),                 # 0..9 as a union (not 4 + 1 + 1 + 4) and 20..21
    ("startup.cache_misses", 1.0),               # the one at 33 fell into the window
    ("loop.compiles_in_window.train", 1.0),      # epoch 2's; epoch 0 is warm-up
])
def test_compile_event_readers_on_a_hand_made_log(metric, expected):
    assert compile_events.read({}, events=LOG, **layer_args(metric)) == pytest.approx(expected)


def test_compile_event_readers_on_a_real_compile():
    """The program's own log, read where the benchmark reads it: a function
    compiled inside a warm-up epoch is start-up, the same inside epoch 2 is
    a compile in the window, and a program that keeps no log reads as None."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tpu.utils import compile_cache

    compile_cache.enable_compile_cache()
    x = jnp.ones((8, 8))
    args = {m: layer_args(m) for m in ("startup.compile_s", "startup.cache_misses",
                                       "loop.compiles_in_window.train")}
    before = {m: compile_events.read({}, **a) for m, a in args.items()}
    with compile_cache.compile_phase("train/epoch", epoch=0):
        jax.jit(lambda a: (a * 3).sum())(x).block_until_ready()
    warm = {m: compile_events.read({}, **a) for m, a in args.items()}
    assert warm["startup.compile_s"] > before["startup.compile_s"]
    assert warm["loop.compiles_in_window.train"] == before["loop.compiles_in_window.train"]
    with compile_cache.compile_phase("train/epoch", epoch=2):
        jax.jit(lambda a: (a * 5).sum())(x).block_until_ready()
    late = {m: compile_events.read({}, **a) for m, a in args.items()}
    assert late["loop.compiles_in_window.train"] == warm["loop.compiles_in_window.train"] + 1
    assert late["startup.compile_s"] == warm["startup.compile_s"]


def test_a_program_without_a_compile_log_reads_as_nothing(monkeypatch):
    from pytorch_distributed_training_tpu.utils import compile_cache

    monkeypatch.delattr(compile_cache, "compile_events")      # the parent of PR 26
    assert compile_events.read({}, **layer_args("startup.compile_s")) is None


# ---- kernels by name, spans, collectives: hand-made tables --------------

FWD = "%flash_fwd.3 = (bf16[8,1024,768]{2,1,0}, f32[8,2,1024,6]{3,2,1,0}) custom-call(%a, %b, %c), custom_call_target=\"tpu_custom_call\""
BWD = "%flash_bwd.4 = (bf16[8,1024,768]{2,1,0}, bf16[8,1024,768]{2,1,0}, bf16[8,1024,768]{2,1,0}) custom-call(%a), custom_call_target=\"tpu_custom_call\""
DQ = "%flash_bwd_dq.1 = bf16[8,12,1024,64]{3,2,1,0} custom-call(%a), custom_call_target=\"tpu_custom_call\""
DKV = "%flash_bwd_dkv.1 = (bf16[8,12,1024,64]{3,2,1,0}, bf16[8,12,1024,64]{3,2,1,0}) custom-call(%a), custom_call_target=\"tpu_custom_call\""
OLD = "%attn.7 = (bf16[8,1024,768]{2,1,0}, f32[8,2,1024,6]{3,2,1,0}) custom-call(%a), custom_call_target=\"tpu_custom_call\""


def table(planes, host=()):
    return {"planes": [
        {"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Ops", "events": [[n, s * MS, d * MS] for n, s, d in ops]}]}
        for i, ops in enumerate(planes)
    ] + [{"name": "/host:CPU", "lines": [
        {"name": "main", "events": [[n, s * MS, d * MS] for n, s, d in host]}]}]}


def facts_of(events, annotations=("train",)):
    return {"trace": tracered.reduce(events, {"annotations": list(annotations)}),
            "peaks": PEAKS, "config": {"n_head": 12}}


def test_kernel_roofline_by_name():
    # least times at GPT-2's shapes (8 x 1024, 12 heads of 64, causal): forward
    # 2 x 6.44 GFLOP = 65.4 us, backward 5 x 6.44 GFLOP = 163.5 us (both compute bound)
    facts = facts_of(table([[(FWD, 0, 0.4), (BWD, 1, 0.8), (OLD, 2, 0.5)]]))
    fwd = kernel_roofline.read(facts, **layer_args("kernel.flash_fwd_roofline.train"))
    bwd = kernel_roofline.read(facts, **layer_args("kernel.flash_bwd_roofline.train"))
    assert fwd == pytest.approx(100 * 65.4 / 400, rel=2e-3)
    assert bwd == pytest.approx(100 * 163.5 / 800, rel=2e-3)
    # a split backward: both halves' time, the backward's least time once
    split = facts_of(table([[(DQ, 0, 0.3), (DKV, 1, 0.6)]]))
    assert kernel_roofline.read(split, **layer_args("kernel.flash_bwd_roofline.train")) == \
        pytest.approx(100 * 163.5 / 900, rel=2e-3)
    assert kernel_roofline.read(split, **layer_args("kernel.flash_fwd_roofline.train")) is None
    # kernels without names (the parent of PR 26): nothing to read, for either
    old = facts_of(table([[(OLD, 0, 0.4)]]))
    assert kernel_roofline.read(old, **layer_args("kernel.flash_fwd_roofline.train")) is None
    assert kernel_roofline.read(old, **layer_args("kernel.flash_bwd_roofline.train")) is None


def test_input_wait_share_and_exposed_collectives():
    ar = "%all-reduce.5 = f32[50257,768]{1,0} all-reduce(%g), channel_id=1"
    done = "%all-gather-done.2 = bf16[32,1024,768]{2,1,0} all-gather-done(%s)"
    fusion = "%fusion.9 = f32[8,1024]{1,0} fusion(%x), kind=kLoop, calls=%all-reduce-like"
    ops = [(fusion, 0, 6), (ar, 6, 3), (done, 9, 1), (fusion, 10, 10)]
    events = table([ops, ops], host=[("train", 0, 2), ("train/input_wait", 2, 1),
                                      ("train/input_wait", 12, 1), ("train/host_sync", 13, 7),
                                      ("serve/decode", 0, 20)])
    cell = ("train", "train/input_wait", "train/host_sync")
    facts = facts_of(events, cell)
    assert annotation_share.read(facts, **layer_args("input.wait_share.train")) == pytest.approx(10.0)   # 2 of 20 ms
    assert annotation_share.read(facts, annotation="serve/decode") is None       # not one of the cell's
    # 4 ms of collectives' own time in 20 busy ms a chip; the fusion's name does not count
    assert op_share.read(facts, **layer_args("comm.exposed_share.train")) == pytest.approx(20.0)
    # a cell whose file lists only "train" keeps no input-wait events
    assert annotation_share.read(facts_of(events), **layer_args("input.wait_share.train")) is None
    # a chip that waits while the host pulls its batch: the gap goes to the
    # innermost annotation over it, not to the step marker around both
    starved = table([[(fusion, 0, 2), (fusion, 3, 7)]],
                    host=[("train", 0, 10), ("train/input_wait", 1.8, 1.4)])
    r = tracered.reduce(starved, {"annotations": list(cell)})
    assert r["top_gaps"] == [["train/input_wait", pytest.approx(0.001)]]


# ---- the slice recorded from the four-chip cell's own traced run ---------

SLICE = os.path.join(HERE, "fixtures", "gpt2_dp4_slice.events.json.gz")


def slice_facts():
    events = tracered.read_events(SLICE)
    cell = load_json("workloads", "gpt2-124m.train.dp4.json")
    return events, {"trace": tracered.reduce(events, cell["trace"]),
                    "peaks": load_peaks("TPU v5 lite"), "config": {"n_head": 12}}


def test_recorded_dp4_slice_reduces_as_four_chips():
    """56 ms from the start of the capture of ``gpt2-124m.train.dp4`` on a
    v5e-4 host (PR 26): the gradient all-reduce and Adam that end one step,
    the next step's first forward pass and the start of its backward, and
    on the host's clock the three dispatches, the two batch pulls between
    them and the start of the loss fetch that closes the capture."""
    events, facts = slice_facts()
    r = facts["trace"]
    assert r["chips"] == 4 and r["window_s"] == pytest.approx(0.056)
    assert sum(r["op_self_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert sum(r["idle_by_annotation_s"].values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert [h[0] for h in sorted(r["host"], key=lambda h: h[1])] == [
        "train", "train/input_wait", "train", "train/input_wait", "train", "train/host_sync"]
    # every chip ends the step it was cut into at 15.04 ms and begins the next 7 us later
    modules = sorted(r["modules"], key=lambda m: m[1] + m[2])
    assert all(m[0].startswith("jit_train_step") for m in modules) and len(modules) == 8
    assert all(15.040e6 < m[1] + m[2] < 15.042e6 for m in modules[:4])
    # The chips did not record from one instant: two planes begin 4.2 and 12.2 ms
    # into the window, and that, not waiting, is the slice's (and the cell's) idle share.
    assert r["top_gaps"][0] == ["train", pytest.approx(0.012157001, rel=1e-6)]
    assert r["top_gaps"][1] == ["none", pytest.approx(0.004232590, rel=1e-6)]
    assert all(gap < 25e-6 for _, gap in r["top_gaps"][2:])
    # Between steps each chip pauses for 19 us, under the host's dispatch of a later
    # step; while the host pulls a batch (train/input_wait) no chip has a gap at all,
    # so no gap is put down to it — here or anywhere in the capture this was cut from.
    assert set(r["idle_by_annotation_s"]) == {"train", "none"}
    assert [name for name, _ in r["top_gaps"][2:6]] == ["train"] * 4


def test_new_readers_on_the_recorded_dp4_slice():
    from benchmark.readers import flash_roofline

    events, facts = slice_facts()
    calls = [c for c in facts["trace"]["custom_calls"] if c[0].endswith(" tpu_custom_call")]
    fwd = [s for n, s in calls if n.startswith("%flash_fwd.")]
    bwd = [s for n, s in calls if n.startswith("%flash_bwd.")]
    assert len(fwd) == 48 and len(bwd) == 28 and len(calls) == 76      # 12 layers x 4 chips; 7 layers back
    assert sum(fwd) / 48 == pytest.approx(406.4e-6, rel=1e-3)         # the one-chip cell's 406 us
    assert sum(bwd) / 28 == pytest.approx(725.5e-6, rel=1e-3)         # and its 725 us
    assert kernel_roofline.read(facts, **layer_args("kernel.flash_fwd_roofline.train")) == \
        pytest.approx(16.0959, rel=1e-4)
    assert kernel_roofline.read(facts, **layer_args("kernel.flash_bwd_roofline.train")) == \
        pytest.approx(22.5380, rel=1e-4)
    # the accepted reader, which tells the two apart by their shapes, lies between them
    both = flash_roofline.read(facts, **layer_args("kernel.flash_roofline.train"))
    assert both == pytest.approx(19.3824, rel=1e-4)
    # two pulls of 4.27 and 3.88 ms in a 56 ms window
    assert annotation_share.read(facts, **layer_args("input.wait_share.train")) == \
        pytest.approx(100 * (4.267469 + 3.880429) / 56.0, rel=1e-6)
    # the gradient all-reduce that ends the step (9.6 ms a chip; three chips recorded all of it)
    # and the loop's collective-permutes; gathers fused into compute do not show under an opcode
    comm = op_share.read(facts, **layer_args("comm.exposed_share.train"))
    assert comm == pytest.approx(4.6506, rel=1e-4)
    reduces = sum(s for n, s in facts["trace"]["op_self_s"].items() if n.endswith(" all-reduce"))
    assert 0.7 < reduces / (comm / 100 * facts["trace"]["busy_s"]) <= 1.0


# ---- the toy four-device cell with the program's annotations -----------


def test_rehearsal_of_the_annotated_four_device_cell():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.rehearse", "--workload", "tiny-gpt2.train.dp4.spans",
         "--seconds", "1", "--seed", "2147483659", "--cpu-devices", "4"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["devices"] == 4
    assert line["attempted"] > 0 and line["failed"] == 0
    cell = load_json("rehearsal", "tiny-gpt2.train.dp4.spans.json")
    real = load_json("workloads", "gpt2-124m.train.dp4.json")
    assert cell["trace"]["annotations"] == real["trace"]["annotations"] == \
        ["train", "train/input_wait", "train/host_sync"]
    assert real["chips"] == 4 and real["mesh"] == {"data": -1}
    assert real["step"] == {"samples": 64, "microbatches": 2, "seq_len": 1024}
