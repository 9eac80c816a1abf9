"""The trace reduction: on a hand-made event table whose answers are worked
by hand, and on the small recorded trace cut from a real run on the chip."""

import os

import pytest

from benchmark import tracered
from benchmark.harness import HERE

MS = 1e6  # ns


def table(ops, host=(), modules=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [[n, s * MS, d * MS, {}] for n, s, d in ops]},
            {"name": "XLA Modules", "events": [[n, s * MS, d * MS, {}] for n, s, d in modules]},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [[n, s * MS, d * MS, {}] for n, s, d in host]},
        ]},
    ]}


def test_union_and_self_times():
    assert tracered.union([[0, 2], [1, 3], [5, 6]]) == [[0, 3], [5, 6]]
    # a 10 ms loop holding two 3 ms bodies keeps 4 ms of its own
    got = dict(tracered.self_times([["while", 0, 10, {}], ["body", 1, 3, {}], ["body2", 5, 3, {}]]))
    assert got == {"while": 4, "body": 3, "body2": 3}


def test_busy_idle_sums_and_gap_attribution():
    events = table(
        ops=[("fusion.1", 0, 4), ("while.2", 10, 6), ("fusion.1", 11, 2), ("custom-call.3", 20, 5)],
        host=[("serve/decode", 0, 12), ("serve/prefill", 16, 9), ("other", 0, 30)],
        modules=[("jit_step(1)", 0, 4), ("jit_step(1)", 10, 6)],
    )
    r = tracered.reduce(events, {"annotations": ["serve/prefill", "serve/decode"]})
    assert r["window_s"] == pytest.approx(0.025)          # 0 .. 25 ms
    assert r["busy_s"] == pytest.approx(0.015)            # 4 + 6 + 5
    assert r["idle_share"] == pytest.approx(0.4)
    assert r["op_self_s"]["fusion.1"] == pytest.approx(0.006)   # 4 + 2 (inside the loop)
    assert r["op_self_s"]["while.2"] == pytest.approx(0.004)    # 6 - 2
    assert r["top_ops"][0][0] == "fusion.1"
    # gaps: 4..10 (under serve/decode), 16..20 (under serve/prefill)
    assert r["top_gaps"][:2] == [["serve/decode", pytest.approx(0.006)],
                                 ["serve/prefill", pytest.approx(0.004)]]
    assert r["idle_by_annotation_s"] == {"serve/decode": pytest.approx(0.006),
                                         "serve/prefill": pytest.approx(0.004)}
    assert [m[0] for m in r["modules"]] == ["jit_step(1)", "jit_step(1)"]


def test_a_gap_under_no_named_annotation_is_none_and_no_device_plane_is_an_error():
    r = tracered.reduce(table(ops=[("a", 0, 1), ("b", 3, 1)]), {"annotations": ["train"]})
    assert r["top_gaps"] == [["none", pytest.approx(0.002)]]
    with pytest.raises(ValueError):
        tracered.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})


def test_load_events_reads_a_live_capture(tmp_path):
    """``load_events`` on a capture made here: the CPU has no device plane,
    so this checks the reading, not the reduction."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("serve/decode"):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    events = tracered.load_events(str(tmp_path))
    assert any(e[0] == "serve/decode" for e in tracered.host_events(events))
    assert tracered.device_planes(events) == []


FIXTURES = os.path.join(HERE, "fixtures")


def brute_force_busy_ns(events, plane_name="/device:TPU:0"):
    """Busy time by painting a 1 ns-resolution timeline coarsely: sort the
    interval edges and count depth — another route than ``union``."""
    plane = next(p for p in events["planes"] if p["name"] == plane_name)
    ops = next(l for l in plane["lines"] if l["name"] == "XLA Ops")["events"]
    edges = sorted([(e[1], 1) for e in ops] + [(e[1] + e[2], -1) for e in ops],
                   key=lambda x: (x[0], -x[1]))
    busy, depth, last = 0.0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth, last = depth + d, t
    return busy


def test_recorded_train_slice():
    """40 ms of a GPT-2 124M train step on the v5e (PR 23): the end of one
    microbatch's backward pass, flash kernels included."""
    events = tracered.read_events(os.path.join(FIXTURES, "gpt2_train_slice.events.json.gz"))
    r = tracered.reduce(events, {"annotations": ["train"]})
    assert r["chips"] == 1
    assert r["busy_s"] * 1e9 == pytest.approx(brute_force_busy_ns(events), rel=1e-9)
    assert r["window_s"] == pytest.approx(0.039805334, rel=1e-6)
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    assert 0 < r["idle_share"] < 0.01                      # a train step keeps the chip busy
    # self times partition the busy union: every busy nanosecond belongs to one op
    assert sum(r["op_self_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert sum(r["idle_by_annotation_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    # the flash kernels are Mosaic custom calls named %attn.<n>, told apart by their results
    mosaic = [c for c in r["custom_calls"] if c[0].endswith(" tpu_custom_call")]
    backward = [c for c in mosaic if c[0].count("bf16[8,1024,768]") == 3]
    forward = [c for c in mosaic if "f32[8,2,1024,6]" in c[0]]
    assert len(mosaic) == len(backward) + len(forward) and backward and forward
    assert all(c[0].startswith("%attn.") for c in mosaic)
    assert 700e-6 < sum(c[1] for c in backward) / len(backward) < 750e-6    # 725 us each
    assert 390e-6 < sum(c[1] for c in forward) / len(forward) < 420e-6      # 406 us each


def test_flash_roofline_reader_on_the_recorded_slice():
    from benchmark.harness import load_peaks
    from benchmark.readers import flash_roofline, op_share

    events = tracered.read_events(os.path.join(FIXTURES, "gpt2_train_slice.events.json.gz"))
    facts = {"trace": tracered.reduce(events), "peaks": load_peaks("TPU v5 lite"),
             "config": {"n_head": 12}}
    share = flash_roofline.read(facts, heads_key="n_head", causal=True)
    # forward 12.9 GFLOP -> 65 us of 406; backward 32.2 GFLOP -> 163 us of 725
    assert 15.0 < share < 25.0
    mosaic = op_share.read(facts, pattern=" custom-call tpu_custom_call$")
    assert 0.0 < mosaic < 100.0
    facts["trace"]["custom_calls"] = []
    assert flash_roofline.read(facts, heads_key="n_head", causal=True) is None


def test_recorded_serve_ticks():
    """Two decode ticks of the paged GPT-2 124M engine on the v5e (PR 23, 32
    slots, a 12288-block pool), up to the dispatch of the prefill that
    follows: host annotations cover the gaps between the programs."""
    events = tracered.read_events(os.path.join(FIXTURES, "gpt2_serve_ticks.events.json.gz"))
    r = tracered.reduce(events, {"annotations": ["serve/prefill", "serve/decode"]})
    assert r["busy_s"] * 1e9 == pytest.approx(brute_force_busy_ns(events), rel=1e-9)
    assert r["window_s"] == pytest.approx(0.321, rel=1e-6)
    assert r["idle_share"] == pytest.approx(0.027174, rel=1e-3)
    assert sum(r["op_self_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert [m[0].split("(")[0] for m in r["modules"]] == ["jit_decode", "jit_decode"]
    assert all(155e6 < m[2] < 157e6 for m in r["modules"])              # 156 ms a tick
    # the gap between the two ticks lies under serve/decode (the host fetches
    # the tokens and dispatches the next tick); the last under serve/prefill
    assert r["top_gaps"][0] == ["serve/prefill", pytest.approx(0.004273582, rel=1e-6)]
    assert r["top_gaps"][1] == ["serve/decode", pytest.approx(0.003206733, rel=1e-6)]
    assert set(r["idle_by_annotation_s"]) == {"serve/prefill", "serve/decode"}
    assert sum(r["idle_by_annotation_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    # what the tick spends its time on: whole-pool copies, not attention
    copies = sum(s for n, s in r["op_self_s"].items() if "bf16[12288,12,16,64] copy" in n)
    assert copies / r["busy_s"] > 0.9

    from benchmark.readers import annotation_median_ms, device_step_ms, idle_share, op_share

    facts = {"trace": r}
    assert annotation_median_ms.read(facts, annotation="serve/decode") == pytest.approx(158.30, abs=0.6)
    assert annotation_median_ms.read(facts, annotation="serve/verify") is None
    assert device_step_ms.read(facts, module_prefix="jit_decode") == pytest.approx(156.04, abs=0.02)
    assert idle_share.read(facts) == pytest.approx(2.7174, rel=1e-3)
    assert 3.0 < op_share.read(facts, pattern=" custom-call tpu_custom_call$") < 5.0
