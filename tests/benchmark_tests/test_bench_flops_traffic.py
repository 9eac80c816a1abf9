"""The benchmark's FLOPs functions against hand-worked values, its request
generator, and its percentile arithmetic.  CPU only, no JAX."""

import json
import os

import numpy as np
import pytest

from benchmark import stats, traffic
from benchmark.flops import flash_attention, gpt2, vit
from benchmark.harness import HERE, load_json


def test_gpt2_124m_flops_hand_worked():
    cfg = load_json("configs", "gpt2-124m.json")
    # derivation in benchmark/flops/gpt2.py
    assert gpt2.forward_flops_per_token(cfg, 1024) == 169_869_312 + 18_874_368 + 77_194_752
    per_token = gpt2.train_flops_per_sample(cfg, {"seq_len": 1024}) / 1024
    assert per_token == 797_815_296
    assert per_token / 1e9 == pytest.approx(0.80, abs=0.005)
    assert gpt2.units_per_sample(cfg, {"seq_len": 1024}) == ("tokens", 1024.0)


def test_vit_b16_flops_hand_worked():
    cfg = load_json("configs", "vit-b16.json")
    # derivation in benchmark/flops/vit.py
    assert vit.forward_flops_per_image(cfg) == (
        231_211_008 + 33_464_254_464 + 1_430_654_976 + 1_536_000)
    assert vit.train_flops_per_sample(cfg, {}) == 105_382_969_344
    assert vit.train_flops_per_sample(cfg, {}) / 1e9 == pytest.approx(105.4, abs=0.05)


def test_flash_attention_ops_bytes():
    kw = dict(batch=8, heads=12, q_len=1024, kv_len=1024, head_dim=64, itemsize=2)
    fwd_ops, fwd_bytes = flash_attention.ops_bytes(causal=True, backward=False, **kw)
    bwd_ops, _ = flash_attention.ops_bytes(causal=True, backward=True, **kw)
    full_ops, _ = flash_attention.ops_bytes(causal=False, backward=False, **kw)
    pair = 2 * 8 * 12 * 1024 * 1024 * 64
    assert full_ops == 2 * pair and fwd_ops == pair and bwd_ops == 2.5 * pair
    tensor = 8 * 12 * 1024 * 64 * 2
    assert fwd_bytes == 4 * tensor + 8 * 12 * 1024 * 4


# The chat-shaped mix of ISSUE 23 (PERF.md section 7, rows 1-2): no cell offers it yet.
STEADY = {
    "base_seed": 23,
    "arrivals": {"process": "gamma", "cv": 1.0, "rate_per_s": 2.8},
    "prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 16, "max": 768},
    "output_len": {"dist": "lognormal", "median": 64, "sigma": 0.6, "min": 8, "max": 192},
    "max_total": 1022,
}


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_generator_same_seed_same_requests(seed):
    a = traffic.generate(STEADY, seed=seed, seconds=20, vocab_size=50257)
    b = traffic.generate(STEADY, seed=seed, seconds=20, vocab_size=50257)
    assert len(a) == len(b) == traffic.n_requests(STEADY, 20)
    assert np.array_equal(a.budgets, b.budgets) and np.array_equal(a.due_s, b.due_s)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


def test_generator_ranges_and_position_bound():
    g = traffic.generate(STEADY, seed=5, seconds=30, vocab_size=50257)
    lens = np.asarray([p.size for p in g.prompts])
    assert lens.min() >= 16 and lens.max() <= 768
    assert g.budgets.min() >= 8 and g.budgets.max() <= 192
    assert (lens + g.budgets).max() <= 1022
    assert all(p.dtype == np.int32 and p.min() >= 0 and p.max() < 50257 for p in g.prompts)
    assert np.all(np.diff(g.due_s) >= 0) and 0 <= g.due_s[0] and g.due_s[-1] < 30


def test_generator_every_seed_gets_the_same_work_in_another_order():
    a = traffic.generate(STEADY, seed=1, seconds=25, vocab_size=50257)
    b = traffic.generate(STEADY, seed=2, seconds=25, vocab_size=50257)
    pairs = lambda g: sorted(zip((p.size for p in g.prompts), g.budgets.tolist()))  # noqa: E731
    assert pairs(a) == pairs(b)                       # same multiset of (prompt, budget)
    assert [p.size for p in a.prompts] != [p.size for p in b.prompts]   # another order
    gaps = lambda g: np.sort(np.concatenate([[2 * g.due_s[0]], np.diff(g.due_s)]))  # noqa: E731
    assert np.allclose(gaps(a), gaps(b))              # same multiset of inter-arrival gaps


def test_generator_burstiness_is_data():
    mix = json.loads(json.dumps(STEADY))
    mix["arrivals"]["cv"] = 3.0
    g = traffic.generate(mix, seed=3, seconds=20, vocab_size=50257)
    gaps = np.diff(g.due_s)
    assert gaps.std() / gaps.mean() > 1.5             # burstier than Poisson (cv 1)
    assert gaps.sum() + g.due_s[0] < 20


def test_percentile_matches_numpy():
    xs = np.random.default_rng(0).random(101).tolist()
    for q in (50, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([], 95) is None and stats.median([3.0]) == 3.0


def test_peaks_table_has_source_and_refuses_unknown():
    from benchmark.harness import load_peaks

    assert "Google Cloud" in load_json("peaks.json")["source"]
    assert load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        load_peaks("cpu")
    assert os.path.isdir(HERE)


def test_record_and_counter_readers():
    from benchmark.readers import data_wait_share, queue_wait_p95_ms, slot_occupancy

    records = [{"arrival": 10.0, "admitted": 10.0 + i / 1000.0} for i in range(101)]
    records.append({"arrival": 11.0, "admitted": None})            # still queued: no sample
    assert queue_wait_p95_ms.read({"records": records}) == pytest.approx(95.0)
    assert queue_wait_p95_ms.read({"records": []}) is None
    facts = {"engine_stats": {"decode_ticks": 10, "decode_slot_ticks": 240}, "num_slots": 32}
    assert slot_occupancy.read(facts) == pytest.approx(75.0)
    assert slot_occupancy.read({"engine_stats": {"decode_ticks": 0}, "num_slots": 32}) is None
    assert data_wait_share.read({"data_wait_s": 0.5, "window_s": 25.0}) == pytest.approx(2.0)
    assert data_wait_share.read({"window_s": 25.0}) is None        # nothing to read: left out


class FakeChip:
    def __init__(self, **stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize("seen, stats, peak", [
    # a footprint read at one instant beats either region's own peak
    (5_000, dict(peak_bytes_in_use=3_000, peak_bytes_reserved=4_000), 5_000),
    # the two peaks are never added: they need not fall at the same instant
    (0, dict(peak_bytes_in_use=3_000, peak_bytes_reserved=4_000), 4_000),
    (0, dict(peak_bytes_in_use=3_000), 3_000),
    (0, None, 0),                                   # the CPU reports nothing
])
def test_memory_peak_is_a_footprint_that_was_held(seen, stats, peak):
    from benchmark import harness

    chips = [FakeChip(bytes_in_use=1, bytes_reserved=1), FakeChip()]
    chips[1].stats = stats
    ctx = harness.Context(cell_name="x", cell={}, config={}, seed=0, seconds=1.0, trace=False,
                          devices=chips, peaks=None, t_start=0.0, memory_seen=seen)
    assert harness.peak_memory_bytes(ctx) == peak


def test_memory_footprint_is_read_in_one_call_on_the_fullest_chip():
    from benchmark import harness

    chips = [FakeChip(bytes_in_use=700, bytes_reserved=200, peak_bytes_reserved=10**9),
             FakeChip(bytes_in_use=100, bytes_reserved=900)]
    assert harness.memory_footprint_bytes(chips) == 1_000
    ctx = harness.Context(cell_name="x", cell={}, config={}, seed=0, seconds=1.0, trace=False,
                          devices=chips, peaks=None, t_start=0.0)
    ctx.sample_memory()
    chips[1].stats = dict(bytes_in_use=10, bytes_reserved=0)
    ctx.sample_memory()
    assert ctx.memory_seen == 1_000
