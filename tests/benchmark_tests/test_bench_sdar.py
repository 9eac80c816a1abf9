"""The SDAR-MoE configuration's benchmark pieces at a toy size on the CPU:
the plain reference against the system (logits, loss, every gradient leaf),
the chip's share of the experts against the uncut reference, the FLOPs
function against its hand-worked docstring, the new readers on hand-made
tables, the configuration file against the published row, and the kind
through the test-only entry."""

import ast
import copy
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.flops import block_diffusion_attention, ragged_dot, sdar_moe as flops
from benchmark.harness import ROOT, load_json, load_manifest, model_overrides
from benchmark.readers import bd_attention_roofline, counter_ratio, op_share, ragged_dot_roofline
from benchmark.reference import sdar_moe as ref
from pytorch_distributed_training_tpu import models
from pytorch_distributed_training_tpu.models import moe
from pytorch_distributed_training_tpu.train import block_diffusion

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def toy(held=None):
    """hidden 64, 4 query / 2 K/V heads of 16, 8 experts top-2 of width 32, B 4."""
    cfg = copy.deepcopy(load_json("rehearsal", "tiny-sdar.json"))
    cfg["system"]["overrides"]["experts_held"] = held
    return cfg


def system_and_params(cfg, seq_len=32):
    net = models.create_model("sdar_30b_a3b", dtype=jnp.float32, cfg_overrides=model_overrides(cfg))
    params = net.init(jax.random.PRNGKey(1), jnp.zeros((1, seq_len), jnp.int32), train=False)["params"]
    # norm scales start at one: move them, or a wrong scale would not show
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x for x, k in zip(leaves, keys)]
    return net, jax.tree_util.tree_unflatten(tree, leaves)


def test_reference_matches_the_system_all_experts_held():
    cfg = toy(held=None)
    net, params = system_and_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 511)
    noisy, masked, p = block_diffusion.noise(tokens, jax.random.PRNGKey(3), net.cfg)
    both = jnp.concatenate([noisy, tokens], axis=1)

    def system_loss(prm):
        logits = net.apply({"params": prm}, both, train=True, block_diffusion=True)
        return block_diffusion.weighted_masked_ce(logits, tokens, masked, p), logits

    (want_loss, want_logits), want_grads = jax.value_and_grad(system_loss, has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        got_logits = jnp.stack([ref.noisy_logits(params, tokens[n], masked[n], cfg) for n in range(2)])
        got_loss, got_grads = jax.value_and_grad(ref.loss)(params, tokens, masked, p, cfg)
    np.testing.assert_allclose(got_logits, want_logits, rtol=2e-4, atol=2e-5)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, got in jax.tree_util.tree_leaves_with_path(got_grads):
        want = flat_want[path]
        assert float(jnp.abs(want).max()) > 0, path
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-6 + 1e-4 * float(jnp.abs(want).max()),
                                   err_msg=jax.tree_util.keystr(path))
    value, norms, held = ref.loss_and_grad_norms(params, tokens, masked, p, cfg)
    assert float(value) == pytest.approx(float(want_loss), rel=1e-5)
    for path, got in jax.tree_util.tree_leaves_with_path(norms):
        assert float(got) == pytest.approx(float(jnp.linalg.norm(flat_want[path])), rel=2e-4), path
    assert float(held) == 2 * 2 * 64 * 2       # all experts held: sequences x layers x positions x k
    assert ref.noise_is_the_assumed(np.asarray(masked), np.asarray(p))


def test_reference_knows_masks_that_are_not_the_assumed_noise():
    rng = np.random.default_rng(0)
    p = np.array([0.001, 0.3, 0.97])
    masked = rng.random((3, 4096)) < p[:, None]
    assert ref.noise_is_the_assumed(masked, p)
    assert not ref.noise_is_the_assumed(masked, p * np.array([1, 0.8, 1]))      # a quarter more than drawn
    assert not ref.noise_is_the_assumed(masked, np.array([0.0005, 0.3, 0.97]))  # under the floor
    assert not ref.noise_is_the_assumed(masked, np.array([0.001, 0.3, 1.01]))


def test_the_shares_add_up_to_the_uncut_reference_layer(monkeypatch):
    """Four chips hold two experts each; their layers' outputs, each computed
    by the system with its own share's weights, sum to what the uncut
    reference gives for the whole layer."""
    cfg = toy(held=None)
    monkeypatch.setattr(moe, "ROWS_CHUNK", 48)       # several passes of the expert loop
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 64))
    whole = moe.TopKMoe(8, 2, 32, experts_held=None)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    with jax.default_matmul_precision("highest"):
        want = ref.experts(x[0], params, cfg, (0, 8))
    total = jnp.zeros_like(x)
    for first in range(0, 8, 2):
        share = {"router": params["router"],
                 **{k: params[k][first:first + 2] for k in ("w_gate", "w_up", "w_down")}}
        part = moe.TopKMoe(8, 2, 32, experts_held=(first, 2)).apply({"params": share}, x)
        with jax.default_matmul_precision("highest"):
            held = copy.deepcopy(cfg)
            np.testing.assert_allclose(part[0], ref.experts(x[0], share, held, (first, 2)),
                                       rtol=2e-4, atol=1e-6)
        total = total + part
    np.testing.assert_allclose(total[0], want, rtol=2e-4, atol=1e-6)


def test_reference_shares_no_code_with_the_program():
    tree = ast.parse(open(os.path.join(ROOT, "benchmark", "reference", "sdar_moe.py")).read())
    imported = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    imported += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    assert not any("pytorch_distributed_training_tpu" in m for m in imported), imported


def test_reference_mask_is_the_definition():
    m = np.asarray(ref.training_mask(8, 4))
    n, c = slice(0, 8), slice(8, 16)
    blocks = np.arange(8) // 4
    np.testing.assert_array_equal(m[n, n], blocks[:, None] == blocks[None])
    np.testing.assert_array_equal(m[n, c], blocks[None] < blocks[:, None])
    np.testing.assert_array_equal(m[c, c], blocks[None] <= blocks[:, None])
    assert not m[c, n].any()


# ---- FLOPs ---------------------------------------------------------------


def docstring_numbers(module):
    return {int(x.replace(",", "")) for x in re.findall(r"= +([\d,]{9,})", module.__doc__)}


def test_flops_function_against_its_hand_worked_docstring():
    cfg = load_json("configs", "sdar-30b-a3b-chat.json")
    shape = {"seq_len": 4096}
    numbers = docstring_numbers(flops)
    assert flops.live_pairs(4096, 4) == 16_793_600 and 16_793_600 in numbers
    forward = flops.forward_flops_per_sequence(cfg, 4096)
    assert forward == 4_314_563_084_288 and int(forward) in numbers
    assert flops.train_flops_per_sample(cfg, shape) == 12_943_689_252_864
    assert flops.train_flops_per_sample(cfg, shape, held_share=16 / 128) == 12_943_689_252_864
    # the held experts' term follows the assignments that ran: 9,437,184 a pass, x 8192 x 6 x 3 at 1 pass
    step = 9_437_184 * 8192 * 6 * 3
    assert flops.train_flops_per_sample(cfg, shape, held_share=0.115) == pytest.approx(
        12_943_689_252_864 - step * (1 - 0.115 * 8), rel=1e-12)
    assert 12_943_689_252_864 in numbers and 665_988_366_336 in numbers
    assert flops.units_per_sample(cfg, shape) == ("tokens", 4096.0)


@pytest.mark.parametrize("seq_len, block", [(32, 4), (48, 8), (40, 16), (256, 4)])
def test_live_pairs_against_a_brute_force_count(seq_len, block):
    assert flops.live_pairs(seq_len, block) == int(np.asarray(ref.training_mask(seq_len, block)).sum())


def test_attention_operations_and_bytes():
    ops, nbytes = block_diffusion_attention.ops_bytes(
        batch=1, heads=32, kv_heads=4, seq_len=4096, block=4, head_dim=128, itemsize=2, backward=False)
    assert ops == 4 * 128 * 32 * 16_793_600
    q, kv, lse = 32 * 8192 * 128 * 2, 4 * 8192 * 128 * 2, 32 * 8192 * 4
    assert nbytes == 2 * q + 2 * kv + lse
    ops_b, bytes_b = block_diffusion_attention.ops_bytes(
        batch=1, heads=32, kv_heads=4, seq_len=4096, block=4, head_dim=128, itemsize=2, backward=True)
    assert ops_b == 2.5 * ops and bytes_b == 4 * q + 4 * kv + lse


# ---- readers ---------------------------------------------------------------


def layer_args(metric):
    return load_json("layers", metric + ".json")["args"]


def test_bd_attention_rooflines_on_a_hand_made_table():
    cfg = load_json("configs", "sdar-30b-a3b-chat.json")
    fwd_least = 4 * 128 * 32 * 16_793_600 / 197e12            # compute-bound: 1.397 ms
    calls = [
        ["%flash_bd_fwd.3 = (bf16[1,32,8192,128], f32[1,32,8192,8]) custom-call tpu_custom_call", 4e-3],
        ["%flash_bd_fwd.9 = (bf16[1,32,8192,128], f32[1,32,8192,8]) custom-call tpu_custom_call", 4e-3],
        ["%flash_bd_bwd.4 = (bf16[1,32,8192,128], bf16[1,4,8192,128], bf16[1,4,8192,128]) custom-call tpu_custom_call", 6e-3],
        ["%flash_bd_bwd.5 = (bf16[1,32,8192,128], bf16[1,4,8192,128], bf16[1,4,8192,128]) custom-call tpu_custom_call", 4e-3],
        ["%flash_fwd.1 = (bf16[8,1024,768], f32[8,12,1024]) custom-call tpu_custom_call", 1e-3],
        ["%ragged-dot-none.2 = bf16[16384,768] custom-call tpu_custom_call", 1e-3],
    ]
    facts = {"peaks": PEAKS, "config": cfg, "trace": {"custom_calls": calls}}
    fwd = bd_attention_roofline.read(facts, **layer_args("kernel.bd_attn_fwd_roofline.train"))
    bwd = bd_attention_roofline.read(facts, **layer_args("kernel.bd_attn_bwd_roofline.train"))
    assert fwd == pytest.approx(100 * 2 * fwd_least / 8e-3) and 0 < fwd < 100
    assert bwd == pytest.approx(100 * 2 * 2.5 * fwd_least / 10e-3)
    # a trace without the kernels (the parent's, another cell's): nothing to read, no raise
    facts["trace"]["custom_calls"] = calls[4:]
    assert bd_attention_roofline.read(facts, **layer_args("kernel.bd_attn_fwd_roofline.train")) is None
    # and the GPT-2 metrics' patterns do not read the new kernels
    for metric in ("kernel.flash_fwd_roofline.train", "kernel.flash_bwd_roofline.train"):
        rx = re.compile(layer_args(metric)["pattern"])
        assert not any(rx.search(name) for name, _ in calls[:4])


def test_counter_readers():
    counters = {"moe_held_assignments": 3 * 8 * 6 * 8200.0, "moe_routed_assignments": 3 * 8 * 6 * 65536.0,
                "moe_load_max": 3 * 8 * 6 * 580.0, "moe_experts_held_per_layer": 16.0}
    facts = {"counters": counters}
    share = counter_ratio.read(facts, **layer_args("moe.held_assignment_share.train"))
    assert share == pytest.approx(100 * 8200 / 65536)
    imbalance = counter_ratio.read(facts, **layer_args("moe.load_imbalance.train"))
    assert imbalance == pytest.approx(580 / (8200 / 16))
    for metric in ("moe.held_assignment_share.train", "moe.load_imbalance.train"):
        assert counter_ratio.read({}, **layer_args(metric)) is None          # a kind with no counters


def test_ragged_dot_roofline_on_a_hand_made_table():
    """Two traced steps of the cell's shape: 6 layers x 8 microbatches a step,
    8192 live rows a place on average (the counter is a step's total)."""
    cfg = load_json("configs", "sdar-30b-a3b-chat.json")
    ops, nbytes = ragged_dot.ops_bytes(rows=8192, groups=16, d_in=2048, d_out=768, itemsize=2)
    assert ops == 2 * 8192 * 2048 * 768 and nbytes == 2 * (8192 * (2048 + 768) + 16 * 2048 * 768)
    least = max(ops / 197e12, nbytes / 819e9)                  # compute-bound: 131 us
    assert least == ops / 197e12
    calls = [["%ragged-dot-none.7 = bf16[16384,768] custom-call tpu_custom_call", 0.20],
             ["%ragged-dot-none.9 = bf16[16,2048,768] custom-call tpu_custom_call", 0.15],
             ["%flash_bd_fwd.3 = (bf16[1,32,8192,128], f32[1,32,8192,8]) custom-call tpu_custom_call", 0.5]]
    trace = {"custom_calls": calls, "busy_s": 4.0, "op_self_s": dict(calls),
             "modules": [["jit_train_step(1)", 0.0, 2e9], ["jit_train_step(1)", 2e9, 2e9], ["jit_noise(2)", 0.0, 1e6]]}
    facts = {"peaks": PEAKS, "config": cfg, "trace": trace, "steps": 12, "microbatches": 8,
             "counters": {"moe_held_assignments": 12 * 48 * 8192.0, "moe_experts_held_per_layer": 16.0}}
    got = ragged_dot_roofline.read(facts, **layer_args("kernel.ragged_dot_roofline.train"))
    assert got == pytest.approx(100 * 2 * 48 * 9 * least / 0.35) and 0 < got < 100
    assert op_share.read(facts, **layer_args("moe.grouped_matmul_share.train")) == pytest.approx(100 * 0.35 / 4.0)
    # nothing to read, no raise: no such call (another cell's trace), no counters (another kind)
    for lacking in ({"trace": {**trace, "custom_calls": calls[2:]}}, {"counters": {}}, {"microbatches": None}):
        assert ragged_dot_roofline.read({**facts, **lacking}, **layer_args("kernel.ragged_dot_roofline.train")) is None


# ---- the configuration and the cell -----------------------------------------


def test_configuration_keeps_every_published_width():
    cfg = load_json("configs", "sdar-30b-a3b-chat.json")
    published = {"hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
                 "moe_intermediate_size": 768, "num_experts_per_tok": 8, "rope_theta": 1000000,
                 "rms_norm_eps": 1e-6, "intermediate_size": 6144, "max_position_embeddings": 32768,
                 "num_hidden_layers": 48, "norm_topk_prob": True, "tie_word_embeddings": False}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    overrides = cfg["system"]["overrides"]
    assert overrides["num_experts"] == cfg["published"]["num_experts"]           # the router stays 128 wide
    assert overrides["experts_held"] == [0, cfg["num_experts"]] and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert overrides["mask_token_id"] == cfg["vocab_size"] - 1 and 4 <= cfg["layers"] <= 48
    assert "8 chips share each layer" in cfg["deployment"]
    net = models.create_model("sdar_30b_a3b", cfg_overrides=model_overrides(cfg))
    assert net.cfg.num_hidden_layers == cfg["layers"] and net.cfg.experts_held == (0, 16)
    assert {"block_length", "noise", "label_shift", "mask_token_id", "qk_norm", "router_aux_loss",
            "weights"} <= set(cfg["assumed"])


def test_cell_is_the_issues():
    manifest = load_manifest()
    cell = load_json("workloads", "sdar-30b-a3b-chat.train.bd4k.json")
    assert cell["kind"] == "train_block_diffusion" and cell["chips"] == 1
    assert cell["step"] == {"samples": 8, "microbatches": 8, "seq_len": 4096}
    assert cell["trace"]["annotations"] == ["train", "train/input_wait", "train/host_sync"]
    check = cell["reference_check"]
    assert 0 < check["loss_rtol"] <= 1e-3 and 0 < check["grad_leaf_rtol"] <= 0.05 < check["router_grad_rtol"] < 1
    assert 0 < check["held_assignments_rtol"] <= 0.05 and len(check["reason"]) > 80
    # the loss a fresh model starts at: ln V and half the logits' variance at a 0.02 head
    first = cell["first_loss"]
    assert first["expected"] == pytest.approx(np.log(18992) + 2048 * 0.02 ** 2 / 2, abs=1e-4) and first["rtol"] == 0.05
    assert cell["masked_tokens"]["eps"] == block_diffusion.NOISE_EPS == ref.NOISE_EPS
    assert "routing" in cell["why"] and cell["why"] == next(
        w["why"] for w in manifest["workloads"] if w["name"] == "sdar-30b-a3b-chat.train.bd4k")
    reported = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
                if "workloads" not in m or "sdar-30b-a3b-chat.train.bd4k" in m["workloads"]}
    assert {"train_mfu", "setup_s", "loop.device_step_ms.train", "kernel.mosaic_share.train",
            "device.idle_share.train", "device.peak_hbm_gb.train", "loop.compiles_in_window.train",
            "input.data_wait_share.train", "kernel.bd_attn_fwd_roofline.train",
            "kernel.bd_attn_bwd_roofline.train", "moe.held_assignment_share.train",
            "moe.load_imbalance.train", "input.wait_share.train", "kernel.ragged_dot_roofline.train",
            "moe.grouped_matmul_share.train"} <= reported
    assert "kernel.flash_fwd_roofline.train" not in reported


@pytest.mark.parametrize("wrong, passes", [
    (None, True),
    ("['block_0']['moe']['router']", True),        # 20 % off: inside the routers' own limit
    ("['block_0']['moe']['w_down']", False),       # the same on any other leaf is one wrong leaf
    ("['block_1']['moe']['w_down']", False),       # a gradient where the reference has exactly none
    ("loss", False), ("held", False),
])
def test_kind_reads_the_routers_leaves_apart(wrong, passes):
    from benchmark.kinds import train_block_diffusion as kind

    check = load_json("workloads", "sdar-30b-a3b-chat.train.bd4k.json")["reference_check"]
    # block_1's held experts drew no token: its expert stack's gradient is exactly 0
    leaves = {"['block_0']['moe']['router']": 1.0, "['block_1']['moe']['router']": 0.5,
              "['block_0']['moe']['w_down']": 5.0, "['block_1']['moe']['w_down']": 0.0, "['embed']": 300.0}
    want = (10.0, leaves, 50000.0)
    got = (10.0 * (1.01 if wrong == "loss" else 1.0),
           {k: v * 1.001 + (0.2 * max(v, 1.0) if k == wrong else 0.0) for k, v in leaves.items()},
           50000.0 * (1.2 if wrong == "held" else 1.0))
    read = kind.readings(got, want)
    assert kind.within(read, check) is passes
    assert "router" in read["worst_router"] and "router" not in read["worst_leaf"]
    assert read["grad_norm"] < 2e-3            # the whole tree's norm hides either leaf
    assert set(kind.LIMITS.values()) <= set(check)


def test_kind_rehearsal_counts_only():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.rehearse", "--workload", "tiny-sdar.train.bd",
         "--seconds", "1", "--seed", "3000000019"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, out.stdout[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0 and "counters" in line["facts"]
    assert "metrics" not in line and "device" not in line
    for word in ("tokens/s", " ms", "mfu"):
        assert word not in out.stdout
