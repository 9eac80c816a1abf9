"""The Instella-MoE configuration's benchmark pieces at a toy size on the CPU:
the plain reference against the system (logits, the loss and its three parts,
every gradient leaf; far-skip on and off), the chip's share of the experts
against the uncut reference with the shared MLP counted once, the FLOPs
function against its hand count, the configuration file against the published
row, the cell against ISSUE 32, the new layer file's reader, and the kind
through the test-only entry."""

import ast
import copy
import json
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.flops import instella_moe as flops
from benchmark.harness import ROOT, load_json, load_manifest, model_overrides
from benchmark.readers import kernel_roofline, op_share, ragged_dot_roofline
from benchmark.reference import instella_moe as ref
from pytorch_distributed_training_tpu import models
from pytorch_distributed_training_tpu.models import instella_moe as im, moe
from pytorch_distributed_training_tpu.ops.losses import cross_entropy_loss

CELL = "instella-moe-16b-a3b-base.train.causal8k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def toy(held=None, **keys):
    """hidden 64, 4 heads of 24 + 8 / 32 over a latent of 16, 8 experts top-2
    of width 32 beside a shared MLP of 64, a dense layer of 96, 2 + 1 blocks."""
    cfg = copy.deepcopy(load_json("rehearsal", "tiny-instella.json"))
    cfg["system"]["overrides"]["experts_held"] = held
    cfg["system"]["overrides"]["remat"] = False
    cfg.update(keys)
    return cfg


def system_and_params(cfg, seq_len=32):
    net = models.create_model("instella_moe_16b_a3b", dtype=jnp.float32, cfg_overrides=model_overrides(cfg))
    params = net.init(jax.random.PRNGKey(1), jnp.zeros((1, seq_len), jnp.int32), train=False)["params"]
    # norm scales start at one and the selection bias at zero: move them, or
    # a wrong scale or an ignored bias would not show
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x for x, k in zip(leaves, keys)]
    return net, jax.tree_util.tree_unflatten(tree, leaves)


@pytest.mark.parametrize("farskip, held", [(True, None), (False, None), (True, (2, 4))])
def test_reference_matches_the_system(farskip, held):
    """Loss, its three parts, both heads' logits and every gradient leaf."""
    cfg = toy(held=held, farskip=farskip)
    net, params = system_and_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 512)

    def system_loss(prm):
        (logits, mtp), sown = net.apply({"params": prm}, tokens, mtp=True, mutable=["losses", "moe_counters"])
        parts = jnp.stack([cross_entropy_loss(logits[:, :-1], tokens[:, 1:]),
                           cross_entropy_loss(mtp[:, :-2], tokens[:, 2:]),
                           1e-4 * sum(jnp.sum(x) for x in jax.tree_util.tree_leaves(sown["losses"]))])
        held_n = sum(jnp.sum(x) for path, x in jax.tree_util.tree_leaves_with_path(sown["moe_counters"])
                     if "moe_held_assignments" in jax.tree_util.keystr(path))
        return parts[0] + 0.3 * parts[1] + parts[2], (parts, logits, mtp, held_n)

    (want_loss, (want_parts, want_logits, want_mtp, want_held)), want_grads = jax.value_and_grad(
        system_loss, has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        for n in range(2):
            logits, mtp, _, _ = ref.logits_of(params, tokens[n], cfg)
            np.testing.assert_allclose(logits, want_logits[n], rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(mtp, want_mtp[n], rtol=2e-4, atol=2e-5)
        got_loss, got_grads = jax.value_and_grad(ref.loss)(params, tokens, cfg)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, got in jax.tree_util.tree_leaves_with_path(got_grads):
        want, name = flat_want[path], jax.tree_util.keystr(path)
        assert (float(jnp.abs(want).max()) > 0) != ("router_bias" in name), name    # only the bias takes none
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-6 + 1e-4 * float(jnp.abs(want).max()), err_msg=name)
    value, parts, norms, held_n = ref.loss_and_grad_norms(params, tokens, cfg)
    assert float(value) == pytest.approx(float(want_loss), rel=1e-5)
    np.testing.assert_allclose(parts, want_parts, rtol=1e-5)
    for path, got in jax.tree_util.tree_leaves_with_path(norms):
        assert float(got) == pytest.approx(float(jnp.linalg.norm(flat_want[path])), rel=2e-4, abs=1e-9), path
    assert float(held_n) == float(want_held)
    if held is None:
        assert float(held_n) == 2 * 2 * 32 * 2        # sequences x expert places (1 layer + MTP) x T x k


def test_farskip_is_read_from_the_file_and_changes_the_reference():
    on, off = toy(), toy(farskip=False)
    _, params = system_and_params(on)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (32,), 0, 512)
    a, b = ref.logits_of(params, tokens, on)[0], ref.logits_of(params, tokens, off)[0]
    assert float(jnp.abs(a - b).max()) > 1e-3


def test_the_shares_and_the_shared_mlp_once_add_up_to_the_uncut_reference_layer(monkeypatch):
    """Four chips hold two routed experts each and every one the shared MLP;
    the chips' routed parts, each computed by the system with its own share's
    weights, plus the shared MLP counted ONCE equal what the uncut reference
    gives for the whole feed-forward sublayer."""
    cfg = toy(held=None)
    monkeypatch.setattr(moe, "ROWS_CHUNK", 48)       # several passes of the expert loop
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 64))
    layer = lambda held: moe.TopKMoe(8, 2, 32, experts_held=held, scoring="sigmoid", selection_bias=True,
                                     routed_scaling_factor=2.5, seq_aux=True)
    routed = layer(None).init(jax.random.PRNGKey(1), x)["params"]
    routed["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (8,))
    shared = im.GatedMlp(64).init(jax.random.PRNGKey(2), x)["params"]
    with jax.default_matmul_precision("highest"):
        want_routed, _, all_held = ref.experts(x[0], routed, cfg, (0, 8))
        want = want_routed + ref.gated_mlp(x[0], shared)
    assert float(all_held) == 64 * 2
    total = im.GatedMlp(64).apply({"params": shared}, x)          # every chip computes it alike: once
    for first in range(0, 8, 2):
        share = {"router": routed["router"], "router_bias": routed["router_bias"],
                 **{k: routed[k][first:first + 2] for k in ("w_gate", "w_up", "w_down")}}
        part, _ = layer((first, 2)).apply({"params": share}, x, mutable=["losses", "moe_counters"])
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(part[0], ref.experts(x[0], share, cfg, (first, 2))[0], rtol=2e-4, atol=1e-6)
        total = total + part
    np.testing.assert_allclose(total[0], want, rtol=2e-4, atol=1e-6)


def test_reference_balance_and_bias_follow_the_equations():
    cfg = toy()
    b = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    p = {"router": 0.5 * jax.random.normal(jax.random.PRNGKey(1), (64, 8)), "router_bias": jnp.zeros((8,))}
    top_e, top_w, s = ref.route(b, p, cfg)
    np.testing.assert_allclose(top_w.sum(-1), 2.5, rtol=1e-6)              # normalised, then the scaling factor
    biased = {**p, "router_bias": jnp.zeros((8,)).at[3].set(5.0)}
    e2, w2, _ = ref.route(b, biased, cfg)
    assert bool(jnp.all(jnp.any(e2 == 3, axis=-1)))
    chosen = jnp.take_along_axis(s, e2, axis=-1)
    np.testing.assert_allclose(w2, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    stacks = {k: jnp.zeros((8, *shape)) for k, shape in (("w_gate", (64, 32)), ("w_up", (64, 32)), ("w_down", (32, 64)))}
    _, balance, held = ref.experts(b, {**p, **stacks}, cfg, (0, 8))
    f = np.bincount(np.asarray(top_e).ravel(), minlength=8) * 8 / (2 * 16)
    want = float(np.sum(f * np.asarray(jnp.mean(s / s.sum(-1, keepdims=True), axis=0))))
    assert float(balance) == pytest.approx(want, rel=1e-5) and float(held) == 32


def test_reference_shares_no_code_with_the_program():
    tree = ast.parse(open(os.path.join(ROOT, "benchmark", "reference", "instella_moe.py")).read())
    imported = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    imported += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    assert not any("pytorch_distributed_training_tpu" in m for m in imported), imported


def test_reference_frequencies_and_scale_against_hand_computed_values():
    cfg = load_json("configs", "instella-moe-16b-a3b-base.json")
    inv = np.asarray(ref.yarn_frequencies(cfg))
    plain = np.array([8e6 ** (-i / 16) for i in range(16)])
    np.testing.assert_allclose(inv[:4], plain[:4], rtol=1e-6)
    np.testing.assert_allclose(inv[7:], plain[7:] / 40, rtol=1e-6)
    np.testing.assert_allclose(inv[4:7], [plain[i] * (1 - q) + plain[i] / 40 * q for i, q in ((4, .25), (5, .5), (6, .75))],
                               rtol=1e-6)
    assert ref.score_scale(cfg) == pytest.approx(128 ** -0.5 * (0.1 * math.log(40) + 1) ** 2, rel=1e-12)
    np.testing.assert_allclose(inv, im.yarn_inv_freq(32, 8e6, cfg["rope_scaling"]), rtol=1e-6)


def test_flops_function_against_the_hand_count_at_the_cuts_sizes():
    cfg = load_json("configs", "instella-moe-16b-a3b-base.json")
    attention = 2048 * 2048 + 2048 * 544 + 512 * 3584 + 2048 * 2048 + 2048 * 2048
    assert attention == 15_532_032
    met = (6 * attention + 3 * 2048 * 10944 + 5 * (3 * 2048 * 2816 + 2048 * 64 + 0.75 * 3 * 2048 * 1408)
           + 4096 * 2048 + 2 * 2048 * 16112)
    assert met == 354_418_688
    per_token = 3 * (2 * met + 6 * 2 * 8192 * 16 * 128)
    assert per_token == 2_730_491_904                              # 2.73 GFLOP a token
    assert flops.forward_flops_per_token(cfg, 8192) == per_token / 3
    assert flops.train_flops_per_sample(cfg, {"seq_len": 8192}) == per_token * 8192 == 22_368_189_677_568
    assert flops.units_per_sample(cfg, {"seq_len": 8192}) == ("tokens", 8192.0) and flops.expert_blocks(cfg) == 5
    # the experts at the share that ran: a sixteenth instead of an eighth halves their part
    less = flops.forward_flops_per_token(cfg, 8192, held_share=1 / 16)
    assert per_token / 3 - less == 5 * 6 / 16 * 3 * 2 * 2048 * 1408
    assert "2,730,491,904" in flops.__doc__ and "354,418,688" in flops.__doc__


def test_the_cut_holds_668_million_parameters():
    cfg = load_json("configs", "instella-moe-16b-a3b-base.json")
    net = models.create_model("instella_moe_16b_a3b", cfg_overrides=model_overrides(cfg))
    shapes = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32), train=False))
    sizes = {jax.tree_util.keystr(p): x.size for p, x in jax.tree_util.tree_leaves_with_path(shapes["params"])}
    total = sum(sizes.values())
    assert total == 668_046_144 and 10.68e9 < total * 16 < 10.70e9
    block = lambda name: sum(v for k, v in sizes.items() if k.startswith(f"['{name}']"))
    assert block("block_0") == 15_532_032 + 3 * 2048 * 10944 + 2 * 2048 + 512 + 2 * 128           # the dense layer
    assert block("block_1") == block("mtp_block") == (
        15_532_032 + 17_301_504 + 2048 * 64 + 64 + 8 * 8_650_752 + 2 * 2048 + 512 + 2 * 128)     # 102.2 M
    assert sizes["['embed']"] == sizes["['lm_head']['kernel']"] == 16112 * 2048
    assert sizes["['mtp_proj']['kernel']"] == 4096 * 2048


def test_configuration_keeps_every_published_key_but_the_reduced():
    cfg = load_json("configs", "instella-moe-16b-a3b-base.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"       # where the sandbox has it
    row = next(json.loads(l) for l in open(catalog) if '"Instella-MoE-16B-A3B-Base"' in l) \
        if os.path.exists(catalog) else None
    published = {"hidden_size": 2048, "num_attention_heads": 16, "qk_nope_head_dim": 96, "qk_rope_head_dim": 32,
                 "v_head_dim": 128, "kv_lora_rank": 512, "intermediate_size": 10944, "moe_intermediate_size": 1408,
                 "num_experts_per_tok": 6, "n_shared_experts": 2, "first_k_dense_replace": 1, "num_hidden_layers": 27,
                 "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-6, "rope_theta": 8000000, "routed_scaling_factor": 2.5,
                 "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "seq_aux": True,
                 "norm_topk_prob": True, "gated_attention": True, "qk_layernorm": True, "farskip": True,
                 "rope_interleave": True, "q_lora_rank": None, "tie_word_embeddings": False,
                 "max_position_embeddings": 65536}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
                                   "original_max_position_embeddings": 4096, "type": "yarn"}
    if row is not None:
        assert cfg["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if cfg.get(k, "absent") != v} == {"n_routed_experts", "vocab_size"}
    assert sorted(cfg["reduced"]) == ["layers", "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 128896}
    overrides = cfg["system"]["overrides"]
    assert overrides["n_routed_experts"] == cfg["published"]["n_routed_experts"]      # the router stays 64 wide
    assert overrides["experts_held"] == [0, cfg["n_routed_experts"]] and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["layers"] - cfg["first_k_dense_replace"] >= 4 and cfg["n_head"] == cfg["num_attention_heads"]
    assert "8 chips share each layer" in cfg["deployment"] and {"layers", "n_head"} <= set(cfg["notes"])
    assert {"gated_attention", "qk_layernorm", "farskip", "seq_aux_alpha", "router_bias", "mtp_loss_weight",
            "mtp_input", "rope_interleave", "weights", "optimizer"} <= set(cfg["assumed"])
    for key in ("gated_attention", "qk_layernorm", "farskip"):
        assert f"reads key {key}" in cfg["assumed"][key]
    net = models.create_model("instella_moe_16b_a3b", cfg_overrides=model_overrides(cfg))
    assert net.cfg == im.InstellaMoeConfig(
        num_hidden_layers=5, vocab_size=16112, experts_held=(0, 8), remat=True)       # every other field as published
    assert ref.settings(cfg) == {"experts_held": (0, 8), "mtp_loss_weight": net.cfg.mtp_loss_weight,
                                 "seq_aux_alpha": net.cfg.seq_aux_alpha, "farskip": True}


def test_cell_is_the_issues():
    manifest = load_manifest()
    cell = load_json("workloads", CELL + ".json")
    assert cell["kind"] == "train_moe" and cell["chips"] == 1
    assert cell["step"] == {"samples": 4, "microbatches": 4, "seq_len": 8192}
    assert cell["input"]["source"] == "synthetic_tokens" and cell["input"]["num_workers"] == 0
    assert (cell["warmup_steps"], cell["calibration_steps"]) == (2, 3)
    assert cell["trace"]["annotations"] == ["train", "train/input_wait", "train/host_sync"]
    check = cell["reference_check"]
    assert 0 < check["loss_rtol"] <= 1e-3 and 0 < check["grad_leaf_rtol"] <= 0.05 and 0 < check["router_grad_rtol"] <= 0.25
    assert 0 < check["held_assignments_rtol"] <= 0.05 and len(check["reason"]) > 80
    assert 0 < check["mtp_loss_rtol"] <= 1e-3 and 0 < check["moe_balance_loss_rtol"] <= 0.05
    assert 0 < check["grad_direction_rtol"] <= 0.2 < check["routed_direction_rtol"] < 0.5
    # a fresh model: (1 + lambda) x (ln V + half the logits' variance at a 0.02 head) + alpha x 5 layers
    first = cell["first_loss"]
    assert first["expected"] == pytest.approx(1.3 * (np.log(16112) + 2048 * 0.02 ** 2 / 2) + 5e-4, abs=1e-3)
    assert 0 < first["rtol"] <= 0.05 and len(first["reason"]) > 80
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": "instella-moe-16b-a3b-base", "traffic": "train.causal8k", "chips": 1,
                     "why": cell["why"]} and "1/8" in cell["why"]
    assert manifest["workloads"][-1] == entry and manifest["configs"][-1]["name"] == "instella-moe-16b-a3b-base"
    reported = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"train_mfu", "setup_s", "input.data_wait_share.train", "input.wait_share.train",
            "loop.device_step_ms.train", "loop.compiles_in_window.train", "kernel.mosaic_share.train",
            "kernel.flash_fwd_roofline.train", "kernel.flash_bwd_roofline.train", "kernel.flash_share.train",
            "device.idle_share.train", "device.peak_hbm_gb.train", "moe.held_assignment_share.train",
            "moe.load_imbalance.train", "kernel.ragged_dot_roofline.train", "moe.grouped_matmul_share.train",
            "startup.compile_s", "startup.cache_misses"} == reported
    assert all(m["workloads"][-1] == CELL for m in manifest["per_layer"] + manifest["end_to_end"]
               if CELL in m.get("workloads", ()))
    assert manifest["per_layer"][-1]["name"] == "kernel.flash_share.train"


def test_the_accepted_readers_read_this_cells_kernels():
    """On a hand-made table in the trace's own spelling: the multi-tile causal
    kernels lead with bf16[batch, heads, len, dim]; the split backward's least
    time is counted once; the grouped products by the accepted file's keys."""
    cfg = load_json("configs", "instella-moe-16b-a3b-base.json")
    calls = [
        ("%flash_fwd.3 = (bf16[1,16,8192,128], f32[1,16,8192,8]) custom-call tpu_custom_call", 4e-3),
        ("%flash_bwd_dq.3 = bf16[1,16,8192,128] custom-call tpu_custom_call", 5e-3),
        ("%flash_bwd_dkv.3 = (bf16[1,16,8192,128], bf16[1,16,8192,128]) custom-call tpu_custom_call", 6e-3),
        ("%ragged-dot-none.7 = bf16[49152,1408] custom-call tpu_custom_call", 1e-3),
        ("%fusion.1 = bf16[8192,2048] fusion", 9e-3),
    ]
    facts = {"peaks": PEAKS, "config": cfg, "steps": 2, "microbatches": 4,
             "counters": {"moe_held_assignments": 2 * 4 * 5 * 6144.0, "moe_experts_held_per_layer": 8.0},
             "trace": {"custom_calls": [c for c in calls if "custom-call" in c[0]], "busy_s": 25e-3,
                       "op_self_s": dict(calls), "modules": [("jit_train_step(1)", 0, 1.0)]}}
    fwd = load_json("layers", "kernel.flash_fwd_roofline.train.json")["args"]
    bwd = load_json("layers", "kernel.flash_bwd_roofline.train.json")["args"]
    pair = 2.0 * 16 * 8192 * 8192 * 128 / 2
    assert kernel_roofline.read(facts, **fwd) == pytest.approx(100 * (2 * pair / 197e12) / 4e-3, rel=1e-9)
    assert kernel_roofline.read(facts, **bwd) == pytest.approx(100 * (5 * pair / 197e12) / 11e-3, rel=1e-9)
    share = load_json("layers", "kernel.flash_share.train.json")
    assert share["reader"] == "op_share" and op_share.read(facts, **share["args"]) == pytest.approx(100 * 15 / 25)
    # the masked pair's names are not this metric's, and the native pair's are
    rx = share["args"]["pattern"]
    assert not re.search(rx, "%flash_bd_fwd.1 = ...") and re.search(rx, "%flash_bwd.12 = ...")
    grouped = load_json("layers", "kernel.ragged_dot_roofline.train.json")["args"]
    ops = 2.0 * 6144 * 2048 * 1408
    assert ragged_dot_roofline.read(facts, **grouped) == pytest.approx(
        100 * (1 * 5 * 4 * 9 * ops / 197e12) / 1e-3, rel=1e-9)
    # the parent, or a cell without these kernels, gives nothing to read
    empty = {**facts, "trace": {**facts["trace"], "custom_calls": [], "op_self_s": {}}}
    assert kernel_roofline.read(empty, **fwd) is None and op_share.read(empty, **share["args"]) == 0.0


@pytest.mark.parametrize("wrong, over", [
    (None, set()),
    ("mtp_loss", {"mtp_loss"}), ("moe_balance_loss", {"moe_balance_loss"}),
    # a gradient of the right norm that points elsewhere: only its difference from the reference shows it
    ("['block_0']['attn']['wq']['kernel']", {"grad_direction"}),
    ("['mtp_block']['shared']['w_up']['kernel']", {"grad_direction"}),
    # the routed experts' leaves have the looser limit: a fifth off passes, a whole norm off does not
    ("['block_1']['moe']['w_down']", set()), ("['block_1']['moe']['w_down'] far", {"routed_direction"}),
    ("norm ['embed']", {"grad_leaf", "grad_direction"}),
])
def test_kind_holds_every_reading_to_its_limit(wrong, over):
    from benchmark.kinds import train_moe as kind

    check = load_json("workloads", CELL + ".json")["reference_check"]
    want_norms = {"['block_0']['attn']['wq']['kernel']": 2.0, "['block_1']['attn']['wq']['kernel']": 1.0,
                  "['block_1']['moe']['w_down']": 5.0, "['block_1']['moe']['router']": 1.0,
                  "['block_1']['moe']['router_bias']": 0.0, "['mtp_block']['shared']['w_up']['kernel']": 3.0,
                  "['embed']": 300.0}
    sys_norms = {k: v * (1.2 if wrong == "norm " + k else 1.001) for k, v in want_norms.items()}
    diff = {k: v * 0.004 for k, v in want_norms.items()}
    if wrong in diff:
        diff[wrong] = 0.2 * want_norms[wrong]
    if wrong and wrong.endswith(" far"):
        diff[wrong[:-4]] = want_norms[wrong[:-4]]
    if wrong == "norm ['embed']":
        diff["['embed']"] = 60.0
    parts = {"mtp_loss": 10.2 * (1.01 if wrong == "mtp_loss" else 1.0),
             "moe_balance_loss": 5.0e-4 * (1.1 if wrong == "moe_balance_loss" else 1.0)}
    read = kind.all_readings((13.0, 30000.0, parts), (13.0, 30010.0, np.array([9.9, 10.2, 5.0e-4])),
                             (sys_norms, want_norms, diff))
    limits = kind.limits(check)
    assert {k for k, limit in limits.items() if not read[k] <= limit} == over
    assert set(limits) == {"loss", "grad_leaf", "router_grad", "held_assignments", "grad_direction",
                           "routed_direction", "mtp_loss", "moe_balance_loss"}
    assert not kind.routed(read["worst_direction"]) and kind.routed(read["worst_routed_direction"])
    # a block's leaf is held to that parameter's norm over all the blocks
    assert kind.direction_readings({"['block_0']['a']": 0.0, "['block_1']['a']": 0.3, "['block_1']['moe']['b']": 0.0},
                                   {"['block_0']['a']": 4.0, "['block_1']['a']": 3.0, "['block_1']['moe']['b']": 0.0}
                                   )["grad_direction"] == pytest.approx(0.3 / 5.0)


def test_leaf_norms_reads_both_trees_and_their_difference():
    from benchmark.kinds import train_moe as kind
    from pytorch_distributed_training_tpu import comm

    mesh = comm.make_mesh(comm.MeshConfig(data=-1), devices=jax.devices()[:1])
    got = {"a": np.array([3.0, 4.0], np.float32), "b": {"c": np.ones((2, 2), np.float32)}}     # waits on the host
    want = {"a": jnp.array([3.0, 0.0]), "b": {"c": jnp.ones((2, 2))}}
    sys_norms, ref_norms, diff = kind.leaf_norms(mesh, got, want)
    assert (sys_norms["['a']"], ref_norms["['a']"], diff["['a']"]) == (5.0, 3.0, 4.0)
    assert (sys_norms["['b']['c']"], diff["['b']['c']"]) == (2.0, 0.0)


def test_kind_rehearsal_counts_only():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.rehearse", "--workload", "tiny-instella.train.causal",
         "--seconds", "1", "--seed", "3000000019"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, out.stdout[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0 and "counters" in line["facts"]
    assert "metrics" not in line and "device" not in line
    for word in ("tokens/s", " ms", "mfu"):
        assert word not in out.stdout
