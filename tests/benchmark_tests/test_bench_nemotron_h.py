"""The Nemotron-H configuration's benchmark pieces at a toy size on the CPU:
the FLOPs function against its hand count, the configuration file against the
published row, the cell against ISSUE 34, the manifest's new entries against
their files, the two new layer files' patterns (against names taken from the
chip trace), the ``train_parts`` kind's
readings against their limits and the kind through the test-only entry.  The
reference against the system is ``tests/test_nemotron_h.py``'s."""

import ast
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.flops import nemotron_h as flops
from benchmark.harness import ROOT, load_json, load_manifest, model_overrides
from benchmark.readers import kernel_roofline, op_share
from benchmark.reference import nemotron_h as ref
from pytorch_distributed_training_tpu import models
from pytorch_distributed_training_tpu.models import nemotron_h as nh

CONFIG = "nemotron-labs-twotower-30b-a3b-base"
CELL = CONFIG + ".train.causal8k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_flops_function_against_the_hand_count_at_the_cuts_sizes():
    cfg = load_json("configs", CONFIG + ".json")
    mixer = 2688 * 10304 + 4096 * 2688
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256
    experts = 2688 * 128 + 2 * 2688 * 3712 + 0.375 * 2 * 2688 * 1856
    assert (mixer, attention, experts) == (38_707_200, 23_396_352, 24_041_472)
    met = 4 * mixer + attention + 4 * experts + 2688 * 16384
    assert met == 318_431_232
    forward = 2 * met + 4 * 2 * 4 * 6144 + 4 * 4 * 64 * 64 * 128 + 2 * 8192 * 32 * 128
    assert forward == 712_556_544 and 3 * forward == 2_137_669_632              # 2.14 GFLOP a token
    assert flops.forward_flops_per_token(cfg, 8192) == forward
    assert flops.train_flops_per_sample(cfg, {"seq_len": 8192}) == 3 * forward * 8192 == 17_511_789_625_344
    assert flops.units_per_sample(cfg, {"seq_len": 8192}) == ("tokens", 8192.0)
    assert flops.layers(cfg) == "MEMEM*EME" and flops.expert_blocks(cfg) == cfg["expert_layers"] == 4
    # the experts at the share that ran: a thirty-second instead of a sixteenth halves their part
    less = flops.forward_flops_per_token(cfg, 8192, held_share=1 / 32)
    assert forward - less == 4 * 6 / 32 * 2 * 2 * 2688 * 1856
    # the scan is the recurrence's own count, 4 H P N a position, whatever computes it
    no_state = {**cfg, "ssm_state_size": 0}
    assert forward - flops.forward_flops_per_token(no_state, 8192) == 4 * (
        4 * 64 * 64 * 128 + 2 * 2688 * 2 * 8 * 128 + 2 * 4 * 2 * 8 * 128)          # + B and C's columns of W_in and of the conv
    for number in ("2,137,669,632", "318,431,232", "17,511,789,625,344"):
        assert number in flops.__doc__


def test_the_cut_holds_667_million_parameters():
    cfg = load_json("configs", CONFIG + ".json")
    net = models.create_model("nemotron_h_30b_a3b", cfg_overrides=model_overrides(cfg))
    shapes = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32), train=False))
    sizes = {jax.tree_util.keystr(p): x.size for p, x in jax.tree_util.tree_leaves_with_path(shapes["params"])}
    total = sum(sizes.values())
    assert total == 666_963_456 and 10.66e9 < total * 16 < 10.68e9
    block = lambda i: sum(v for k, v in sizes.items() if k.startswith(f"['block_{i}']"))
    assert [block(i) for i in range(9)] == [
        {"M": 38_744_896, "E": 100_125_440, "*": 23_399_040}[kind] for kind in "MEMEM*EME"]
    assert sizes["['embed']"] == sizes["['lm_head']['kernel']"] == 16384 * 2688
    assert sizes["['block_0']['mixer']['in_proj']['kernel']"] == 2688 * 10304
    assert sizes["['block_1']['moe']['w_up']"] == 8 * 2688 * 1856 and "['block_1']['moe']['w_gate']" not in sizes
    for text in ("38,744,896", "100,125,440", "23,399,040", "666,963,456"):
        assert text in json.dumps(cfg["arithmetic"]) + json.dumps(cfg["reduced"])


def test_configuration_keeps_every_published_key_but_the_reduced():
    cfg = load_json("configs", CONFIG + ".json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"       # where the sandbox has it
    row = next(json.loads(l) for l in open(catalog) if '"Nemotron-Labs-TwoTower-30B-A3B-Base-BF16"' in l) \
        if os.path.exists(catalog) else None
    published = {"hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
                 "conv_kernel": 4, "chunk_size": 128, "expand": 2, "num_attention_heads": 32, "num_key_value_heads": 2,
                 "head_dim": 128, "intermediate_size": 1856, "moe_intermediate_size": 1856,
                 "moe_shared_expert_intermediate_size": 3712, "n_shared_experts": 1, "num_experts_per_tok": 6,
                 "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
                 "num_hidden_layers": 52, "norm_eps": 1e-5, "layer_norm_epsilon": 1e-5, "mlp_hidden_act": "relu2",
                 "mamba_hidden_act": "silu", "use_conv_bias": True, "use_bias": False, "mlp_bias": False,
                 "attention_bias": False, "mamba_proj_bias": False, "time_step_min": 0.001, "time_step_max": 0.1,
                 "time_step_floor": 0.0001, "time_step_limit": [0, None], "rope_theta": 10000,
                 "tie_word_embeddings": False, "model_type": "nemotron_h", "max_position_embeddings": 262144,
                 "hybrid_override_pattern": nh.PATTERN}
    assert {k: cfg[k] for k in published} == published
    if row is not None:
        assert cfg["source"] == row["source_url"] and nh.PATTERN == row["config"]["hybrid_override_pattern"]
        assert {k for k, v in row["config"].items() if cfg.get(k, "absent") != v} == {"n_routed_experts", "vocab_size"}
    assert sorted(cfg["reduced"]) == ["layers", "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {"layers": 52, "n_routed_experts": 128, "vocab_size": 131072,
                                "hybrid_override_pattern": nh.PATTERN}
    assert cfg["layers"] == 9 and cfg["pattern_run"] == nh.PATTERN[:9] == "MEMEM*EME"
    assert "EMEMEM*" in cfg["pattern_run"] * 2                            # the repeating unit, whole in rotation
    overrides = cfg["system"]["overrides"]
    assert overrides["n_routed_experts"] == cfg["published"]["n_routed_experts"]      # the router stays 128 wide
    assert overrides["experts_held"] == [0, cfg["n_routed_experts"]] and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_head"] == cfg["num_attention_heads"] and cfg["expert_layers"] == cfg["pattern_run"].count("E") >= 4
    assert "16 chips share each layer" in cfg["deployment"] and {"layers", "n_head", "expert_layers"} <= set(cfg["notes"])
    assert {"attention_positions", "gate_before_norm", "initialisation", "router_bias", "weights", "optimizer",
            "float32_leaves"} <= set(cfg["assumed"])
    assert "No positional encoding is applied" in cfg["assumed"]["attention_positions"]
    assert set(cfg["not_included"]) == {"denoiser_tower", "adaLN", "cross_tower_conditioning", "block_diffusion"}
    net = models.create_model("nemotron_h_30b_a3b", cfg_overrides=model_overrides(cfg))
    assert net.cfg == nh.NemotronHConfig(
        num_hidden_layers=9, vocab_size=16384, experts_held=(0, 8), remat=True)       # every other field as published
    assert ref.layers(cfg) == net.cfg.layers and ref.experts_held(cfg) == (0, 8)


def test_cell_is_the_issues():
    cell = load_json("workloads", CELL + ".json")
    assert cell["kind"] == "train_parts" and cell["chips"] == 1
    assert cell["loss_parts"] == [] and cell["counters"] == ["moe_held_assignments", "moe_load_max"]
    assert cell["step"] == {"samples": 4, "microbatches": 4, "seq_len": 8192}
    assert cell["input"]["source"] == "synthetic_tokens" and cell["input"]["num_workers"] == 0
    assert (cell["warmup_steps"], cell["calibration_steps"]) == (2, 3)
    assert cell["trace"]["annotations"] == ["train", "train/input_wait", "train/host_sync"]
    check = cell["reference_check"]
    assert 0 < check["loss_rtol"] <= 1e-3 and 0 < check["grad_leaf_rtol"] <= 0.2 and 0 < check["router_grad_rtol"] <= 0.25
    assert 0 < check["held_assignments_rtol"] <= 0.05 and len(check["reason"]) > 80
    assert 0 < check["grad_direction_rtol"] <= 0.2 < check["routed_direction_rtol"] < 0.3
    # a fresh model: ln V + half the logits' variance at a 0.02 head
    first = cell["first_loss"]
    assert first["expected"] == pytest.approx(np.log(16384) + 2688 * 0.02 ** 2 / 2, abs=1e-3)
    assert 0 < first["rtol"] <= 0.05 and len(first["reason"]) > 80
    assert "1/16" in cell["why"] and "16x" in cell["why"]


@pytest.mark.parametrize("cell, config, metrics", [
    (CELL, CONFIG, {
        "train_mfu", "setup_s", "input.data_wait_share.train", "input.wait_share.train", "loop.device_step_ms.train",
        "loop.compiles_in_window.train", "kernel.mosaic_share.train", "kernel.flash_fwd_roofline.train",
        "kernel.flash_bwd_roofline.train", "kernel.flash_share.train", "device.idle_share.train",
        "device.peak_hbm_gb.train", "moe.held_assignment_share.train", "moe.load_imbalance.train",
        "moe.expert_window_share.train", "ssm.mixer_share.train", "startup.compile_s", "startup.cache_misses"}),
    ("instella-moe-16b-a3b-base.train.causal8k", "instella-moe-16b-a3b-base", {
        "train_mfu", "setup_s", "input.data_wait_share.train", "input.wait_share.train", "loop.device_step_ms.train",
        "loop.compiles_in_window.train", "kernel.mosaic_share.train", "kernel.flash_fwd_roofline.train",
        "kernel.flash_bwd_roofline.train", "kernel.flash_share.train", "device.idle_share.train",
        "device.peak_hbm_gb.train", "moe.held_assignment_share.train", "moe.load_imbalance.train",
        "kernel.ragged_dot_roofline.train", "moe.grouped_matmul_share.train", "startup.compile_s",
        "startup.cache_misses"}),
])
def test_cells_keep_their_entries(cell, config, metrics):
    """A cell's entry is its file's, its configuration's entry resolves to a
    file, and it reports exactly these metrics — wherever in their lists a
    later PR's entries come to stand."""
    manifest = load_manifest()
    spec = load_json("workloads", cell + ".json")
    entry = next(w for w in manifest["workloads"] if w["name"] == cell)
    assert entry == {"name": cell, "config": config, "traffic": "train.causal8k", "chips": 1, "why": spec["why"]}
    listed = next(c for c in manifest["configs"] if c["name"] == config)
    assert listed["file"] == f"benchmark/configs/{config}.json" and os.path.isfile(os.path.join(ROOT, listed["file"]))
    assert sorted(listed["reduced"]) == sorted(load_json("configs", config + ".json")["reduced"])
    reported = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]}
    assert reported == metrics
    for m in manifest["per_layer"]:
        if m["name"] in metrics:
            assert os.path.isfile(os.path.join(ROOT, "benchmark", "layers", m["name"] + ".json"))


def test_the_instella_cell_keeps_its_limits():
    """What ``test_bench_instella.py::test_cell_is_the_issues`` pins of PR 32's
    cell beside its PLACE in the lists (it wants its entries last, and from
    this PR on another cell stands after them, so it fails in the open): the
    kind, the step, the annotations, every cap of the reference check and the
    first loss.  Nothing here asks where an entry stands."""
    cell = load_json("workloads", "instella-moe-16b-a3b-base.train.causal8k.json")
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
    first = cell["first_loss"]
    assert first["expected"] == pytest.approx(1.3 * (np.log(16112) + 2048 * 0.02 ** 2 / 2) + 5e-4, abs=1e-3)
    assert 0 < first["rtol"] <= 0.05 and len(first["reason"]) > 80 and "1/8" in cell["why"]


def test_the_new_metrics_list_the_new_cell_alone_and_the_grouped_products_metrics_do_not_read_it():
    manifest = load_manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert by_name["ssm.mixer_share.train"] == {
        "name": "ssm.mixer_share.train", "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "state-space", "moves": "train_mfu", "workloads": [CELL]}
    assert by_name["moe.expert_window_share.train"] == {
        **by_name["moe.grouped_matmul_share.train"], "name": "moe.expert_window_share.train", "workloads": [CELL]}
    # the cell's experts run as plain products over windows: no ``%ragged-dot`` call for these two to read
    assert CELL not in by_name["kernel.ragged_dot_roofline.train"]["workloads"]
    assert CELL not in by_name["moe.grouped_matmul_share.train"]["workloads"]
    assert not os.path.exists(os.path.join(ROOT, "benchmark", "layers", "kernel.ragged_dot_2mat_roofline.train.json"))
    assert nh.EXPERT_WINDOW == 512 and load_json("layers", "moe.expert_window_share.train.json")["reader"] == "op_share"


def test_the_readers_read_this_cells_kernels():
    """On a hand-made table in the trace's own spelling: the tabled causal
    pair leads with bf16[batch, heads, len, dim] at the QUERY heads' count;
    the experts' windows are told by their rows (two or three digits) beside
    an expert's two widths, their weight gradients by the float32 stacks they
    are added to — not the stacks' AdamW (three results), not a token-wide
    product."""
    cfg = load_json("configs", CONFIG + ".json")
    calls = [
        ("%flash_fwd.3 = (bf16[1,32,8192,128], f32[1,32,8192,8]) custom-call tpu_custom_call", 5e-3),
        ("%flash_bwd.3 = (bf16[1,32,8192,128], bf16[1,2,8192,128], bf16[1,2,8192,128]) custom-call tpu_custom_call", 9e-3),
        ("%fusion.3065 = bf16[512,1856] fusion", 1e-3), ("%compare_select_fusion.246 = bf16[512,1856] fusion", 1e-3),
        ("%fusion.3067 = bf16[512,2688] fusion", 1e-3), ("%fusion.3162 = bf16[512] fusion", 0.5e-3),
        ("%fusion.3060 = f32[8,1856,2688] fusion", 1e-3), ("%select_add_fusion.120 = f32[8,2688,1856] fusion", 0.5e-3),
        ("%fusion.1571 = (f32[8,2688,1856], f32[8,2688,1856], f32[8,2688,1856]) fusion", 1e-3),
        ("%fusion.1 = bf16[8192,2688] fusion", 4e-3),
    ]
    facts = {"peaks": PEAKS, "config": cfg, "steps": 2, "microbatches": 4,
             "counters": {"moe_held_assignments": 2 * 4 * 4 * 3072.0, "moe_experts_held_per_layer": 8.0},
             "trace": {"custom_calls": [c for c in calls if "custom-call" in c[0]], "busy_s": 25e-3,
                       "op_self_s": dict(calls), "modules": [("jit_train_step(1)", 0, 1.0)]}}
    fwd = load_json("layers", "kernel.flash_fwd_roofline.train.json")["args"]
    bwd = load_json("layers", "kernel.flash_bwd_roofline.train.json")["args"]
    pair = 2.0 * 32 * 8192 * 8192 * 128 / 2
    assert kernel_roofline.read(facts, **fwd) == pytest.approx(100 * (2 * pair / 197e12) / 5e-3, rel=1e-9)
    assert kernel_roofline.read(facts, **bwd) == pytest.approx(100 * (5 * pair / 197e12) / 9e-3, rel=1e-9)
    share = load_json("layers", "kernel.flash_share.train.json")
    assert op_share.read(facts, **share["args"]) == pytest.approx(100 * 14 / 25)
    windows = load_json("layers", "moe.expert_window_share.train.json")
    assert windows["layer"] == "experts" and windows["moves"] == "train_mfu"
    assert op_share.read(facts, **windows["args"]) == pytest.approx(100 * 5 / 25)
    # another window (a later PR's 512, SDAR's if it takes one) still reads; a trace with no window reads 0
    assert re.search(windows["args"]["pattern"], "%fusion.9 = bf16[512,2688] fusion")
    assert op_share.read({"trace": {"op_self_s": dict(calls[:2] + calls[-2:]), "busy_s": 1.0}}, **windows["args"]) == 0.0


# Names as the cell's traced run on the chip spelled them (my chip run, PR 34, seed 2034000431, the program as it
# is now; ``tracered.short_name``'s form), by what made them.
MIXER_NAMES = [
    "%convolution_bitcast_fusion.41 = bf16[1,8192,10304] fusion",                   # the in-projection
    "%select_add_fusion.126 = f32[2688,10304] fusion",                              # ... and its weight gradient
    "%fusion.1570 = (f32[2688,10304], f32[2688,10304], f32[2688,10304]) fusion",    # ... under AdamW
    "%fusion.2969 = bf16[1,8192,6144] fusion", "%slice_convert_fusion.66 = f32[1,8192,6144] fusion",   # the x | B | C stream
    "%multiply_convert_fusion.203 = (bf16[1,8192,6144], f32[1,8192,6144]) fusion",  # ... through the convolution
    "%multiply_reduce_fusion.73 = (bf16[6144], bf16[6144], bf16[6144], bf16[6144], bf16[6144], /*index=5*/f32[1,8192,6144]) fusion",
    "%broadcast_multiply_fusion.37 = (f32[1,8192,6144], f32[1,8192,6144], f32[1,8192,6144], f32[1,8192,6144]) fusion",
    "%fusion.2953 = (f32[4096], f32[1,8192,4096], bf16[1,8192,4096], bf16[4096]) fusion",      # the gate and the norm
    "%fusion.2829 = f32[1,8192,4096] fusion", "%reshape.9868 = f32[1,8192,4096] reshape",
    "%convolution_convert_fusion.26 = f32[8192,4096] fusion", "%broadcast.9498 = f32[8192,8,512] broadcast",
    "%reduce_window_sum.313 = f32[1,64,8,8,128] reduce-window",                     # the running sum of log-decays in a chunk
    "%fusion.2772 = f32[1,64,128,8,8,64] fusion", "%copy.2926 = f32[1,64,128,8,8,64] copy",
    "%fusion.2956 = (f32[64,8,8,128], f32[64,8,8,128], bf16[64,8,8,128,128]) fusion",          # scores under the heads' decay blocks
    "%multiply_reduce_fusion.82 = (f32[64,128,8,8], f32[64,128,8,8], bf16[1,64,128,8,8,64]) fusion",
    "%fusion.2884 = bf16[64,8,128,128] fusion", "%fusion.2887 = bf16[1,64,128,8,128] fusion", "%fusion.2955 = f32[64,128,8,8] fusion",
    "%convolution_bitcast_fusion.40 = f32[64,1,8,8,64,128] fusion", "%fusion.2948 = f32[64,8,8,64,128] fusion",   # the chunks' own states
    "%copy.2897 = f32[512,8,64,128] copy", "%convert_bitcast_fusion.64 = bf16[512,8,64,128] fusion",
    "%fusion.3105 = (f32[8,8], f32[1,8,8,64,128]) fusion", "%bitcast_add_fusion.55 = f32[1,8,8,64,128] fusion",   # the carried state
    "%copy-done.101 = bf16[128,8,64,128] copy-done", "%fusion.2820 = f32[1048576] fusion",
    "%ssd_fwd.3 = (bf16[1,8192,64,64], f32[1,64,64,64,128]) custom-call tpu_custom_call",      # a later kernel keeps it alive
    "%jvp_ssd_bwd_.7 = bf16[1,8192,64,64] custom-call tpu_custom_call",
]
OTHER_NAMES = [
    "%flash_fwd.14 = (bf16[1,32,8192,128], f32[1,32,8192,8]) custom-call tpu_custom_call",
    "%flash_bwd.14 = (bf16[1,32,8192,128], bf16[1,2,8192,128], bf16[1,2,8192,128]) custom-call tpu_custom_call",
    "%fusion.3065 = bf16[512,1856] fusion", "%fusion.3067 = bf16[512,2688] fusion", "%fusion.3162 = bf16[512] fusion",   # the experts' windows
    "%fusion.3060 = f32[8,1856,2688] fusion", "%select_add_fusion.120 = f32[8,2688,1856] fusion",
    "%ragged-dot-none.9 = bf16[16384,2688] custom-call tpu_custom_call",            # another cell's grouped products
    "%select_add_fusion.166 = f32[2688,16384] fusion", "%convolution_bitcast_fusion.43 = bf16[1,8192,16384] fusion",   # the head
    "%fusion.2805 = f32[1,8191,16384] fusion", "%slice_reduce_fusion.4 = (bf16[8191], f32[8191,16384]) fusion",
    "%select_add_fusion.157 = f32[4096,2688] fusion", "%select_add_fusion.144 = f32[2688,4096] fusion",    # W_q / W_o (and W_out: left out)
    "%convolution_reduce-precision_fusion.2 = bf16[8192,32,128] fusion", "%copy.77 = bf16[1,8192,32,128] copy",
    "%multiply_reduce_fusion.70 = (f32[32,8192], bf16[1,32,8192,128]) fusion",
    "%fusion.1444 = bf16[1,8192,4096] fusion",                                      # y in bf16 or attention's q: cannot tell
    "%fusion.3057 = bf16[8192,2688] fusion", "%fusion.2807 = (bf16[2688], f32[8192], bf16[8192,2688]) fusion",
    "%fusion.2798 = bf16[8192,3712] fusion", "%select_add_fusion.188 = f32[3712,2688] fusion",             # the shared expert
    "%sort.3 = (f32[8192,128], s32[8192,128]) sort", "%fusion.2817 = f32[49152] fusion", "%fusion.12 = f32[8,8] fusion",   # routing
]


def test_mixer_share_reads_the_mixers_operations_and_no_others():
    spec = load_json("layers", "ssm.mixer_share.train.json")
    assert spec["reader"] == "op_share" and spec["layer"] == "state-space" and spec["moves"] == "train_mfu"
    rx = re.compile(spec["args"]["pattern"])
    assert [n for n in MIXER_NAMES if not rx.search(n)] == []
    assert [n for n in OTHER_NAMES if rx.search(n)] == []
    ops = {**{n: 2e-3 for n in MIXER_NAMES}, **{n: 1e-3 for n in OTHER_NAMES}}
    busy = sum(ops.values())
    facts = {"trace": {"op_self_s": ops, "busy_s": busy}}
    assert op_share.read(facts, **spec["args"]) == pytest.approx(100 * 2e-3 * len(MIXER_NAMES) / busy)
    # another cell's trace, or the parent's, has none of these shapes: the share reads 0
    assert op_share.read({"trace": {"op_self_s": {n: 1e-3 for n in OTHER_NAMES}, "busy_s": 1.0}}, **spec["args"]) == 0.0


def test_reference_shares_no_code_with_the_program():
    tree = ast.parse(open(os.path.join(ROOT, "benchmark", "reference", "nemotron_h.py")).read())
    imported = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    imported += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    assert not any("pytorch_distributed_training_tpu" in m for m in imported), imported
    source = open(os.path.join(ROOT, "benchmark", "reference", "nemotron_h.py")).read()
    assert "cumsum" not in source and "ragged" not in source and "argsort" not in source    # no chunks, no sort
    assert 'default_matmul_precision("highest")' in source


def test_reference_recurrence_and_routing_follow_the_equations():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x, dt = jax.random.normal(k[0], (6, 4, 3)), jax.nn.softplus(jax.random.normal(k[1], (6, 4)))
    a, b, c = -jnp.arange(1.0, 5.0), jax.random.normal(k[2], (6, 2, 5)), jax.random.normal(k[3], (6, 2, 5))
    got = np.asarray(ref.recurrence(x, dt, a, b, c))
    state = np.zeros((4, 3, 5))
    for t in range(6):
        for h in range(4):
            state[h] = np.exp(dt[t, h] * a[h]) * state[h] + dt[t, h] * np.outer(x[t, h], b[t, h // 2])
            np.testing.assert_allclose(got[t, h], state[h] @ np.asarray(c[t, h // 2]), rtol=1e-5, atol=1e-6)
    cfg = {"num_experts_per_tok": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    rows = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    p = {"router": jax.random.normal(jax.random.PRNGKey(2), (8, 8)), "router_bias": jnp.zeros((8,)).at[3].set(5.0)}
    top_e, top_w = ref.route(rows, p, cfg)
    assert bool(jnp.all(jnp.any(top_e == 3, axis=-1)))                   # the bias selects ...
    chosen = jnp.take_along_axis(jax.nn.sigmoid(rows @ p["router"]), top_e, axis=-1)
    np.testing.assert_allclose(top_w, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)    # ... and weighs nothing


@pytest.mark.parametrize("wrong, over", [
    (None, set()),
    ("loss", {"loss"}), ("held", {"held_assignments"}),
    # a gradient of the right norm that points elsewhere: only its difference from the reference shows it
    ("['block_0']['mixer']['in_proj']['kernel']", {"grad_direction"}),
    ("['block_2']['mixer']['A_log']", {"grad_direction"}),
    # the routed experts' leaves have the looser limit: a fifth off passes, a whole norm off does not
    ("['block_1']['moe']['w_down']", set()), ("['block_1']['moe']['w_down'] far", {"routed_direction"}),
    ("norm ['embed']", {"grad_leaf", "grad_direction"}),
])
def test_kind_holds_every_reading_to_its_limit(wrong, over):
    from benchmark.kinds import train_parts as kind

    cell = load_json("workloads", CELL + ".json")
    check, parts = cell["reference_check"], tuple(cell["loss_parts"])
    want_norms = {"['block_0']['mixer']['in_proj']['kernel']": 2.0, "['block_2']['mixer']['in_proj']['kernel']": 1.0,
                  "['block_0']['mixer']['A_log']": 0.003, "['block_2']['mixer']['A_log']": 0.004,
                  "['block_1']['moe']['w_down']": 5.0, "['block_1']['moe']['router']": 1.0,
                  "['block_1']['moe']['router_bias']": 0.0, "['embed']": 300.0}
    sys_norms = {k: v * (1.2 if wrong == "norm " + k else 1.001) for k, v in want_norms.items()}
    diff = {k: v * 0.004 for k, v in want_norms.items()}
    if wrong in diff:
        diff[wrong] = 0.5 * want_norms[wrong] if "mixer" in wrong else 0.2 * want_norms[wrong]
    if wrong and wrong.endswith(" far"):
        diff[wrong[:-4]] = want_norms[wrong[:-4]]
    if wrong == "norm ['embed']":
        diff["['embed']"] = 60.0
    got = (10.2 * (1.01 if wrong == "loss" else 1.0), 12000.0 * (1.1 if wrong == "held" else 1.0), {})
    read = kind.all_readings(got, (10.2, 12010.0, np.array([10.2])), (sys_norms, want_norms, diff), parts)
    limits = kind.limits(check, parts)
    assert {k for k, limit in limits.items() if not read[k] <= limit} == over
    assert set(limits) == {"loss", "grad_leaf", "router_grad", "held_assignments", "grad_direction", "routed_direction"}
    # with parts named, each is read against the reference's vector in its order
    named = kind.all_readings((13.0, 1.0, {"mtp_loss": 10.0, "moe_balance_loss": 5.5e-4}),
                              (13.0, 1.0, np.array([9.9, 10.1, 5.0e-4])), (sys_norms, want_norms, diff),
                              ("mtp_loss", "moe_balance_loss"))
    assert named["mtp_loss"] == pytest.approx(0.1 / 10.1) and named["moe_balance_loss"] == pytest.approx(0.1)
    assert set(kind.limits({**check, "mtp_loss_rtol": 1e-3, "moe_balance_loss_rtol": 1e-2},
                           ("mtp_loss", "moe_balance_loss"))) == set(limits) | {"mtp_loss", "moe_balance_loss"}


def test_kind_rehearsal_counts_only():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.rehearse", "--workload", "tiny-nemotron-h.train.causal",
         "--seconds", "1", "--seed", "3000000019"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, out.stdout[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0 and "counters" in line["facts"]
    assert "metrics" not in line and "device" not in line
    assert "'moe_routed_assignments': " in out.stdout and "'moe_experts_held_per_layer': 4.0" in out.stdout
    for word in ("tokens/s", " ms", "mfu"):
        assert word not in out.stdout
