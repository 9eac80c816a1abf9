"""The Qwen3-Next configuration's benchmark pieces at a toy size on the CPU:
the configuration file against the catalog row, the FLOPs function against
its hand count, the cut's parameters, the cell's sizes and limits, the
manifest's new entries against their files, the four linear-attention layer
files' scopes against ``obs.trace.PHASES`` and the rehearsal model's step,
the ``train_parts`` kind's readings against the cell's limits and the kind
through the test-only entry.  The reference against the system is
``tests/test_qwen3_next.py``'s."""

import ast
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.flops import qwen3_next as flops
from benchmark.harness import ROOT, load_json, load_manifest, model_overrides
from benchmark.readers import scope_share
from benchmark.reference import qwen3_next as ref
from pytorch_distributed_training_tpu import models
from pytorch_distributed_training_tpu.models import qwen3_next as qn
from pytorch_distributed_training_tpu.obs.cost import scope_table
from pytorch_distributed_training_tpu.obs.trace import PHASES
from test_scope_table import tiny_step

CONFIG = "qwen3-next-80b-a3b-instruct"
CELL = CONFIG + ".train.causal8k"
TINY = "tiny-qwen3-next.train.causal"
GDN = {"gdn.proj_share.train": "gdn/proj", "gdn.conv_share.train": "gdn/conv",
       "gdn.delta_share.train": "gdn/delta", "gdn.gate_share.train": "gdn/gate"}


def test_configuration_keeps_every_published_key_but_the_reduced():
    cfg = load_json("configs", CONFIG + ".json")
    published = {"hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16, "num_key_value_heads": 2,
                 "partial_rotary_factor": 0.25, "rope_theta": 10000000, "linear_num_key_heads": 16,
                 "linear_num_value_heads": 32, "linear_key_head_dim": 128, "linear_value_head_dim": 128,
                 "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
                 "num_experts_per_tok": 10, "norm_topk_prob": True, "full_attention_interval": 4,
                 "decoder_sparse_step": 1, "mlp_only_layers": [], "num_hidden_layers": 48, "rms_norm_eps": 1e-6,
                 "intermediate_size": 5120, "hidden_act": "silu", "tie_word_embeddings": False,
                 "model_type": "qwen3_next", "max_position_embeddings": 262144, "rope_scaling": None,
                 "use_sliding_window": False}
    assert {k: cfg[k] for k in published} == published
    assert cfg["source"] == "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
    assert sorted(cfg["reduced"]) == ["layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"layers": 48, "num_experts": 512, "vocab_size": 151936}
    assert cfg["layers"] == 4 and cfg["layers_run"] == flops.layers(cfg) == "LLLF"
    overrides = cfg["system"]["overrides"]
    assert overrides["num_experts"] == cfg["published"]["num_experts"]              # the router stays 512 wide
    assert overrides["experts_held"] == [0, cfg["num_experts"]] and cfg["num_experts"] == 32 >= 8
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_head"] == cfg["num_attention_heads"] and {"layers", "n_head", "model_flops"} <= set(cfg["notes"])
    assert "16 chips share each layer" in cfg["deployment"] and "32 a chip" in cfg["deployment"]
    assert {"projection_layout", "initialisation", "chunk", "float32_leaves", "weights", "optimizer"} <= set(cfg["assumed"])
    assert set(cfg["not_included"]) == {"mtp"}
    net = models.create_model("qwen3_next_80b_a3b", cfg_overrides=model_overrides(cfg))
    assert net.cfg == qn.Qwen3NextConfig(num_hidden_layers=4, vocab_size=18992, experts_held=(0, 32), remat=True)
    assert ref.experts_held(cfg) == (0, 32) and [ref.full_attention(cfg, i) for i in range(4)] == [False] * 3 + [True]


def test_the_cut_holds_626_million_parameters():
    cfg = load_json("configs", CONFIG + ".json")
    net = models.create_model("qwen3_next_80b_a3b", cfg_overrides=model_overrides(cfg))
    shapes = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32), train=False))
    sizes = {jax.tree_util.keystr(p): x.size for p, x in jax.tree_util.tree_leaves_with_path(shapes["params"])}
    total = sum(sizes.values())
    assert total == 625_667_136 and 10.00e9 < total * 16 < 10.02e9
    block = lambda i: sum(v for k, v in sizes.items() if k.startswith(f"['block_{i}']"))
    assert [block(i) for i in range(4)] == [138_582_208] * 3 + [132_127_232]
    assert sizes["['embed']"] == sizes["['lm_head']['kernel']"] == 18992 * 2048
    assert sizes["['block_0']['linear_attn']['in_proj_qkvz']['kernel']"] == 2048 * 12288
    assert sizes["['block_3']['self_attn']['q_proj']['kernel']"] == 2048 * 2 * 16 * 256
    assert sizes["['block_0']['moe']['w_gate']"] == 32 * 2048 * 512 and sizes["['block_0']['moe']['router']"] == 2048 * 512
    for text in ("138,582,208", "132,127,232", "625,667,136"):
        assert text in json.dumps(cfg["arithmetic"]) + json.dumps(cfg["reduced"])


def test_flops_function_against_the_hand_count_at_the_cuts_sizes():
    cfg = load_json("configs", CONFIG + ".json")
    linear = 2048 * (2 * 2048 + 2 * 4096 + 64) + 4096 * 2048
    attention = 2048 * 2 * 16 * 256 + 2 * 2048 * 2 * 256 + 16 * 256 * 2048
    experts = 2048 * 512 + 3 * 2048 * 512 + 2048 + 10 * 32 / 512 * 3 * 2048 * 512
    assert (linear, attention, experts) == (33_685_504, 27_262_976, 6_162_432)
    met = 3 * linear + attention + 4 * experts + 2048 * 18992
    assert met == 191_864_832
    forward = 2 * met + 3 * 2 * 4 * 8192 + 3 * 6 * 128 * 128 * 32 + 2 * 8192 * 16 * 256
    assert forward == 460_472_320 and 3 * forward == 1_381_416_960                 # 1.38 GFLOP a token
    assert flops.forward_flops_per_token(cfg, 8192) == forward
    assert flops.train_flops_per_sample(cfg, {"seq_len": 8192}) == 3 * forward * 8192 == 11_316_567_736_320
    assert flops.units_per_sample(cfg, {"seq_len": 8192}) == ("tokens", 8192.0)
    assert flops.expert_blocks(cfg) == cfg["layers"] == 4
    # the experts at the share that ran: a thirty-second instead of a sixteenth halves their part
    assert forward - flops.forward_flops_per_token(cfg, 8192, held_share=1 / 32) == 4 * 10 / 32 * 2 * 3 * 2048 * 512
    # the delta rule is the recurrence's own count, 6 d_k d_v a position and value head, whatever computes it
    no_rule = {**cfg, "linear_value_head_dim": 0}
    assert forward - flops.forward_flops_per_token(no_rule, 8192) == 3 * (
        6 * 128 * 128 * 32 + 2 * 2048 * 2 * 4096 + 2 * 4096 * 2048 + 2 * 4 * 4096)       # and v, z, W_out, v's conv
    for number in ("1,381,416,960", "191,864,832", "11,316,567,736,320", "460,472,320"):
        assert number in flops.__doc__
    assert "11,316,567,736,320" in cfg["notes"]["model_flops"]


def test_cell_is_as_specified():
    cell = load_json("workloads", CELL + ".json")
    assert cell["kind"] == "train_parts" and cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["loss_parts"] == [] and cell["counters"] == ["moe_held_assignments", "moe_load_max"]
    assert cell["step"] == {"samples": 4, "microbatches": 4, "seq_len": 8192}
    assert cell["input"]["source"] == "synthetic_tokens" and cell["input"]["num_workers"] == 0
    assert (cell["warmup_steps"], cell["calibration_steps"]) == (2, 3)
    assert cell["trace"]["annotations"] == ["train", "train/input_wait", "train/host_sync"]
    check = cell["reference_check"]
    assert 0 < check["loss_rtol"] <= 1e-3 and 0 < check["held_assignments_rtol"] <= 0.05
    assert 0 < check["grad_direction_rtol"] < check["routed_direction_rtol"] < 0.5 and len(check["reason"]) > 200
    # a fresh model: ln V + half the logits' variance at a 0.02 head (the final zero-centred norm starts at unit scale)
    first = cell["first_loss"]
    assert first["expected"] == pytest.approx(np.log(18992) + 2048 * 0.02 ** 2 / 2, abs=1e-4) == pytest.approx(10.2614, abs=1e-4)
    assert 0 < first["rtol"] <= 0.05 and len(first["reason"]) > 80
    assert "1/16" in cell["why"] and "16x" in cell["why"] and len(cell["why"]) <= 200


def test_the_manifest_gains_one_config_one_cell_and_four_metrics():
    manifest = load_manifest()
    spec = load_json("workloads", CELL + ".json")
    assert manifest["configs"][-1]["name"] == CONFIG and manifest["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "train.causal8k", "chips": 1, "why": spec["why"]}
    assert manifest["configs"][-1]["reduced"] == ["layers", "num_experts", "vocab_size"]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][-4:]] == list(GDN)
    for name in GDN:
        assert by_name[name] == {"name": name, "unit": "%", "better": "lower", "source": "program_span",
                                 "layer": "linear attention", "moves": "train_mfu", "workloads": [CELL]}
    reported = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert reported == {
        "train_mfu", "setup_s", "startup.compile_s", "startup.cache_misses",
        "input.data_wait_share.train", "input.wait_share.train", "loop.device_step_ms.train",
        "loop.compiles_in_window.train", "loop.head_share.train", "loop.optimizer_share.train",
        "loop.unscoped_share.train", "kernel.mosaic_share.train", "kernel.flash_fwd_roofline.train",
        "kernel.flash_bwd_roofline.train", "kernel.flash_share.train", "device.idle_share.train",
        "device.peak_hbm_gb.train", "moe.held_assignment_share.train", "moe.load_imbalance.train",
        "kernel.ragged_dot_roofline.train", "moe.grouped_matmul_share.train", "moe.route_share.train",
        "moe.experts_share.train", "moe.shared_share.train", "block.norm_share.train",
        "block.attn_proj_share.train", "block.attn_core_share.train", *GDN}
    # appended, never placed before another cell
    assert all(m["workloads"][-1] == CELL for m in manifest["per_layer"] + manifest["end_to_end"]
               if CELL in m.get("workloads", []))


@pytest.mark.parametrize("name", sorted(GDN))
def test_a_linear_attention_layer_file_reads_its_scope(name):
    spec = load_json("layers", name + ".json")
    assert spec == {"layer": "linear attention", "moves": "train_mfu", "unit": "%", "reader": "scope_share",
                    "args": {"scopes": [GDN[name]]}}
    assert GDN[name] in PHASES
    assert GDN[name] in set(scope_table(tiny_step(TINY)[-1]).values())


def test_the_rehearsal_models_step_has_every_scope_its_cells_entries_read():
    """What ``test_bench_scopes.py::test_an_entrys_cells_have_its_scopes``
    holds for the cells it knows by a rehearsal model (its table of those
    predates this cell): every scope-share entry that lists this cell finds
    one of its scopes in the rehearsal model's step, and the shares of all
    the entries add to a hundred there."""
    manifest = load_manifest()
    entries = [m for m in manifest["per_layer"] if load_json("layers", m["name"] + ".json")["reader"] == "scope_share"]
    table = scope_table(tiny_step(TINY)[-1])
    held = set(table.values())
    listed = [m for m in entries if CELL in m["workloads"]]
    assert {m["name"] for m in listed} == {
        "loop.head_share.train", "loop.optimizer_share.train", "loop.unscoped_share.train", "block.norm_share.train",
        "block.attn_proj_share.train", "block.attn_core_share.train", "moe.route_share.train",
        "moe.experts_share.train", "moe.shared_share.train", *GDN}
    for m in listed:
        scopes = load_json("layers", m["name"] + ".json")["args"]["scopes"]
        assert scopes is None or set(scopes) & held, m["name"]
    for m in entries:                      # and no entry that leaves this cell out reads a scope its step has
        scopes = load_json("layers", m["name"] + ".json")["args"]["scopes"]
        if m not in listed and scopes is not None:
            assert not set(scopes) & held, m["name"]
    names = [f"%{name} = f32[8,8] fusion" for name in table]
    facts = {"trace": {"op_self_s": dict.fromkeys(names, 1.0), "busy_s": float(len(names))}}
    shares = [scope_share.read(facts, **load_json("layers", m["name"] + ".json")["args"], table=table) for m in entries]
    small = sum(scope_share.read(facts, [name], table=table) for name in ("train/mtp", "train/noise"))
    assert sum(shares) + small == pytest.approx(100.0, abs=1e-9)


def test_reference_shares_no_code_with_the_program():
    source = open(os.path.join(ROOT, "benchmark", "reference", "qwen3_next.py")).read()
    tree = ast.parse(source)
    imported = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    imported += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    assert not any("pytorch_distributed_training_tpu" in m for m in imported), imported
    for chunked in ("cumsum", "triangular", "ragged", "argsort", "chunk"):          # no chunks, no solve, no sort
        assert chunked not in source, chunked
    assert 'default_matmul_precision("highest")' in source


@pytest.mark.parametrize("wrong, over", [
    (None, set()),
    ("loss", {"loss"}), ("held", {"held_assignments"}),
    # a gradient of the right norm that points elsewhere: only its difference from the reference shows it
    ("['block_0']['linear_attn']['in_proj_qkvz']['kernel']", {"grad_direction"}),
    ("['block_1']['linear_attn']['A_log']", {"grad_direction"}),
    # the routed experts' leaves have the looser limit: a small error passes, a whole norm off does not
    ("['block_0']['moe']['w_down']", set()), ("['block_0']['moe']['w_down'] far", {"routed_direction"}),
])
def test_kind_holds_every_reading_to_the_cells_limits(wrong, over):
    from benchmark.kinds import train_parts as kind

    cell = load_json("workloads", CELL + ".json")
    check, parts = cell["reference_check"], tuple(cell["loss_parts"])
    want_norms = {"['block_0']['linear_attn']['in_proj_qkvz']['kernel']": 2.0,
                  "['block_1']['linear_attn']['in_proj_qkvz']['kernel']": 1.0,
                  "['block_0']['linear_attn']['A_log']": 0.003, "['block_1']['linear_attn']['A_log']": 0.004,
                  "['block_0']['moe']['w_down']": 5.0, "['block_0']['moe']['router']": 1.0, "['embed']": 300.0}
    sys_norms = {k: v * 1.001 for k, v in want_norms.items()}
    diff = {k: v * 0.004 for k, v in want_norms.items()}
    limit = {"grad_direction": check["grad_direction_rtol"], "routed_direction": check["routed_direction_rtol"]}
    if wrong in diff:
        diff[wrong] = (1.5 * limit["grad_direction"] if "moe" not in wrong else 0.5 * limit["routed_direction"]) \
            * (want_norms[wrong] if "A_log" not in wrong else 0.005)
    if wrong and wrong.endswith(" far"):
        diff[wrong[:-4]] = want_norms[wrong[:-4]]
    got = (10.26 * (1.01 if wrong == "loss" else 1.0), 20000.0 * (1.1 if wrong == "held" else 1.0), {})
    read = kind.all_readings(got, (10.26, 20000.0, np.array([10.26])), (sys_norms, want_norms, diff), parts)
    limits = kind.limits(check, parts)
    assert {k for k, lim in limits.items() if not read[k] <= lim} == over
    assert set(limits) == {"loss", "grad_leaf", "router_grad", "held_assignments", "grad_direction", "routed_direction"}


def test_kind_rehearsal_counts_only():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.rehearse", "--workload", TINY, "--seconds", "1", "--seed", "3000000019"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, out.stdout[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0 and "counters" in line["facts"]
    assert "metrics" not in line and "device" not in line
    assert "'moe_routed_assignments': " in out.stdout and "'moe_experts_held_per_layer': 4.0" in out.stdout
    for word in ("tokens/s", " ms", "mfu"):
        assert word not in out.stdout
