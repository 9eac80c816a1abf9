"""The scope table of a compiled train step (``obs/cost.py::scope_table``,
``train/step.py::step_scopes``): every instruction -> the innermost
``obs.trace.PHASES`` name in its ``op_name``, a fusion by its product.  On the
CPU, on the tiny rehearsal models, through the step the benchmark's kinds build."""

import functools
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmark.harness import load_json, model_overrides
from pytorch_distributed_training_tpu import comm, models, train
from pytorch_distributed_training_tpu.obs.cost import innermost_scope, scope_census, scope_table
from pytorch_distributed_training_tpu.obs.trace import PHASES, scope
from pytorch_distributed_training_tpu.train import step as step_module
from pytorch_distributed_training_tpu.utils.compile_cache import compile_events

TINY = ("tiny-gpt2.train", "tiny-vit.train", "tiny-sdar.train.bd", "tiny-instella.train.causal",
        "tiny-nemotron-h.train.causal")
NEW = ("train/head", "block/norm", "block/mlp", "attn/proj", "attn/core", "ssm/proj")
WRAPPERS = ("grad_accum/microbatch", "train/loss")
# The scopes a tiny model's step must hold (what the benchmark's entries list its cell for).
EXPECTED = {
    "tiny-gpt2.train": {"train/head", "train/optimizer", "block/norm", "block/mlp", "attn/proj", "attn/core"},
    "tiny-vit.train": {"train/head", "train/optimizer", "block/norm", "block/mlp", "attn/proj", "attn/core"},
    "tiny-sdar.train.bd": {"train/head", "train/optimizer", "block/norm", "attn/proj", "attn/core", "moe/route",
                           "moe/experts", "train/noise"},
    "tiny-instella.train.causal": {"train/head", "train/optimizer", "block/norm", "block/mlp", "attn/mla",
                                   "attn/core", "moe/route", "moe/experts", "moe/shared", "train/mtp"},
    "tiny-nemotron-h.train.causal": {"train/head", "train/optimizer", "block/norm", "attn/proj", "attn/core",
                                     "moe/route", "moe/experts", "moe/shared", "ssm/conv", "ssm/scan", "ssm/gate",
                                     "ssm/proj"},
}


@functools.lru_cache(maxsize=None)
def tiny_step(name):
    """``(mesh, state, batch, step, compiled text)`` of a rehearsal cell's train
    step, built as the benchmark's kinds build it (the configuration's numbers
    through ``system.map``, ``make_train_step`` at the cell's microbatches)."""
    cell = load_json("rehearsal", name + ".json")
    config = load_json("rehearsal", cell["config"] + ".json")
    system, sizes = config["system"], cell["step"]
    task = system["task"]
    policy = train.make_policy(system["precision"]["train"])
    kw = {"cfg_overrides": model_overrides(config)}
    if task == "image_classifier":
        kw["num_classes"] = int(config["num_labels"])
    net = models.create_model(system["registry"], dtype=policy.compute_dtype, **kw)
    mesh = comm.make_mesh(comm.MeshConfig(data=-1), devices=jax.devices()[:1])
    samples = int(sizes["samples"])
    if task == "lm":
        sample = jnp.zeros((1, int(sizes["seq_len"])), jnp.int32)
        batch = {"tokens": jnp.zeros((samples, int(sizes["seq_len"])), jnp.int32)}
    else:
        side = int(sizes["image_size"])
        sample = jnp.zeros((1, side, side, 3), policy.compute_dtype)
        batch = {"image": jnp.zeros((samples, side, side, 3), jnp.uint8), "label": jnp.zeros((samples,), jnp.int32)}
    state = train.create_train_state(net, jax.random.PRNGKey(0), sample, optax.adamw(1e-3), mesh=mesh,
                                     init_kwargs={"train": False})
    fn = train.make_train_step(kind=task, policy=policy, num_microbatches=int(sizes["microbatches"]),
                               base_rng=jax.random.PRNGKey(1))
    # The session's compile cache may hold this program under an older tree's
    # op_names (its key leaves them out): for this compile they are in the key.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        with mesh:
            text = fn.lower(state, batch).compile().as_text()
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    return mesh, state, batch, fn, text


_LINE = re.compile(r'^\s+(?:ROOT )?%?([\w.\-]+) = .*?[\s)\]}]([a-z][\w\-]*)\(.*op_name="([^"]*)"', re.M)


def instructions(text):
    """``[(name, opcode, op_name)]`` of the text's instructions that carry an ``op_name``."""
    return _LINE.findall(text)


@pytest.mark.parametrize("path, scope_name", [
    ("jit(step)/grad_accum/microbatch/jvp(train/loss)/Net/block_0/moe/moe/experts/dot_general", "moe/experts"),
    ("jit(step)/grad_accum/microbatch/transpose(jvp(moe/experts))/while/body/jvp(moe/experts)/checkpoint/dot_general",
     "moe/experts"),
    ("jit(step)/grad_accum/microbatch/transpose(jvp(train/loss))/Net/block_1/attn/attn/block_diffusion/attn/core/"
     "shard_map/pjit/flash_bd_bwd/pallas_call", "attn/core"),
    ("jit(step)/grad_accum/microbatch/jvp(train/loss)/Net/block_1/attn/attn/block_diffusion/reshape",
     "attn/block_diffusion"),
    ("jit(step)/train/optimizer/mul", "train/optimizer"),
    ("jit(step)/grad_accum/microbatch/add", "grad_accum/microbatch"),
    ("jit(step)/grad_accum/microbatch/jvp(train/loss)/ssm/scan/mul;jit(step)/train/optimizer/add", "ssm/scan"),
    ("jit(step)/while/body/closed_call/add", None),
    # a module path that only looks like a scope: ``moe`` then ``router``, ``attn`` then ``core_norm``
    ("jit(step)/Net/block_0/moe/router/attn/core_norm/xattn/core/dot_general", None),
    ("", None),
])
def test_the_innermost_scope_of_a_path(path, scope_name):
    assert innermost_scope(path) == scope_name


# A step's text as a TPU compile prints it, cut to what the rule reads: the
# weight-gradient product fused with the float32 add that accumulates it (the
# add, and with it the fusion, sits in ``grad_accum/microbatch``), a fusion in
# a fusion, a loop body, a fusion of elementwise work only.
FUSED = '''HloModule jit_train_step, is_scheduled=true

%fused_computation.7.clone (param_0.1: bf16[512,64]) -> bf16[512,64,1] {
  %param_0.1 = bf16[512,64]{1,0} parameter(0)
  ROOT %bitcast.3 = bf16[512,64,1]{1,0,2} bitcast(%param_0.1), metadata={op_name="jit(train_step)/grad_accum/microbatch/jvp(train/loss)/Net/block_0/block/norm/ln/mul"}
}

%fused_computation.3 (param_0.2: f32[64,96], param_1.2: bf16[512,64], param_2.2: bf16[512,96]) -> f32[64,96] {
  %param_1.2 = bf16[512,64]{1,0} parameter(1)
  %fusion.11 = bf16[512,64,1]{1,0,2} fusion(%param_1.2), kind=kLoop, calls=%fused_computation.7.clone
  %param_2.2 = bf16[512,96]{1,0} parameter(2)
  %convolution.5 = f32[64,96]{1,0} convolution(%fusion.11, %param_2.2), window={size=1}, dim_labels=0bf_io0->0bf, metadata={op_name="jit(train_step)/grad_accum/microbatch/transpose(jvp(moe/experts))/while/body/jvp(moe/experts)/checkpoint/dot_general"}
  %param_0.2 = f32[64,96]{1,0} parameter(0)
  ROOT %add.9 = f32[64,96]{1,0} add(%param_0.2, %convolution.5), metadata={op_name="jit(train_step)/grad_accum/microbatch/add"}
}

%fused_computation.4 (param_0.3: f32[512,64]) -> f32[512] {
  %param_0.3 = f32[512,64]{1,0} parameter(0)
  %multiply.2 = f32[512,64]{1,0} multiply(%param_0.3, %param_0.3), metadata={op_name="jit(train_step)/grad_accum/microbatch/jvp(train/loss)/Net/block_0/attn/proj/mul"}
  ROOT %reduce.1 = f32[512]{0} reduce(%multiply.2), dimensions={1}, metadata={op_name="jit(train_step)/grad_accum/microbatch/jvp(train/loss)/Net/block_0/block/norm/ln/reduce_sum"}
}

%fused_computation.5 (param_0.4: f32[64,96]) -> (f32[64,96], f32[64,96]) {
  %param_0.4 = f32[64,96]{1,0} parameter(0)
  %multiply.3 = f32[64,96]{1,0} multiply(%param_0.4, %param_0.4)
  ROOT %tuple.8 = (f32[64,96]{1,0}, f32[64,96]{1,0}) tuple(%multiply.3, %param_0.4)
}

%fused_computation.6 (param_0.5: bf16[8,96], param_1.5: f32[8]) -> bf16[8,96] {
  %param_0.5 = bf16[8,96]{1,0} parameter(0)
  %convert.4 = f32[8,96]{1,0} convert(%param_0.5), metadata={op_name="jit(train_step)/grad_accum/microbatch/jvp(train/loss)/train/head/slice"}
  %param_1.5 = f32[8]{0} parameter(1)
  %sub.3 = f32[8,96]{1,0} broadcast(%param_1.5), dimensions={0}, metadata={op_name="jit(train_step)/grad_accum/microbatch/jvp(train/loss)/train/head/sub"}
  %constant.7 = f32[] constant(0), metadata={op_name="jit(train_step)/grad_accum/microbatch"}
  %exp.2 = f32[8,96]{1,0} exponential(%convert.4), metadata={op_name="jit(train_step)/grad_accum/microbatch/transpose(jvp(train/loss))/train/head/exp"}
  ROOT %convert.5 = bf16[8,96]{1,0} convert(%exp.2)
}

%region_1.2 (arg_tuple.1: (s32[], f32[64,96], bf16[512,64], bf16[512,96])) -> (s32[], f32[64,96], bf16[512,64], bf16[512,96]) {
  %arg_tuple.1 = (s32[], f32[64,96]{1,0}, bf16[512,64]{1,0}, bf16[512,96]{1,0}) parameter(0)
  %get-tuple-element.1 = f32[64,96]{1,0} get-tuple-element(%arg_tuple.1), index=1
  %select_add_fusion.4 = f32[64,96]{1,0} fusion(%get-tuple-element.1), kind=kOutput, calls=%fused_computation.3, metadata={op_name="jit(train_step)/grad_accum/microbatch/add"}
  %flash_bwd.2 = bf16[512,64]{1,0} custom-call(%get-tuple-element.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/grad_accum/microbatch/transpose(jvp(train/loss))/Net/block_0/attn/attn/core/jit(_flash_bwd)/flash_bwd/pallas_call"}
  %copy.7 = bf16[64,512]{0,1} copy(%flash_bwd.2)
  %ragged-dot-none.3 = bf16[512,96]{1,0} custom-call(%get-tuple-element.1, %select_add_fusion.4, /*index=2*/%select_add_fusion.4), custom_call_target="tpu_custom_call", frontend_attributes={mosaic_fusion_entry_point="true"}, metadata={op_name="ragged-dot-none"}
  %reshape.6 = bf16[49152]{0} reshape(%ragged-dot-none.3)
  %add_convert_fusion.2 = bf16[8,96]{1,0} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.6
  ROOT %tuple.2 = (s32[], f32[64,96]{1,0}, bf16[512,64]{1,0}, bf16[512,96]{1,0}) tuple(%get-tuple-element.1)
}

ENTRY %main.9 (Arg_0.1: f32[64,96], Arg_1.2: f32[512,64]) -> f32[64,96] {
  %Arg_1.2 = f32[512,64]{1,0} parameter(1)
  %fusion.20 = f32[512]{0} fusion(%Arg_1.2), kind=kInput, calls=%fused_computation.4
  %while.1 = (s32[], f32[64,96]{1,0}, bf16[512,64]{1,0}, bf16[512,96]{1,0}) while(%fusion.20), condition=%region_1.2, body=%region_1.2, metadata={op_name="jit(train_step)/while"}
  ROOT %multiply_add_fusion.1 = (f32[64,96]{1,0}, f32[64,96]{1,0}) fusion(%while.1), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(train_step)/train/optimizer/add"}
}
'''


@pytest.mark.parametrize("name, scope_name", [
    ("select_add_fusion.4", "moe/experts"),     # by its product, not by the add at its root
    ("flash_bwd.2", "attn/core"),               # a custom call in a loop body, its own path
    ("fusion.20", "block/norm"),                # no product: its root's
    ("multiply_add_fusion.1", "train/optimizer"),   # no product, a root without a path: its own
    ("add_convert_fusion.2", "train/head"),         # nor a path of its own (the compiler's cast at the root): most of its insides
    ("copy.7", "attn/core"),                        # the compiler's relayout, no path at all: its operand's
    ("ragged-dot-none.3", "moe/experts"),           # XLA's grouped matmul, renamed by its rewrite: most of its operands'
    ("reshape.6", "moe/experts"),                   # ... and along a chain of such
    ("while.1", None),                              # a loop takes none (its operand is fusion.20's)
    ("get-tuple-element.1", None),
])
def test_a_fusion_takes_its_products_scope(name, scope_name):
    table = scope_table(FUSED)
    assert table[name] == scope_name
    # the insides of a fused computation are no device events
    assert not {"convolution.5", "add.9", "fusion.11", "bitcast.3", "reduce.1", "multiply.3", "exp.2"} & set(table)
    assert scope_census(FUSED)["none"] == sum(v is None for v in table.values())


@pytest.mark.parametrize("name", TINY)
def test_products_land_in_leaf_scopes_forward_and_backward(name):
    """Every ``dot`` / ``convolution`` / custom call of a block is under a leaf
    scope, in the forward and in the backward (the hand-written backward of
    ``held_experts``, the scan's, attention's, under ``nn.remat`` where the
    rehearsal model rematerializes), and the step holds its model's scopes."""
    text = tiny_step(name)[-1]
    table = scope_table(text)
    heavy = [(n, op, path) for n, op, path in instructions(text)
             if op in ("dot", "convolution", "custom-call") and re.search(r"/(block_\d+|mtp_block)/", path)]
    assert len(heavy) >= 8
    sides = {}
    for n, op, path in heavy:
        assert table[n] in PHASES and table[n] not in WRAPPERS, (n, op, path)
        sides.setdefault(table[n], set()).add("transpose(" in path)
    assert all(both == {False, True} for both in sides.values()), sides
    assert EXPECTED[name] <= set(table.values())
    if "moe/experts" in EXPECTED[name]:
        assert any("transpose(" in path and table[n] == "moe/experts" for n, _, path in heavy)
    if name in ("tiny-sdar.train.bd", "tiny-instella.train.causal", "tiny-nemotron-h.train.causal"):
        assert "checkpoint" in text          # these rehearsal models rematerialize their blocks


@pytest.mark.parametrize("name", TINY)
def test_the_optimizers_instructions_take_train_optimizer(name):
    text = tiny_step(name)[-1]
    table = scope_table(text)
    updates = [n for n, _, path in instructions(text) if "/train/optimizer/" in path and n in table]
    assert len(updates) >= 20 and all(table[n] == "train/optimizer" for n in updates)
    assert scope_census(text)["train/optimizer"] >= len(updates)


def test_kernel_backwards_stay_in_their_scopes_under_remat():
    """The flash pair's and the state-space pair's ``custom_vjp`` backward,
    inside ``jax.checkpoint``: what the backward runs (the CPU's interpreter
    makes plain operations of the kernels) carries the scope round the call."""
    from pytorch_distributed_training_tpu.ops.attention import dot_product_attention
    from pytorch_distributed_training_tpu.ops.ssd import ssd_chunked, ssd_plan

    q = jnp.ones((1, 256, 2, 64), jnp.bfloat16)

    @jax.checkpoint
    def attend(q):
        return dot_product_attention(q, q, q, causal=True, use_flash=True)

    x = jnp.ones((1, 128, 2, 64), jnp.bfloat16)
    steps, decay = jnp.full((1, 128, 2), 0.1, jnp.float32), -jnp.ones((2,), jnp.float32)
    b = jnp.ones((1, 128, 1, 128), jnp.bfloat16)
    assert ssd_plan(128, 2, 1, 64, 128, 128, 2).kind == "pallas"

    @jax.checkpoint
    def scan(x):
        with scope("ssm/scan"):
            return ssd_chunked(x, steps, decay, b, b, chunk=128)

    loss = lambda q, x: jnp.sum(attend(q).astype(jnp.float32)) + jnp.sum(scan(x).astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(q, x).compile().as_text()
    table = scope_table(text)
    products = [(n, path) for n, op, path in instructions(text) if op == "dot" and n in table]
    # (the sum's gradient needs no forward value: what is left is the rematerialized forward and the backward)
    for wanted, kernel in (("attn/core", "flash_bwd"), ("ssm/scan", "ssd_bwd")):
        mine = [path for n, path in products if table[n] == wanted]
        assert any("transpose(" in path and "checkpoint" in path and kernel in path for path in mine), wanted
    assert all(table[n] in ("attn/core", "ssm/scan") for n, _ in products)


@pytest.mark.parametrize("name", NEW)
def test_the_new_names_are_phases_and_in_some_step(name):
    assert name in PHASES
    assert any(name in scope_table(tiny_step(tiny)[-1]).values() for tiny in TINY)


def run_epochs(name, tmp_path, profile):
    """Two epochs of three steps through ``Trainer.run_epoch``; with
    ``profile`` the second holds a capture of its middle step."""
    mesh, state, batch, fn, _ = tiny_step(name)
    state = jax.tree_util.tree_map(jnp.copy, state)          # the step donates its state
    trainer = train.Trainer(state, fn, mesh, train.TrainerConfig(progress=False, prefetch=0))
    trainer.run_epoch([batch] * 3, epoch=0)
    if profile:
        trainer.config.profile_dir, trainer.config.profile_steps = str(tmp_path / "trace"), (4, 5)
    before = len(compile_events())
    trainer.run_epoch([batch] * 3, epoch=1)
    return compile_events(before)


def test_nothing_is_remembered_and_nothing_compiles_without_a_capture(tmp_path):
    step_module.note_capture(True)
    step_module.note_capture(False)          # a capture in which no step ran
    assert step_module.step_scopes() is None
    assert run_epochs("tiny-gpt2.train", tmp_path, profile=False) == []
    assert step_module._capture["noted"] is None and step_module.step_scopes() is None
    assert not [e for e in compile_events() if e.get("phase") == "trace/scopes"]


@pytest.mark.parametrize("name", ["tiny-gpt2.train", "tiny-nemotron-h.train.causal"])
def test_the_step_that_ran_in_a_capture_gives_its_table(name, tmp_path):
    in_epoch = run_epochs(name, tmp_path, profile=True)
    assert not [e for e in in_epoch if e["what"] == "backend_compile"]       # nothing compiles inside the loop
    jitted, _, args = step_module._capture["noted"]
    assert all(isinstance(leaf, jax.ShapeDtypeStruct) for leaf in jax.tree_util.tree_leaves(args))   # nothing live
    before = len(compile_events())
    table = step_module.step_scopes()
    asked = compile_events(before)
    assert {e["phase"] for e in asked} <= {"trace/scopes"}
    # (the same shapes and shardings: JAX answers from memory, or from the cache the step's own compile wrote)
    assert table == scope_table(tiny_step(name)[-1])
    assert step_module.step_scopes() is table and len(compile_events()) == before + len(asked)    # compiled once
    assert jax.config.jax_compilation_cache_include_metadata_in_key is False
    step_module.note_capture(True)           # the next capture forgets this one's step
    step_module.note_capture(False)
    assert step_module.step_scopes() is None


def test_a_loaded_executable_with_another_trees_names_is_compiled_again(tmp_path, monkeypatch):
    """The persistent cache's key leaves ``op_name`` out, so a run may LOAD an
    executable that carries another tree's scopes.  Where the lowered text
    names a scope the compiled text lacks, the step is compiled once more
    with the metadata in the key, and the table is that compile's: the same
    instruction names, this tree's scopes."""
    run_epochs("tiny-gpt2.train", tmp_path, profile=True)
    named = step_module.scopes_named
    calls = []

    def stale_once(text):              # the first asked is the lowered text: one scope more than the executable has
        calls.append(len(text))
        return named(text) | ({"ssm/proj"} if len(calls) == 1 else set())

    monkeypatch.setattr(step_module, "scopes_named", stale_once)
    before = len(compile_events())
    table = step_module.step_scopes()
    asked = compile_events(before)
    assert len(calls) == 2
    assert [e for e in asked if e["what"] == "backend_compile" and e["phase"] == "trace/scopes"]
    assert table == scope_table(tiny_step("tiny-gpt2.train")[-1])
    assert jax.config.jax_compilation_cache_include_metadata_in_key is False
