"""The chunked scan's Pallas pair (``ops/ssd.py``) under the CPU's interpreter:
against the position-by-position recurrence and against the ``jnp`` form,
forward and all five gradients; ``ssd_plan``'s answers from shapes alone; the
mixer's traced program; and the names the benchmark's mixer share reads."""

import ast
import inspect
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu import models
from pytorch_distributed_training_tpu.obs.cost import mosaic_kernels
from pytorch_distributed_training_tpu.ops import ssd
from test_nemotron_h import recurrence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK, P, N = 128, 64, 128
INPUTS = "x dt a b c".split()


def scan_inputs(t, g, r, dtype=jnp.float32, bsz=2, rate=3.0):
    """Steps of about exp(-``rate``) against decays A = -h / H: at ``rate`` 3
    a chunk of 128 keeps a quarter to a hundredth of the state it was given."""
    h = g * r
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    wide = lambda key: (jax.random.normal(key, (bsz, t, g, N)) * N ** -0.25).astype(dtype)
    return (jax.random.normal(k[0], (bsz, t, h, P)).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (bsz, t, h)) - rate),
            -jnp.arange(1.0, h + 1.0) / h, wide(k[2]), wide(k[3]))


def cost(fn):
    return lambda *inputs: jnp.sum(jnp.sin(fn(*inputs).astype(jnp.float32)))


def gradients(fn, args):
    return jax.grad(cost(fn), argnums=(0, 1, 2, 3, 4))(*args)


pair = lambda *inputs: ssd.ssd_chunked(*inputs, chunk=CHUNK)
plain = lambda *inputs: ssd._ssd_xla(*inputs, CHUNK)


@pytest.mark.parametrize("t, g, r", [(256, 1, 1), (256, 1, 2), (512, 2, 2), (256, 2, 2)])
def test_the_pair_is_the_recurrence_forward_and_backward(t, g, r):
    args = scan_inputs(t, g, r)
    assert ssd.ssd_plan(t, g * r, g, P, N, CHUNK, 4).kind == "pallas"
    want = recurrence(*args)
    top = float(jnp.abs(want).max())
    np.testing.assert_allclose(pair(*args), want, rtol=2e-5, atol=2e-5 * top)
    np.testing.assert_allclose(pair(*args), plain(*args), rtol=2e-5, atol=2e-5 * top)
    for name, got, want_g, plain_g in zip(INPUTS, gradients(pair, args), gradients(recurrence, args),
                                          gradients(plain, args)):
        atol = 1e-4 * float(jnp.abs(want_g).max())
        np.testing.assert_allclose(got, want_g, rtol=1e-4, atol=atol, err_msg=name)
        np.testing.assert_allclose(got, plain_g, rtol=1e-4, atol=atol, err_msg=name + " against the jnp form")


def test_the_state_the_pair_carries_over_four_chunks_is_not_small():
    """Slow steps: the fourth chunk's output is mostly what the first three
    left — the last chunk alone, from a zero state, reads otherwise — and the
    first chunk's inputs take gradient from the last chunk's outputs."""
    args = scan_inputs(512, 2, 2, rate=5.0)
    want = recurrence(*args)
    got = pair(*args)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5 * float(jnp.abs(want).max()))
    alone = pair(*(m[:, -CHUNK:] if m.ndim > 1 else m for m in args))
    assert float(jnp.abs(alone - want[:, -CHUNK:]).max()) > 0.05 * float(jnp.abs(want).max())
    late = lambda fn: lambda *inputs: jnp.sum(jnp.sin(fn(*inputs)[:, -CHUNK:]))
    got_g = jax.grad(late(pair), argnums=(0, 1, 2, 3, 4))(*args)
    want_g = jax.grad(late(recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    for name, g_, w_ in zip(INPUTS, got_g, want_g):
        np.testing.assert_allclose(g_, w_, rtol=1e-4, atol=1e-4 * float(jnp.abs(w_).max()), err_msg=name)
    first = float(jnp.abs(got_g[0][:, :CHUNK]).max())
    assert first > 0.01 * float(jnp.abs(got_g[0]).max()), first


def test_the_pair_in_bf16_keeps_float32_steps_and_rounds_where_the_jnp_form_does():
    args = scan_inputs(512, 2, 2, dtype=jnp.bfloat16)
    assert args[1].dtype == args[2].dtype == jnp.float32
    exact = recurrence(*(m.astype(jnp.float32) for m in args))
    got, same = pair(*args), plain(*args)
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.abs(exact).max())
    # both forms round operands to bf16 in the same places: they differ by an output rounding or two, and
    # stand equally far from the float32 recurrence
    assert float(jnp.abs(got.astype(jnp.float32) - same.astype(jnp.float32)).max()) <= 2 ** -6 * scale
    miss = lambda y: float(jnp.sqrt(jnp.mean(jnp.square(y.astype(jnp.float32) - exact))))
    assert miss(got) <= 1.1 * miss(same) + 1e-6
    got_g, same_g, exact_g = (gradients(fn, a) for fn, a in (
        (pair, args), (plain, args), (recurrence, tuple(m.astype(jnp.float32) for m in args))))
    for name, g_, s_, e_ in zip(INPUTS, got_g, same_g, exact_g):
        assert g_.dtype == s_.dtype, name
        norm = float(jnp.linalg.norm(e_))
        off = lambda m: float(jnp.linalg.norm(m.astype(jnp.float32) - e_)) / norm
        assert off(g_) <= 1.25 * off(s_) + 1e-3, (name, off(g_), off(s_))
        assert off(g_) < 0.02, (name, off(g_))


@pytest.mark.parametrize("shape, kind", [
    # T, H, G, P, N, chunk, itemsize
    ((8192, 64, 8, 64, 128, 128, 2), "pallas"),        # the cell
    ((512, 4, 2, 64, 128, 128, 4), "pallas"),
    ((256, 1, 1, 64, 128, 128, 4), "pallas"),          # one group: any width is the whole array's
    ((256, 2, 2, 64, 128, 128, 4), "xla"),             # a head of 64 columns a group splits a lane tile
    ((64, 8, 2, 8, 16, 16, 4), "xla"),                 # tests/test_nemotron_h.py's toy shapes
    ((64, 8, 2, 8, 16, 32, 4), "xla"),
    ((8192, 64, 8, 64, 128, 64, 2), "xla"),            # half a lane tile of positions
    ((8192, 64, 8, 64, 128, 2048, 2), "xla"),          # a (Q, Q) block VMEM cannot hold a dozen of
    ((256, 2, 1, 64, 128, 128, 2), "pallas"),          # one group of one slab
    ((256, 6, 2, 64, 128, 128, 2), "xla"),             # three heads a group: a slab of two leaves one over
    # what no model here has, the kernels were not written for
    ((256, 2, 1, 128, 128, 128, 2), "xla"),            # heads of a whole lane tile
    ((256, 8, 2, 32, 128, 128, 2), "xla"),             # heads of a quarter
    ((256, 4, 2, 64, 256, 128, 2), "xla"),             # a state of two lane tiles
    ((256, 4, 1, 64, 64, 128, 2), "xla"),              # a state of half a one
])
def test_the_plan_is_a_function_of_the_shapes(shape, kind):
    assert ssd.ssd_plan(*shape).kind == kind
    assert ssd.ssd_plan(*shape, backend="tpu").kind == kind
    assert ssd.ssd_plan(*shape, backend="gpu").kind == "xla"
    source = inspect.getsource(ssd.ssd_plan) + inspect.getsource(ssd.ssd_chunked)
    assert "environ" not in source and "getenv" not in source


@pytest.mark.parametrize("data, tensor", [(2, 2), (4, 1), (1, 4)])
def test_under_a_mesh_of_several_devices_the_pair_runs_a_shard(data, tensor):
    """A Mosaic call cannot be partitioned: under GSPMD the pair goes into a
    ``shard_map``, the batch over ``data`` and the groups over ``tensor``
    where each divides (4 ways, neither does: every device runs the whole).
    ``a``'s gradient is summed over the batch's shards."""
    from pytorch_distributed_training_tpu import comm

    args = scan_inputs(256, 2, 2)
    both = lambda *inputs: (pair(*inputs),) + gradients(pair, inputs)
    want = both(*args)
    mesh = comm.make_mesh(comm.MeshConfig(data=data, tensor=tensor), devices=jax.devices()[:data * tensor])
    with mesh:
        program = jax.jit(both)
        got = program(*args)
        assert "shard_map" in str(jax.make_jaxpr(both)(*args))
    for name, g_, w_ in zip(["y"] + INPUTS, got, want):
        np.testing.assert_allclose(g_, w_, rtol=1e-6, atol=1e-6 * float(jnp.abs(w_).max()), err_msg=name)


def test_a_call_at_a_refused_shape_runs_the_jnp_form_and_both_are_counted():
    before = ssd.ssd_plans_traced()
    args = scan_inputs(256, 2, 1)                      # a head of 64 columns a group
    np.testing.assert_array_equal(pair(*args), plain(*args))
    pair(*scan_inputs(256, 1, 1))
    after = ssd.ssd_plans_traced()
    assert after.get("xla", 0) == before.get("xla", 0) + 1
    assert after.get("pallas", 0) == before.get("pallas", 0) + 1
    from pytorch_distributed_training_tpu.obs.schema import METRICS
    assert METRICS["ssd_plan"]["labeled"]            # the gauges ``ssd_plan[kind=..]`` the CLI emits them as


def test_every_kernel_of_the_scan_is_launched_under_a_listed_name():
    tree = ast.parse(inspect.getsource(ssd))
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call) and ast.unparse(n.func) == "pl.pallas_call"]
    names = [next(k.value.value for k in call.keywords if k.arg == "name") for call in calls]
    assert sorted(names) == sorted(ssd.KERNEL_NAMES) == ["ssd_bwd", "ssd_fwd"]


MIXER = dict(vocab_size=512, hidden_size=128, hybrid_override_pattern="M", num_hidden_layers=1,
             mamba_num_heads=8, mamba_head_dim=64, n_groups=2, ssm_state_size=128, chunk_size=128,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16, n_routed_experts=8,
             num_experts_per_tok=2, moe_intermediate_size=32, moe_shared_expert_intermediate_size=64)


def walk(jaxpr, scope=""):
    """Every equation of a jaxpr and of the jaxprs inside it — but a
    ``pallas_call``'s body, which is the kernel's own and lives in VMEM —
    with the name stack it sits under."""
    for eqn in jaxpr.eqns:
        yield eqn, scope + "/" + str(eqn.source_info.name_stack)
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from walk(sub, scope + "/" + str(eqn.source_info.name_stack))


def test_the_mixers_traced_program_holds_the_pair_and_no_block_outside_it():
    """One mixer layer at lane-aligned sizes of toy depth, bf16 activations:
    the gradient's jaxpr."""
    net = models.create_model("nemotron_h_30b_a3b", dtype=jnp.bfloat16, cfg_overrides=MIXER)
    tokens = jnp.zeros((1, 256), jnp.int32)
    params = net.init(jax.random.PRNGKey(0), tokens, train=False)["params"]
    loss = lambda p: jnp.sum(net.apply({"params": p}, tokens, train=False).astype(jnp.float32) ** 2)
    eqns = list(walk(jax.make_jaxpr(jax.grad(loss))(params).jaxpr))
    kernels = {}
    for eqn, scope in eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"] if "name" in eqn.params else eqn.params["name_and_src_info"].name
            kernels.setdefault(name, []).append(scope)
    # (the convolution's pair, under ``ssm/conv``, is tests/test_causal_conv_pallas.py's)
    assert {name for name in kernels if name.startswith("ssd_")} == {"ssd_fwd", "ssd_bwd"}, kernels
    assert all("ssm/scan" in scope for name in ssd.KERNEL_NAMES for scope in kernels[name]), kernels
    for eqn, scope in eqns:
        assert eqn.primitive.name not in ("reduce_window", "reduce_window_sum", "cumsum", "cumlogsumexp"), (eqn, scope)
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            block = len(shape) >= 2 and shape[-2:] == (128, 128) and var.aval.dtype == jnp.float32
            # (a group's entering states are (4 x 64, 128) here: no block's shape)
            assert not block or "ssm/scan" not in scope, (eqn.primitive.name, shape, scope)


def test_the_mixer_share_keeps_counting_the_scan_as_custom_calls():
    """``benchmark/layers/ssm.mixer_share.train.json`` as committed: the
    pair's operations as a device trace spells them, the operations around
    them that carry the steps' layouts, and none of another layer's."""
    spec = json.load(open(os.path.join(ROOT, "benchmark", "layers", "ssm.mixer_share.train.json")))
    rx = re.compile(spec["args"]["pattern"])
    ours = [
        "%ssd_fwd.7 = (bf16[1,8192,4096], f32[1,8,64,512,128]) custom-call tpu_custom_call",
        "%ssd_bwd.2 = (bf16[1,8192,4096], bf16[1,8192,1024], bf16[1,8192,1024], f32[1,8,8192,8], f32[1,8,8,8192], "
        "f32[1,8,8192,8]) custom-call tpu_custom_call",
    ]
    others = ["%flash_fwd.3 = (bf16[1,32,8192,128], f32[1,32,8192,8]) custom-call tpu_custom_call",
              "%flash_bwd.3 = (bf16[1,32,8192,128], bf16[1,2,8192,128], bf16[1,2,8192,128]) custom-call tpu_custom_call",
              "%fusion.1 = bf16[8192,2688] fusion"]
    assert [n for n in ours if not rx.search(n)] == []
    assert [n for n in others if rx.search(n)] == []
    text = "\n".join(f'  {line.replace("custom-call tpu_custom_call", "custom-call(), custom_call_target=")}'
                     '"tpu_custom_call"' for line in ours + others[:2])
    assert mosaic_kernels(text) == {"ssd_fwd": 1, "ssd_bwd": 1, "flash_fwd": 1, "flash_bwd": 1}
