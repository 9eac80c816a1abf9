"""The causal convolution + SiLU's Pallas pair (``ops/causal_conv.py``) under
the CPU's interpreter: against a per-position loop and against the ``jnp``
form, forward and the three gradients; ``conv_plan``'s answers from shapes
alone; the mixer's traced program; and the names the benchmark's mixer share
reads."""

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
from pytorch_distributed_training_tpu.ops import causal_conv as cc
from test_ssd_pallas import MIXER, walk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# three time tiles of 128 and channel tiles of 128: the stream at column 128 of a wider array, three pieces
T, OFFSET, SPLITS, EXTRA = 384, 128, (256, 128, 128), 128
NAMES = ["x", "w", "bias"]


def conv_inputs(kernel, dtype=jnp.float32, bsz=2, t=T, offset=OFFSET, splits=SPLITS, extra=EXTRA):
    channels = sum(splits)
    k = jax.random.split(jax.random.PRNGKey(kernel), 3)
    wide = jax.random.normal(k[0], (bsz, t, offset + channels + extra)).astype(dtype)
    return (wide, jax.random.uniform(k[1], (kernel, channels), minval=-0.5, maxval=0.5),
            jax.random.uniform(k[2], (channels,), minval=-0.5, maxval=0.5))


def by_position(wide, w, bias, offset=OFFSET, splits=SPLITS):
    """The definition, a position and a tap at a time: no pad, no shift."""
    k, channels = w.shape
    x = wide[..., offset:offset + channels].astype(jnp.float32)
    rows = []
    for t in range(x.shape[1]):
        pre = bias
        for j in range(k):
            if t - k + 1 + j >= 0:
                pre = pre + w[j] * x[:, t - k + 1 + j]
        rows.append(pre * jax.nn.sigmoid(pre))
    y = jnp.stack(rows, axis=1).astype(wide.dtype)
    edges = np.cumsum((0,) + tuple(splits))
    return tuple(y[..., a:b] for a, b in zip(edges[:-1], edges[1:]))


def plain(wide, w, bias, offset=OFFSET, splits=SPLITS):
    """The ``jnp`` form ``causal_conv_silu`` falls back to."""
    return cc._conv_xla(wide, w, bias, offset, splits)


def pair(wide, w, bias, offset=OFFSET, splits=SPLITS):
    return cc.causal_conv_silu(wide, w, bias, offset=offset, splits=splits)


def cost(fn):
    """Every piece under another weight, so that a piece handed back in
    another's place shows."""
    return lambda *inputs: sum((i + 1.0) * jnp.sum(jnp.sin(piece.astype(jnp.float32)))
                               for i, piece in enumerate(fn(*inputs)))


def gradients(fn, args):
    return jax.grad(cost(fn), argnums=(0, 1, 2))(*args)


@pytest.mark.parametrize("kernel", [4, 2])
@pytest.mark.parametrize("what", ["y", "dx", "dw", "db"])
def test_the_pair_is_the_definition_in_float32(kernel, what):
    """Batch 2, three time tiles (both carries: the forward's tail, the
    backward's head), four channel tiles in three pieces (``dw`` and ``db``
    summed over the time tiles of each)."""
    args = conv_inputs(kernel)
    plan = cc.conv_plan(T, sum(SPLITS), kernel, 4, offset=OFFSET, splits=SPLITS)
    assert (plan.kind, plan.time_tile, plan.channel_tile) == ("pallas", 128, 128)
    if what == "y":
        for got, want, same in zip(pair(*args), by_position(*args), plain(*args)):
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
            np.testing.assert_allclose(got, same, rtol=2e-5, atol=2e-6)
        return
    at = ["dx", "dw", "db"].index(what)
    got, want, same = (gradients(fn, args)[at] for fn in (pair, by_position, plain))
    atol = 2e-5 * float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=atol)
    np.testing.assert_allclose(got, same, rtol=1e-4, atol=atol)
    if what == "dx":        # columns of the wider array the stream does not cover take no gradient
        assert not np.asarray(got[..., :OFFSET]).any() and not np.asarray(got[..., OFFSET + sum(SPLITS):]).any()


@pytest.mark.parametrize("kernel", [4, 2])
def test_the_pair_in_bf16_rounds_where_the_jnp_form_does(kernel):
    args = conv_inputs(kernel, jnp.bfloat16)
    exact_args = (args[0].astype(jnp.float32),) + args[1:]
    for got, same, exact in zip(pair(*args), plain(*args), by_position(*exact_args)):
        assert got.dtype == jnp.bfloat16
        # float32 inside both: they differ by the output's rounding where the two float32 sums differ in the last bit
        assert float(jnp.abs(got.astype(jnp.float32) - same.astype(jnp.float32)).max()) <= 2 ** -7 * float(jnp.abs(exact).max())
        miss = lambda y: float(jnp.sqrt(jnp.mean(jnp.square(y.astype(jnp.float32) - exact))))
        assert miss(got) <= 1.05 * miss(same) + 1e-6
    got_g, same_g, exact_g = (gradients(fn, a) for fn, a in ((pair, args), (plain, args), (by_position, exact_args)))
    for name, g_, s_, e_ in zip(NAMES, got_g, same_g, exact_g):
        assert g_.dtype == s_.dtype, name
        norm = float(jnp.linalg.norm(e_))
        off = lambda m: float(jnp.linalg.norm(m.astype(jnp.float32) - e_)) / norm
        assert off(g_) <= 1.25 * off(s_) + 1e-3, (name, off(g_), off(s_))
        assert off(g_) < 0.01, (name, off(g_))
    assert got_g[1].dtype == got_g[2].dtype == jnp.float32


def test_the_carries_over_three_time_tiles_are_not_small():
    """The first rows of a tile read the tile before, and the last rows of a
    tile take gradient from the tile after: a call on the last tile alone
    reads otherwise there, and nowhere else."""
    args = conv_inputs(4)
    whole = jnp.concatenate(pair(*args), axis=-1)
    alone = jnp.concatenate(pair(args[0][:, -128:], *args[1:]), axis=-1)
    assert float(jnp.abs(alone[:, :3] - whole[:, -128:-125]).max()) > 0.1
    np.testing.assert_allclose(alone[:, 3:], whole[:, -125:], rtol=1e-6, atol=1e-6)
    late = lambda fn: lambda *inputs: jnp.sum(jnp.sin(jnp.concatenate(fn(*inputs), axis=-1)[:, 256:]))
    got = jax.grad(late(pair))(*args)[..., OFFSET:OFFSET + sum(SPLITS)]
    want = jax.grad(late(by_position))(*args)[..., OFFSET:OFFSET + sum(SPLITS)]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    assert float(jnp.abs(got[:, 253:256]).max()) > 0.01 and not np.asarray(got[:, :253]).any()


@pytest.mark.parametrize("call, kind, why", [
    # T, C, K, itemsize, then offset= / splits= / backend=
    (dict(seq_len=8192, channels=6144, kernel=4, itemsize=2, offset=4096, splits=(4096, 1024, 1024)), "pallas", ""),   # the cell
    (dict(seq_len=256, channels=1024, kernel=4, itemsize=2, offset=512, splits=(512, 256, 256)), "pallas", ""),        # MIXER below
    (dict(seq_len=384, channels=512, kernel=2, itemsize=4), "pallas", ""),
    (dict(seq_len=8192, channels=6144, kernel=8, itemsize=2), "pallas", ""),
    (dict(seq_len=8200, channels=6144, kernel=4, itemsize=2), "xla", "length 8200"),                     # ragged length
    (dict(seq_len=64, channels=96, kernel=4, itemsize=4), "xla", "96 channels"),                         # tests/test_nemotron_h.py's toy
    (dict(seq_len=256, channels=1000, kernel=4, itemsize=2), "xla", "1000 channels"),                    # odd channels
    (dict(seq_len=256, channels=1024, kernel=4, itemsize=2, offset=64), "xla", "column spans"),          # a span off the lane tile
    (dict(seq_len=256, channels=512, kernel=4, itemsize=2, splits=(448, 64)), "xla", "column spans"),
    (dict(seq_len=8192, channels=6144, kernel=9, itemsize=2), "xla", "kernel 9"),
    (dict(seq_len=8192, channels=6144, kernel=4, itemsize=1), "xla", "elements of 1"),
])
def test_the_plan_is_a_function_of_the_shapes(call, kind, why):
    for backend in (None, "tpu", "cpu"):
        plan = cc.conv_plan(**call, backend=backend)
        assert plan.kind == kind and why in plan.why and bool(plan.why) == (kind == "xla"), plan
        assert plan.interpret == (kind == "pallas" and backend != "tpu")
    other = cc.conv_plan(**call, backend="gpu")
    assert other.kind == "xla" and (other.why == "backend gpu")
    source = inspect.getsource(cc.conv_plan) + inspect.getsource(cc.causal_conv_silu)
    assert "environ" not in source and "getenv" not in source and "nemotron" not in source.lower()


def test_the_cells_tiles_divide_every_span():
    plan = cc.conv_plan(8192, 6144, 4, 2, offset=4096, splits=(4096, 1024, 1024), backend="tpu")
    assert (plan.time_tile, plan.channel_tile, plan.interpret) == (512, 512, False)
    assert cc._pieces((4096, 1024, 1024), 512) == ((0, 8), (8, 10), (10, 12))


def test_a_call_at_a_refused_shape_runs_the_jnp_form_and_both_are_counted():
    before = cc.conv_plans_traced()
    args = conv_inputs(4, t=100)                       # a ragged length
    for got, same in zip(pair(*args), plain(*args)):
        np.testing.assert_array_equal(got, same)
    pair(*conv_inputs(4, t=128))
    after = cc.conv_plans_traced()
    assert after.get("xla", 0) == before.get("xla", 0) + 1
    assert after.get("pallas", 0) == before.get("pallas", 0) + 1
    from pytorch_distributed_training_tpu.obs.schema import METRICS
    assert METRICS["conv_plan"]["labeled"]           # the gauges ``conv_plan[kind=..]`` the CLI emits them as
    with pytest.raises(ValueError):
        cc.causal_conv_silu(args[0], *args[1:], offset=OFFSET, splits=(256, 128))


def test_every_kernel_of_the_convolution_is_launched_under_a_listed_name():
    tree = ast.parse(inspect.getsource(cc))
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call) and ast.unparse(n.func) == "pl.pallas_call"]
    names = [next(k.value.value for k in call.keywords if k.arg == "name") for call in calls]
    assert sorted(names) == sorted(cc.KERNEL_NAMES) == ["causal_conv_bwd", "causal_conv_fwd"]


def test_the_mixers_traced_program_holds_the_pair_and_no_padded_copy_outside_it():
    """One mixer layer at lane-aligned sizes of toy depth, bf16 activations:
    the gradient's jaxpr holds both kernels under ``ssm/conv`` (the backward
    under the transpose of that scope) and no float32 ``(…, T + K - 1, C)``
    pad of ``xBC`` — nor any float32 ``(…, T, C)`` — outside them."""
    net = models.create_model("nemotron_h_30b_a3b", dtype=jnp.bfloat16, cfg_overrides=MIXER)
    length, kernel = 256, 4
    channels = MIXER["mamba_num_heads"] * MIXER["mamba_head_dim"] + 2 * MIXER["n_groups"] * MIXER["ssm_state_size"]
    tokens = jnp.zeros((1, length), jnp.int32)
    params = net.init(jax.random.PRNGKey(0), tokens, train=False)["params"]
    assert params["block_0"]["mixer"]["conv_w"].shape == (kernel, channels)
    loss = lambda p: jnp.sum(net.apply({"params": p}, tokens, train=False).astype(jnp.float32) ** 2)
    eqns = list(walk(jax.make_jaxpr(jax.grad(loss))(params).jaxpr))
    kernels = {}
    for eqn, scope in eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"] if "name" in eqn.params else eqn.params["name_and_src_info"].name
            kernels.setdefault(name, []).append(scope)
    assert set(kernels) == {"causal_conv_fwd", "causal_conv_bwd", "ssd_fwd", "ssd_bwd"}, kernels
    for name in cc.KERNEL_NAMES:
        assert len(kernels[name]) == 1 and "ssm/conv" in kernels[name][0], kernels
    for eqn, scope in eqns:
        for var in eqn.outvars:
            shape, dtype = getattr(var.aval, "shape", ()), getattr(var.aval, "dtype", None)
            wide = len(shape) >= 2 and shape[-1] == channels and shape[-2] in (length, length + kernel - 1)
            assert not (wide and dtype == jnp.float32), (eqn.primitive.name, shape, scope)


def test_the_mixer_share_keeps_counting_the_convolution_as_custom_calls():
    """``benchmark/layers/ssm.mixer_share.train.json`` as committed (read, not
    edited): the pair's operations as a device trace spells them match by
    the shapes of their results, and ``mosaic_kernels`` counts them by name."""
    spec = json.load(open(os.path.join(ROOT, "benchmark", "layers", "ssm.mixer_share.train.json")))
    rx = re.compile(spec["args"]["pattern"])
    ours = [
        "%causal_conv_fwd.3 = (bf16[1,8192,4096], bf16[1,8192,1024], bf16[1,8192,1024]) custom-call tpu_custom_call",
        "%causal_conv_bwd.1 = (bf16[1,8192,6144], f32[1,4,6144], f32[1,1,6144]) custom-call tpu_custom_call",
    ]
    others = ["%flash_fwd.3 = (bf16[1,32,8192,128], f32[1,32,8192,8]) custom-call tpu_custom_call",
              "%fusion.1 = bf16[8192,2688] fusion"]
    assert rx.search(ours[1])
    # the forward hands back x, B and C, no array 6144 wide: it is counted by the scope share, ssm.conv_share.train
    assert [n for n in others if rx.search(n)] == []
    text = "\n".join(f'  {line.replace("custom-call tpu_custom_call", "custom-call(), custom_call_target=")}'
                     '"tpu_custom_call"' for line in ours + others[:1])
    assert mosaic_kernels(text) == {"causal_conv_fwd": 1, "causal_conv_bwd": 1, "flash_fwd": 1}
