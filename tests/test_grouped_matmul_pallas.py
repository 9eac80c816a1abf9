"""The held experts' grouped products as Pallas kernels
(``ops/grouped_matmul.py``) under the CPU's interpreter: ``gmm``, the data
gradient and ``tgmm`` against ``lax.ragged_dot`` and its ``jax.grad``;
``held_experts`` through the ``pallas`` plan against the ``xla`` plan;
``grouped_plan``'s answers from shapes alone; the traced program; and the
names the benchmark's two expert metrics read."""

import ast
import inspect
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from pytorch_distributed_training_tpu.models import moe
from pytorch_distributed_training_tpu.obs.cost import mosaic_kernels
from pytorch_distributed_training_tpu.ops import grouped_matmul as gm
from test_ssd_pallas import walk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D_IN, D_OUT = 256, 128
# rows of a pass and each group's.  512 rows are ONE tile in four runs of 128; 384 are three tiles of 128:
# ``ends_in_a_tile`` stops at row 200 (in tile 1; tile 2 is never visited), ``three_in_a_tile`` has rows of groups
# 0, 1, 2 and 3 in tile 1
ROUTINGS = {
    "even": (512, (128, 128, 128, 128)),
    "skew": (512, (32, 36, 384, 28, 32)),            # one group with 8 x the others' mean share and more
    "one_has_all": (384, (0, 384, 0, 0)),
    "one_has_none": (384, (130, 0, 126, 128)),
    "ends_in_a_tile": (384, (100, 60, 40)),
    "three_in_a_tile": (384, (140, 30, 40, 150)),
}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def operands(routing, dtype, seed=0):
    rows, sizes = ROUTINGS[routing]
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (rows, D_IN)).astype(dtype),
            (0.1 * jax.random.normal(k[1], (len(sizes), D_IN, D_OUT))).astype(dtype),
            jax.random.normal(k[2], (rows, D_OUT)).astype(dtype),
            jax.random.normal(k[3], (len(sizes), D_IN, D_OUT)),
            jnp.asarray(sizes, jnp.int32))


def live_rows(routing):
    rows, sizes = ROUTINGS[routing]
    return (jnp.arange(rows) < sum(sizes))[:, None]


def close(got, want, dtype, what=""):
    """float32: the same sums in another order; bf16: both round a float32 sum once."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    tol = 2e-5 if dtype == jnp.float32 else 2 ** -7
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * float(np.abs(want).max()), err_msg=what)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("routing", ROUTINGS)
def test_the_three_kernels_are_ragged_dot_and_its_transposes(routing, dtype):
    dtype = DTYPES[dtype]
    lhs, w, dy, into, sizes = operands(routing, dtype)
    plan = gm.grouped_plan(lhs.shape[0], D_IN, D_OUT, w.shape[0], dtype)
    assert (plan.kind, plan.row_tile, plan.interpret) == ("pallas", 512 if lhs.shape[0] == 512 else 128, True)
    live = live_rows(routing)
    masked = lambda m: jnp.where(live, m, 0)            # the rows past the last group are no one's
    close(masked(gm.grouped_matmul(lhs, w, sizes)), masked(lax.ragged_dot(lhs, w, sizes)), dtype, "gmm")
    close(masked(gm.grouped_matmul(dy, w, sizes, transposed=True)),
          masked(lax.ragged_dot(dy, jnp.swapaxes(w, 1, 2), sizes)), dtype, "data gradient")
    sums = lax.ragged_dot_general(lhs, dy, sizes, gm._BY_GROUP, preferred_element_type=jnp.float32)
    stack, carry = into.astype(dtype), jnp.full((D_IN, D_OUT), 7.0)
    got, carried = gm.grouped_weight_grad(lhs, dy, sizes, stack, carry, False, False)
    assert got.dtype == dtype and carried.dtype == jnp.float32
    np.testing.assert_array_equal(carried, carry)       # no group goes on: nothing is handed on
    for g, size in enumerate(ROUTINGS[routing][1]):     # a group with rows is written, one with none not touched
        if size:
            close(got[g], sums[g], dtype, f"tgmm, group {g}")
        else:
            np.testing.assert_array_equal(got[g], stack[g])


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("routing", ROUTINGS)
def test_the_products_gradient_is_ragged_dots(routing, dtype):
    """``jax.grad`` through the custom VJP: the data gradient by the same
    kernel over the matrix's other axis, the weight gradient by ``tgmm``
    from zero, in the stack's dtype."""
    dtype = DTYPES[dtype]
    lhs, w, dy, _, sizes = operands(routing, dtype, seed=1)
    live = live_rows(routing)
    cost = lambda product: lambda l, w: jnp.sum(
        jnp.where(live, product(l, w, sizes).astype(jnp.float32) * dy.astype(jnp.float32), 0))
    got = jax.grad(cost(gm.grouped_matmul), (0, 1))(lhs, w)
    want = jax.grad(cost(lax.ragged_dot), (0, 1))(lhs, w)
    assert got[0].dtype == got[1].dtype == dtype
    close(jnp.where(live, got[0], 0), jnp.where(live, want[0], 0), dtype, "d_lhs")
    close(got[1], want[1], dtype, "d_w")
    back = lambda product: lambda l, w: jnp.sum(
        jnp.where(live, product(l, w).astype(jnp.float32) * lhs.astype(jnp.float32), 0))
    got = jax.grad(back(lambda l, w: gm.grouped_matmul(l, w, sizes, transposed=True)), (0, 1))(dy, w)
    want = jax.grad(back(lambda l, w: lax.ragged_dot(l, jnp.swapaxes(w, 1, 2), sizes)), (0, 1))(dy, w)
    close(jnp.where(live, got[0], 0), jnp.where(live, want[0], 0), dtype, "transposed d_lhs")
    close(got[1], want[1], dtype, "transposed d_w")


@pytest.mark.parametrize("plan", ["pallas", "xla"])
def test_two_passes_weight_gradients_add_through_the_carried_block(plan):
    """Group 1's rows straddle two passes: the first hands its float32 sum
    on, the second starts from it and writes the whole; group 0 ends in the
    first pass and group 2 in the second, each written once; group 3 has rows
    in neither and keeps what the stack held.  The ``xla`` form (a width off
    the lane tile) says the same."""
    d_out = D_OUT if plan == "pallas" else 64
    lhs, _, dy, into, _ = operands("even", jnp.float32)
    dy, into = dy[:, :d_out], into[..., :d_out]
    assert gm.grouped_plan(512, D_IN, d_out, 4, jnp.float32).kind == plan
    first, second = jnp.asarray((200, 100, 0, 0), jnp.int32), jnp.asarray((0, 150, 300, 0), jnp.int32)
    stack, carry = gm.grouped_weight_grad(lhs, dy, first, into, jnp.zeros((D_IN, d_out)), False, True)
    sums = [lax.ragged_dot_general(lhs, dy, s, gm._BY_GROUP, preferred_element_type=jnp.float32) for s in (first, second)]
    close(carry, sums[0][1], jnp.float32, "the block handed on")
    np.testing.assert_array_equal(stack[2:], into[2:])
    stack, _ = gm.grouped_weight_grad(lhs, dy, second, stack, carry, True, False)
    close(stack[:3], jnp.stack([sums[0][0], sums[0][1] + sums[1][1], sums[1][2]]), jnp.float32)
    np.testing.assert_array_equal(stack[3], into[3])
    # a pass with no row at all changes nothing
    same, kept = gm.grouped_weight_grad(lhs, dy, jnp.zeros((4,), jnp.int32), stack, carry, False, False)
    np.testing.assert_array_equal(same, stack)
    np.testing.assert_array_equal(kept, carry)
    if plan == "pallas":        # both results are their operands' buffers
        (call,) = [e for e, _ in walk(jax.make_jaxpr(lambda *a: gm._wgrad_call(*a, 128, True))(
            lhs, dy, first, into, carry, jnp.zeros((2,), jnp.int32)).jaxpr) if e.primitive.name == "pallas_call"]
        assert tuple(call.params["input_output_aliases"]) == ((8, 0), (9, 1))


def _held(gated, dtype, rows, plan, act="silu", rounded=None):
    """``held_experts``' value and its four gradients at lane-aligned toy
    sizes, 4 of 8 experts held: 187 held assignments of 384 (``rounded``:
    the inputs' values as that dtype holds them, computed in ``dtype``)."""
    t, k, e, first, held, d, f = 192, 2, 8, 2, 4, 128, 256
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    cast = lambda m: m.astype(rounded or dtype).astype(dtype)
    tokens = cast(jax.random.normal(keys[0], (t, d)))
    weights, experts, _ = moe.topk_route(jax.random.normal(keys[1], (t, e)), k)
    order, counts = moe.group_held_assignments(experts, first, held)
    shapes = [(held, d, f)] * (2 if gated else 1) + [(held, f, d)]
    stacks = tuple(cast(0.1 * jax.random.normal(key, shape)) for key, shape in zip(keys[2:], shapes))

    def cost(tokens, weights, stacks):
        out = moe.held_experts(tokens, weights, order, counts, stacks, rows, act, False)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

    with pytest.MonkeyPatch.context() as patch:
        if plan == "xla":
            refuse = lambda *a, **kw: gm.GroupedPlan("xla", "the test's", 0, False)
            patch.setattr(gm, "grouped_plan", refuse)
            patch.setattr(moe, "grouped_plan", refuse)
        (_, out), grads = jax.value_and_grad(cost, (0, 1, 2), has_aux=True)(tokens, weights, stacks)
    assert int(counts.sum()) == 187
    return {"value": out, "d_tokens": grads[0], "d_weights": grads[1],
            **{f"d_stack{i}": g for i, g in enumerate(grads[2])}}


@pytest.mark.parametrize("rows", [256, 128])                # one pass, and two whose weight gradients add
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("form", ["gated_silu", "plain_relu2"])
def test_held_experts_through_the_kernels_is_held_experts_through_ragged_dot(form, dtype, rows):
    dtype, gated = DTYPES[dtype], form == "gated_silu"
    act = form.split("_")[1]
    before = gm.grouped_plans_traced()
    ours, theirs = (_held(gated, dtype, rows, plan, act) for plan in ("pallas", "xla"))
    after = gm.grouped_plans_traced()
    # 2 calls forward (into the experts' width with the gate, back with the weight); backward 3 and a weight
    # gradient a stack
    assert after.get("pallas", 0) - before.get("pallas", 0) == (8 if gated else 7)
    assert after.get("xla", 0) > before.get("xla", 0)
    assert sorted(ours) == sorted(theirs) and len(ours) == (6 if gated else 5)
    exact = theirs if dtype == jnp.float32 else _held(gated, jnp.float32, rows, "xla", act, rounded=dtype)
    for name in ours:
        assert ours[name].dtype == theirs[name].dtype, name
        if dtype == jnp.float32:
            close(ours[name], theirs[name], dtype, name)
            continue
        # bf16: the kernels keep a run's products in float32 through the activation and the gate where the other
        # form rounds each array it hands on, so they are held to be no farther from the float32 answer
        off = lambda got: float(jnp.linalg.norm(got[name].astype(jnp.float32) - exact[name])
                                / jnp.linalg.norm(exact[name]))
        assert off(ours) <= 1.1 * off(theirs) + 1e-4 and off(ours) < 0.01, (name, off(ours), off(theirs))


@pytest.mark.parametrize("call, kind, why", [
    # rows, d_in, d_out, groups, dtype
    ((16384, 2048, 768, 16, jnp.bfloat16), "pallas", ""),          # SDAR's cell: ROWS_CHUNK rows of 16 held experts
    ((16384, 2048, 1408, 8, jnp.bfloat16), "pallas", ""),          # Instella's
    ((16384, 768, 2048, 16, jnp.bfloat16), "pallas", ""),          # the down-projection's side
    ((384, 128, 256, 4, jnp.float32), "pallas", ""),
    ((512, 2688, 1856, 8, jnp.bfloat16), "xla", "widths 2688 x 1856"),      # Nemotron-H's expert: 14.5 lane tiles
    ((16384, 2688, 1856, 8, jnp.bfloat16), "xla", "widths 2688 x 1856"),
    ((128, 64, 32, 4, jnp.float32), "xla", "widths 64 x 32"),               # tests/test_moe.py's toy widths
    ((64, 128, 128, 4, jnp.float32), "xla", "64 rows"),
    ((1000, 128, 128, 4, jnp.float32), "xla", "1000 rows"),
    ((512, 128, 128, 4, jnp.int8), "xla", "elements of int8"),
    ((512, 128, 128, 4, jnp.float16), "xla", "elements of float16"),
    ((512, 65536, 128, 4, jnp.float32), "xla", "fits VMEM"),               # a row tile alone is past the blocks' share
])
def test_the_plan_is_a_function_of_the_shapes(call, kind, why):
    for backend in (None, "tpu", "cpu"):
        plan = gm.grouped_plan(*call, backend=backend)
        assert plan.kind == kind and why in plan.why and bool(plan.why) == (kind == "xla"), plan
        assert plan.interpret == (kind == "pallas" and backend != "tpu")
    other = gm.grouped_plan(*call, backend="gpu")
    assert other.kind == "xla" and other.why == "backend gpu"
    source = "".join(inspect.getsource(f) for f in (gm.grouped_plan, gm.grouped_matmul, gm.grouped_weight_grad))
    assert "environ" not in source and "getenv" not in source
    assert not any(name in source.lower() for name in ("sdar", "instella", "nemotron"))


def test_the_cells_blocks_fit_and_every_stack_is_read_whole():
    """Both cells' stack blocks whole in every kernel, at the widest calls an
    expert layer makes: two products of one operand with five more blocks of
    the experts' width (the backward's second run), two products of two
    operands back into the model's width, and ``tgmm``'s float32 sum of a
    group (Instella's 11.5 MB) beside the rounded block's two buffers.  A
    float32 stack's block goes in two."""
    assert gm._stack_tiles(512, 2048, 1408, 4) == (1024, 1408)
    for d, f in ((2048, 768), (2048, 1408)):
        assert gm.grouped_plan(moe.ROWS_CHUNK, d, f, 8, jnp.bfloat16, backend="tpu").row_tile == 512
        assert gm._column_tile(512, (d,), (d, d), f, 5, 2) == f and gm._column_tile(512, (f, f), (f, f), d, 1, 2) == d
        assert gm._stack_tiles(512, d, f, 2) == (d, f) and gm._stack_tiles(512, f, d, 2) == (f, d)
    assert gm._VMEM_BLOCKS < gm._VMEM_BYTES <= 96 * 2**20


@pytest.mark.parametrize("dtype", DTYPES)
def test_products_and_their_epilogue_in_one_pass_over_the_rows(dtype):
    """``grouped_products``: two products of one operand and the rows' own
    operands — one the results' width, one a number a row — through an
    epilogue, and a result a row; two products of two operands summed.  The
    ``xla`` form (a width off the lane tile) says the same."""
    dtype = DTYPES[dtype]
    lhs, w, dy, _, sizes = operands("three_in_a_tile", dtype)
    w2, per_row = w[::-1], jnp.arange(lhs.shape[0], dtype=jnp.float32)[:, None] / 100
    live = live_rows("three_in_a_tile")

    def epilogue(products, extras):
        both = products[0] * jnp.tanh(products[1]) + extras[0] * extras[1]
        return both, jnp.sum(both, axis=-1, keepdims=True)

    outs = ((jnp.dtype(dtype).name, False), ("float32", True))
    got = gm.grouped_products((lhs,), (w, w2), sizes, epilogue, outs, extras=(dy, per_row))
    products = [lax.ragged_dot(lhs, m, sizes, preferred_element_type=jnp.float32) for m in (w, w2)]
    want = epilogue(products, [dy.astype(jnp.float32), per_row])
    assert got[0].shape == (lhs.shape[0], D_OUT) and got[1].shape == (lhs.shape[0], 1) and got[1].dtype == jnp.float32
    for g_, w_ in zip(got, want):
        close(jnp.where(live, g_, 0), jnp.where(live, w_, 0), dtype)
    summed = lambda products, _: (products[0] + products[1],)
    back = gm.grouped_products((dy, 2 * dy), (w, w2), sizes, summed, outs[:1], lhs_of=(0, 1), transposed=True,
                               name=gm.DGRAD)[0]
    want = sum(lax.ragged_dot(l, jnp.swapaxes(m, 1, 2), sizes, preferred_element_type=jnp.float32)
               for l, m in ((dy, w), (2 * dy, w2)))
    close(jnp.where(live, back, 0), jnp.where(live, want, 0), dtype)
    off_tile = gm.grouped_products((lhs,), (w[..., :64], w2[..., :64]), sizes, epilogue, outs,
                                   extras=(dy[:, :64], per_row))
    want = epilogue([p[:, :64] for p in products], [dy[:, :64].astype(jnp.float32), per_row])
    close(jnp.where(live, off_tile[0], 0), jnp.where(live, want[0], 0), dtype)


def test_the_table_of_visits_follows_the_live_rows():
    """Five groups over four 128-row tiles, the third with none: a tile is
    visited once for each group with rows in it, in order, and the visits
    past the last live row repeat the last one."""
    group, tile, starts, ends, count = gm._visits(jnp.asarray((140, 30, 0, 40, 150), jnp.int32), 4, 128)
    assert int(count[0]) == 6 and group.shape == tile.shape == (4 + 5 - 1,)
    assert group.tolist() == [0, 0, 1, 3, 4, 4, 4, 4] and tile.tolist() == [0, 1, 1, 1, 1, 2, 2, 2]
    assert starts.tolist() == [0, 140, 170, 170, 210] and ends.tolist() == [140, 170, 170, 210, 360]
    nothing = gm._visits(jnp.zeros((3,), jnp.int32), 2, 128)
    assert int(nothing[4][0]) == 0 and nothing[0].tolist() == [2] * 4 and nothing[1].tolist() == [0] * 4


def test_every_kernel_is_launched_under_a_listed_name_the_benchmarks_pattern_reads():
    """``benchmark/layers/kernel.ragged_dot_roofline.train.json`` and
    ``moe.grouped_matmul_share.train.json`` as committed (read, not edited):
    the three calls as a device trace spells them match, under no prefix;
    ``mosaic_kernels`` counts names with hyphens."""
    tree = ast.parse(inspect.getsource(gm))
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call) and ast.unparse(n.func) == "pl.pallas_call"]
    names = sorted(ast.unparse(next(k.value for k in call.keywords if k.arg == "name")) for call in calls)
    assert names == ["ADD", "WGRAD", "name"]             # the products' launcher is told its role by the caller
    assert gm.KERNEL_NAMES == (gm.FWD, gm.REFWD, gm.DGRAD, gm.WGRAD, gm.ADD) == (
        "ragged-dot-held-fwd", "ragged-dot-held-refwd", "ragged-dot-held-dgrad", "ragged-dot-held-wgrad",
        "held-rows-add")
    given = {ast.unparse(k.value) for n in ast.walk(ast.parse(inspect.getsource(gm)) ) if isinstance(n, ast.Call)
             for k in n.keywords if k.arg == "name"} | {
        ast.unparse(k.value) for n in ast.walk(ast.parse(inspect.getsource(moe))) if isinstance(n, ast.Call)
        and ast.unparse(n.func) == "grouped_products" for k in n.keywords if k.arg == "name"}
    assert given <= {"name", "ADD", "WGRAD", "DGRAD if transposed else FWD", "REFWD", "DGRAD"}, given
    ours = ["%ragged-dot-held-fwd.3 = bf16[16384,768] custom-call tpu_custom_call",
            "%ragged-dot-held-dgrad.12 = bf16[16384,2048] custom-call tpu_custom_call",
            "%ragged-dot-held-refwd.1 = (bf16[16384,768], bf16[16384,768], bf16[16384,768], f32[16384,1]) custom-call tpu_custom_call",
            "%ragged-dot-held-wgrad.2 = (bf16[16,2048,768], f32[2048,768]) custom-call tpu_custom_call",
            "%ragged-dot-none = bf16[16384,768] custom-call tpu_custom_call"]         # XLA's own, as before
    others = ["%jvp_ragged-dot-held-fwd.1 = bf16[16384,768] custom-call tpu_custom_call",
              "%held-rows-add.3 = bf16[8192,2048] custom-call tpu_custom_call",       # the combine is no product
              "%flash_bd_fwd.3 = (bf16[1,32,8192,128], f32[1,32,8192,8]) custom-call tpu_custom_call",
              "%ragged-dot-held-fwd.3 = bf16[16384,768] fusion"]
    for layer in ("kernel.ragged_dot_roofline.train", "moe.grouped_matmul_share.train"):
        spec = json.load(open(os.path.join(ROOT, "benchmark", "layers", layer + ".json")))
        rx = re.compile(spec["args"]["pattern"])
        assert all(rx.search(n) for n in ours) and not any(rx.search(n) for n in others), layer
    text = "\n".join(f'  {line.replace("custom-call tpu_custom_call", "custom-call(), custom_call_target=")}'
                     '"tpu_custom_call"' for line in ours + others[1:3])
    assert mosaic_kernels(text) == {"ragged-dot-held-fwd": 1, "ragged-dot-held-dgrad": 1, "ragged-dot-held-refwd": 1,
                                    "ragged-dot-held-wgrad": 1, "ragged-dot-none": 1, "held-rows-add": 1,
                                    "flash_bd_fwd": 1}


def test_an_expert_layers_traced_program_holds_the_kernels_under_its_scope():
    """``TopKMoe`` at lane-aligned toy sizes, bf16: the gradient's jaxpr
    holds the forward's 2 calls (3 products), and in the backward the
    down-projection's data gradient, the 2 products into the experts' width
    again with the derivatives in their epilogue, their 2 data gradients
    summed in one call, and 3 weight gradients — 11 products where the model
    counts 9 — and the combine forward and backward, all under
    ``moe/experts``, and no ``ragged_dot`` nor scatter-add of rows beside
    them."""
    layer = moe.TopKMoe(num_experts=8, num_experts_per_tok=2, mlp_dim=256, experts_held=(2, 4), dtype=jnp.bfloat16)
    x = jnp.zeros((1, 128, 128), jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    loss = lambda p, x: jnp.sum(layer.apply({"params": p}, x, mutable=["moe_counters"])[0].astype(jnp.float32) ** 2)
    eqns = list(walk(jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr))
    kernels = {}
    for eqn, scope in eqns:
        assert eqn.primitive.name not in ("ragged_dot", "ragged_dot_general"), scope
        assert not (eqn.primitive.name == "scatter-add" and eqn.outvars[0].aval.shape == (128, 128)), scope
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"] if "name" in eqn.params else eqn.params["name_and_src_info"].name
            kernels.setdefault(name, []).append(scope)
    assert {name: len(at) for name, at in kernels.items()} == {
        "ragged-dot-held-fwd": 2, "ragged-dot-held-refwd": 1, "ragged-dot-held-dgrad": 2, "ragged-dot-held-wgrad": 3,
        "held-rows-add": 2}, kernels
    assert all("moe/experts" in scope for at in kernels.values() for scope in at), kernels
    from pytorch_distributed_training_tpu.obs.schema import METRICS
    assert METRICS["grouped_plan"]["labeled"]         # the gauges ``grouped_plan[kind=..]`` the CLI emits them as


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("tokens, rows, n_live", [(64, 384, 300), (64, 384, 384), (200, 512, 1), (64, 256, 0)])
def test_the_combine_is_a_scatter_add_of_the_live_rows(tokens, rows, n_live, dtype):
    """``add_rows`` against ``.at[].add``: tokens met several times in one
    run of rows, a live count that ends inside a run, dead rows that hold
    anything (NaN here), and what the result held before."""
    dtype = DTYPES[dtype]
    k = jax.random.split(jax.random.PRNGKey(tokens + n_live), 3)
    token_of = jax.random.randint(k[0], (rows,), 0, tokens)
    live = (jnp.arange(rows) < n_live)[:, None]
    values = jnp.where(live, jax.random.normal(k[1], (rows, 256)), jnp.nan).astype(dtype)
    into = jax.random.normal(k[2], (tokens, 256)).astype(dtype)
    assert gm.add_rows_plan(tokens, rows, 256, dtype).kind == "pallas"
    got = gm.add_rows(into, token_of, values, jnp.asarray(n_live))
    want = into.astype(jnp.float32).at[token_of].add(jnp.where(live, values, 0).astype(jnp.float32))
    assert got.dtype == dtype
    close(got, want, dtype)
    off_tile = gm.add_rows(into[:, :64], token_of, values[:, :64], jnp.asarray(n_live))      # the ``xla`` form
    assert gm.add_rows_plan(tokens, rows, 64, dtype).why.startswith("width 64")
    close(off_tile, want[:, :64], dtype)


def test_the_combines_plan_is_a_function_of_the_shapes():
    for backend in ("tpu", "cpu"):
        plan = gm.add_rows_plan(8192, moe.ROWS_CHUNK, 2048, jnp.bfloat16, backend=backend)       # both cells' call
        assert (plan.kind, plan.row_tile, plan.interpret) == ("pallas", 512, backend == "cpu")
    for call, why in (((8192, 16384, 2688, jnp.bfloat16), ""), ((8192, 16384, 1856, jnp.bfloat16), "width 1856"),
                      ((8192, 100, 2048, jnp.bfloat16), "100 rows"), ((30, 512, 2048, jnp.float32), "30 tokens"),
                      ((8192, 512, 2048, jnp.int8), "elements of int8"), ((2 ** 20, 512, 2048, jnp.float32), "fits VMEM")):
        plan = gm.add_rows_plan(*call, backend="tpu")
        assert (plan.kind == "xla") == bool(why) and why in plan.why, plan
    assert gm.add_rows_plan(8192, 16384, 2048, jnp.bfloat16, backend="gpu").why == "backend gpu"


@pytest.mark.parametrize("data, tensor", [(4, 1), (2, 2)])
def test_under_a_mesh_of_several_devices_every_device_runs_the_whole_product(data, tensor):
    """Sorted rows have no batch axis: under GSPMD the kernels go into a
    ``shard_map`` with everything whole, and the results are the unsharded
    call's."""
    from pytorch_distributed_training_tpu import comm

    lhs, w, dy, into, sizes = operands("three_in_a_tile", jnp.float32)
    both = lambda *a: (gm.grouped_matmul(a[0], a[1], a[4]), gm.grouped_matmul(a[2], a[1], a[4], transposed=True),
                       gm.grouped_weight_grad(a[0], a[2], a[4], a[3], a[3][0], False, False)[0])
    want = both(lhs, w, dy, into, sizes)
    mesh = comm.make_mesh(comm.MeshConfig(data=data, tensor=tensor), devices=jax.devices()[:data * tensor])
    with mesh:
        got = jax.jit(both)(lhs, w, dy, into, sizes)
        assert str(jax.make_jaxpr(both)(lhs, w, dy, into, sizes)).count("shard_map") == 3
    live = live_rows("three_in_a_tile")
    for g_, w_ in zip(got, want):
        mask = live if g_.shape[0] == lhs.shape[0] else True
        np.testing.assert_allclose(jnp.where(mask, g_, 0), jnp.where(mask, w_, 0), rtol=1e-6, atol=1e-6)
