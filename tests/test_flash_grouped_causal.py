"""The grouped native-layout flash pair (k_len 513..1024): under ``causal`` a
q block takes the key row's prefix up to its frontier and masks only the
columns the diagonal crosses.  Interpret mode, two heads of 64, lengths that
still take the grouped path."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.ops import flash_attention
from pytorch_distributed_training_tpu.ops import pallas_attention as pa
from pytorch_distributed_training_tpu.ops.attention import _xla_attention

CASES = {
    # name: (q_len, k_len, causal)
    "causal_square": (1024, 1024, True),
    "causal_square_640": (640, 640, True),
    "causal_q_shorter": (384, 768, True),       # causal_offset 384
    "causal_padded_kv_len": (700, 700, True),   # padded to 768, kv_len 700
    "causal_q_shorter_padded": (200, 900, True),
    "causal_fully_masked_rows": (768, 640, True),  # rows 0..127 see no key
    "non_causal": (640, 640, False),
    "non_causal_padded": (520, 700, False),
}


@pytest.fixture
def grouped_launches(monkeypatch):
    """Configurations the grouped launchers were handed, forward first."""
    seen = []
    for name in ("_flash_fwd_grouped", "_flash_bwd_grouped"):
        real = getattr(pa, name)

        def spy(*args, _real=real):
            seen.append(args[-1])
            return _real(*args)

        monkeypatch.setattr(pa, name, spy)
    return seen


@pytest.mark.parametrize("case", CASES)
def test_grouped_pair_matches_xla(case, grouped_launches):
    q_len, k_len, causal = CASES[case]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    q = jax.random.normal(kq, (1, q_len, 2, 64))
    k = jax.random.normal(kk, (1, k_len, 2, 64))
    v = jax.random.normal(kv, (1, k_len, 2, 64))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, interpret=True)

    def ref(q, k, v):
        return _xla_attention(q, k, v, causal=causal)

    np.testing.assert_allclose(flash(q, k, v), ref(q, k, v), atol=3e-5, rtol=3e-5)
    # a weighted sum, so that every output element has a gradient of its own
    w = jax.random.normal(jax.random.PRNGKey(1), (1, q_len, 2, 64))
    loss = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) * w)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=3e-4, err_msg=f"d{name}")
    assert len(grouped_launches) >= 2, "the call did not take the grouped pair"
    if case == "causal_fully_masked_rows":
        assert not np.any(np.asarray(flash(q, k, v))[:, :128])
        assert not np.any(np.asarray(got[0])[:, :128])


def test_bf16_causal_square_close_to_f32_reference():
    """The precisions the train step runs: bf16 operands, f32 statistics."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(x, (2, 1024, 2, 64)) for x in (kq, kk, kv))
    got = flash_attention(*(x.astype(jnp.bfloat16) for x in (q, k, v)),
                          causal=True, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _xla_attention(
        *(x.astype(jnp.bfloat16).astype(jnp.float32) for x in (q, k, v)),
        causal=True,
    )
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("q_len,k_len,offset,kv_len", [
    (1024, 1024, 0, None), (384, 768, 384, None), (768, 768, 0, 700),
    (768, 640, -128, None), (640, 640, 0, None), (1024, 256, -768, None),
])
@pytest.mark.parametrize("block_q", [128, 256])
def test_causal_spans_against_the_dense_mask(q_len, k_len, offset, kv_len, block_q):
    """Every q block lies in one span; columns ``[0, full)`` hold no masked
    pair, columns past ``visit`` no live one, and both are as tight as 128
    columns allow — by the dense mask itself."""
    rows, cols = np.arange(q_len)[:, None], np.arange(k_len)[None, :]
    dense = (rows + offset >= cols) & (cols < (k_len if kv_len is None else kv_len))
    spans = pa._causal_spans(q_len, k_len, block_q, offset, kv_len)
    blocks = [qi for first, last, _, _ in spans for qi in range(first, last + 1)]
    assert blocks == list(range(q_len // block_q))
    for first, last, full, visit in spans:
        assert full % 128 == 0 and visit % 128 == 0 and full <= visit <= k_len
        for qi in range(first, last + 1):
            tile = dense[qi * block_q:(qi + 1) * block_q]
            assert tile[:, :full].all() and not tile[:, visit:].any()
            assert visit == 0 or tile[:, visit - 128:visit].any()
            assert full == visit or not tile[:, full:full + 128].all()


def test_visited_pair_share_pinned_for_gpt2(monkeypatch):
    """(1024, 1024) at the blocks the rule picks: 256-row q blocks visit 10
    of the square's 16 tiles of 256; without ``causal`` the whole row."""
    q = jnp.zeros((1, 1024, 12 * 64), jnp.bfloat16)
    # the launchers note the share as they trace, and are jitted: call them
    # bare, so that a trace an earlier test cached cannot stand in
    for name in ("_flash_fwd_grouped", "_flash_bwd_grouped"):
        monkeypatch.setattr(pa, name, getattr(pa, name).__wrapped__)
    for causal, share in ((True, 0.625), (False, 1.0)):
        cfg = pa._nlhd_group_config(1024, 1024, 12, 64, 2, causal)
        monkeypatch.setattr(pa, "_visited_pair_share", {})
        # abstract evaluation only
        jax.eval_shape(
            lambda q: jax.vjp(
                lambda q, k, v: pa._flash_nlhd_grouped(
                    q, k, v, causal, 0.125, True, 0, None, 12, cfg),
                q, q, q,
            )[1](q),
            q,
        )
        assert pa.flash_visited_pair_share() == {
            "flash_fwd": share, "flash_bwd": share,
        }


def test_visited_pair_share_pinned_for_sdar(monkeypatch):
    """8192 positions under ``bd = (4096, 4)`` at 1024-tiles: of 64 tiles a
    head 24 are live, 12 of them whole; the 8 frontier and 4 same-block tiles
    take their sub-ranges, 17.5 tile-equivalents in all (24, or 0.375, when
    every live tile is computed whole); 0.2502 of the pairs are live."""
    for name in ("_flash_tabled_fwd", "_flash_tabled_bwd"):
        monkeypatch.setattr(pa, name, getattr(pa, name).__wrapped__)
    monkeypatch.setattr(pa, "_visited_pair_share", {})
    q = jnp.zeros((1, 32, 8192, 128), jnp.bfloat16)
    kv = jnp.zeros((1, 4, 8192, 128), jnp.bfloat16)
    jax.eval_shape(
        lambda q, k, v: jax.vjp(
            lambda q, k, v: pa._flash(q, k, v, False, 1.0, 1024, 1024, True,
                                      0, None, (4096, 4)),
            q, k, v,
        )[1](q),
        q, kv, kv,
    )
    assert pa.flash_visited_pair_share() == {
        "flash_bd_fwd": 17.5 / 64, "flash_bd_bwd": 17.5 / 64,
    }
    # a tile too short for either class: every live tile whole
    monkeypatch.setattr(pa, "_visited_pair_share", {})
    jax.eval_shape(
        lambda q: pa._flash(q, q, q, False, 1.0, 128, 128, True, 0, None, (128, 4)),
        jnp.zeros((1, 2, 256, 64), jnp.bfloat16),
    )
    assert pa.flash_visited_pair_share() == {"flash_bd_fwd": 0.75}


GPT2 = dict(q_len=1024, k_len=1024, num_heads=12, head_dim=64, itemsize=2)


def test_group_config_for_gpt2_stays_under_the_vmem_budget():
    """The rule's choice at GPT-2's shape, and its footprint counted here
    tile by tile at the widest causal q block (the last: the whole row)."""
    assert not pa._nlhd_single_fits(1024, 1024, 768, 2)
    hg, bq_f, bq_b = pa._nlhd_group_config(**GPT2, causal=True)
    assert (hg, bq_f, bq_b) == (6, 256, 256)
    hd, k_len = hg * 64, 1024
    widest = max(visit for _, _, _, visit in pa._causal_spans(1024, 1024, bq_b, 0, None))
    assert widest == k_len
    fwd = (2 * k_len * hd + 2 * bq_f * hd) * 2 + 2 * bq_f * widest * 4
    bwd = (3 * bq_b * hd + 4 * k_len * hd) * 2 + 2 * k_len * hd * 4 \
        + 4 * bq_b * widest * 4
    assert fwd <= pa._VMEM_BUDGET and bwd <= pa._VMEM_BUDGET
    # the whole-row form keeps its blocks
    assert pa._nlhd_group_config(**GPT2) == (6, 512, 256)


@pytest.fixture(scope="module")
def one_v5e():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def mosaic_calls_compiled_for(fn, *shapes):
    """``([[name, result], ..], text)``: the Mosaic custom calls in ``fn``
    compiled for the described chip ``shapes`` are placed on, and the whole
    compiled text; the persistent cache off (an entry written for a described
    chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(fn).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
    # outside a step the instruction's name carries the transformations
    # around the call (``%jvp_flash_fwd_.1``); the result follows `` = ``
    return [line.split(" custom-call(")[0].split(" = ")
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line], text


@pytest.mark.parametrize("q_len,k_len,heads,dim,causal", [
    (1024, 1024, 12, 64, True),     # GPT-2's microbatch
    (1024, 1024, 12, 64, False),
    (700, 700, 12, 64, True),       # padded to 768, kv_len 700
    (384, 768, 12, 64, True),
    (256, 256, 16, 256, True),      # a wide model: the rule's VMEM edge
])
def test_grouped_pair_compiles_for_a_v5e(one_v5e, q_len, k_len, heads, dim, causal):
    """Mosaic takes the pair at real widths (VMEM, tiling, the static prefix
    slices), and the calls keep the names and results the roofline readers
    tell them apart by.  Compiled for a described chip: nothing runs."""
    def pair(q, k, v):
        out, vjp = jax.vjp(
            lambda q, k, v: pa.flash_attention(q, k, v, causal=causal, interpret=False),
            q, k, v,
        )
        return (out,) + vjp(out)

    shape = lambda n: jax.ShapeDtypeStruct((8, n, heads, dim), jnp.bfloat16,
                                           sharding=one_v5e)
    calls, _ = mosaic_calls_compiled_for(pair, shape(q_len), shape(k_len), shape(k_len))
    assert len(calls) == 2
    results = {role: result for name, result in calls
               for role in ("flash_fwd", "flash_bwd") if role in name}
    qp, kp, w = q_len + (-q_len) % 128, k_len + (-k_len) % 128, heads * dim
    assert results["flash_fwd"].count(f"bf16[8,{qp},{w}]") == 1 and "f32[" in results["flash_fwd"]
    assert results["flash_bwd"].count("bf16[8,") == 3 and "f32[" not in results["flash_bwd"]
    assert f"bf16[8,{kp},{w}]" in results["flash_bwd"]


def test_tabled_pair_compiles_for_a_v5e_at_sdars_shape(one_v5e):
    """Mosaic takes the masked pair with its sub-tile branches at the cell's
    widths — 8192 positions, 32 / 4 heads of 128, block 4 — inside the fused
    backward's scoped VMEM (``_FUSED_BWD_VMEM``).  Nothing runs."""
    def pair(q, k, v):
        out, vjp = jax.vjp(
            lambda q, k, v: pa.flash_attention(
                q, k, v, block_diffusion=(4096, 4), interpret=False),
            q, k, v,
        )
        return (out,) + vjp(out)

    shape = lambda h: jax.ShapeDtypeStruct((1, 8192, h, 128), jnp.bfloat16,
                                           sharding=one_v5e)
    calls, text = mosaic_calls_compiled_for(pair, shape(32), shape(4), shape(4))
    assert len(calls) == 2
    results = {role: result for name, result in calls
               for role in ("flash_bd_fwd", "flash_bd_bwd") if role in name}
    assert "bf16[1,32,8192,128]" in results["flash_bd_fwd"]
    assert results["flash_bd_bwd"].count("bf16[1,4,8192,128]") == 2     # dk, dv at the K/V heads
    assert f"{pa._FUSED_BWD_VMEM}" in text
    # the causal kernels' metrics must not read these (their patterns:
    # ``flash_fwd[_.]``, ``flash_bwd``)
    assert not any(re.search(r"flash_(fwd|bwd)", name) for name, _ in calls)


def test_tabled_pair_compiles_for_a_v5e_at_instellas_shape(one_v5e):
    """The same pair under the causal mask at the Instella cell's call —
    8192 positions, 16 heads of 128, a softmax scale that is no power of two
    — under the causal kernels' names, the fused backward's first result in
    the shape ``readers/kernel_roofline.py`` parses, and no split backward."""
    def pair(q, k, v):
        out, vjp = jax.vjp(
            lambda q, k, v: pa.flash_attention(
                q, k, v, causal=True, scale=0.1654, interpret=False),
            q, k, v,
        )
        return (out,) + vjp(out)

    shape = jax.ShapeDtypeStruct((1, 8192, 16, 128), jnp.bfloat16, sharding=one_v5e)
    calls, text = mosaic_calls_compiled_for(pair, shape, shape, shape)
    names = sorted(re.sub(r"^%(\w+?)[_.]*\d*$", r"\1", name.strip()) for name, _ in calls)
    assert names == ["flash_bwd", "flash_fwd"], calls
    for name, result in calls:
        assert result.lstrip("(").startswith("bf16[1,16,8192,128]"), (name, result)
    assert f"{pa._FUSED_BWD_VMEM}" in text


def test_tabled_pair_compiles_for_a_v5e_at_nemotrons_shape(one_v5e):
    """The causal pair with grouped K/V at the Nemotron-H cell's call — 8192
    positions, 32 query heads over 2 K/V heads of 128: K/V are read at their
    own head count (dk, dv come back at 2 heads), under the causal kernels'
    names and in the shape ``readers/kernel_roofline.py`` parses."""
    def pair(q, k, v):
        out, vjp = jax.vjp(
            lambda q, k, v: pa.flash_attention(q, k, v, causal=True, interpret=False),
            q, k, v,
        )
        return (out,) + vjp(out)

    shape = lambda h: jax.ShapeDtypeStruct((1, 8192, h, 128), jnp.bfloat16,
                                           sharding=one_v5e)
    calls, text = mosaic_calls_compiled_for(pair, shape(32), shape(2), shape(2))
    names = sorted(re.sub(r"^%(\w+?)[_.]*\d*$", r"\1", name.strip()) for name, _ in calls)
    assert names == ["flash_bwd", "flash_fwd"], calls
    results = {role: result for name, result in calls
               for role in ("flash_fwd", "flash_bwd") if role in name}
    assert results["flash_fwd"].lstrip("(").startswith("bf16[1,32,8192,128]")
    assert results["flash_bwd"].lstrip("(").startswith("bf16[1,32,8192,128]")
    assert results["flash_bwd"].count("bf16[1,2,8192,128]") == 2         # dk, dv at the K/V heads
    assert f"{pa._FUSED_BWD_VMEM}" in text


@pytest.mark.parametrize("length, groups, per_group, head_dim, state, dtype", [
    (8192, 8, 8, 64, 128, jnp.bfloat16),        # the Nemotron-H cell's mixer: two heads a 128-lane slab
    (256, 1, 1, 64, 128, jnp.float32),          # one head a slab, half a lane tile wide
])
def test_the_state_space_pair_compiles_for_a_v5e(one_v5e, length, groups, per_group, head_dim, state, dtype):
    """``ops/ssd.py``'s pair where ``ssd_plan`` answers ``pallas`` (kept in
    this file with the other compiles for a described chip: one process may
    load the TPU's library): both kernels under their names, the results in
    the shapes the device trace will spell."""
    from pytorch_distributed_training_tpu.ops import ssd

    heads = groups * per_group
    assert ssd.ssd_plan(length, heads, groups, head_dim, state, 128, jnp.dtype(dtype).itemsize, backend="tpu").kind == "pallas"

    def pair(x, dt, a, b, c):
        y, vjp = jax.vjp(lambda *inputs: ssd._ssd_pallas(*inputs, groups, 128, False), x, dt, a, b, c)
        return (y,) + vjp(y)

    shape = lambda dims, kind: jax.ShapeDtypeStruct(dims, kind, sharding=one_v5e)
    calls, _ = mosaic_calls_compiled_for(
        pair, shape((1, length, heads * head_dim), dtype), shape((1, length, heads), jnp.float32),
        shape((heads,), jnp.float32), shape((1, length, groups * state), dtype),
        shape((1, length, groups * state), dtype))
    results = {role: result for name, result in calls for role in ssd.KERNEL_NAMES if role in name}
    assert sorted(results) == ["ssd_bwd", "ssd_fwd"] and len(calls) == 2, calls
    kind = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype]
    chunks = length // 128
    assert results["ssd_fwd"].replace(" ", "").startswith(
        f"({kind}[1,{length},{heads * head_dim}]") and f"f32[1,{groups},{chunks},{per_group * head_dim},{state}]" in results["ssd_fwd"]
    assert results["ssd_bwd"].count(f"{kind}[1,{length},{groups * state}]") == 2         # dB, dC
    assert f"f32[1,{groups},{length},{per_group}]" in results["ssd_bwd"] and f"f32[1,{groups},{per_group},{length}]" in results["ssd_bwd"]


@pytest.mark.parametrize("data, tensor", [(4, 1), (2, 2)])
def test_a_mixers_gradient_lowers_for_four_v5es(monkeypatch, data, tensor):
    """The data-parallel step is a GSPMD jit, and Mosaic calls "cannot be
    automatically partitioned": ``ssd_chunked`` puts the pair in a
    ``shard_map`` (as ``ops/attention`` puts flash).  ``jax.grad`` through one
    ``Mamba2Mixer`` layer at lane-aligned sizes, compiled for a described
    2x2 of v5es with the batch sharded: a chip's shard of the batch — and,
    with ``tensor`` 2, of the groups — in each call's results; and the
    convolution's pair (``ops/causal_conv``) in its own, the channels whole."""
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_training_tpu import comm, models
    from test_ssd_pallas import MIXER

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = comm.make_mesh(comm.MeshConfig(data=data, tensor=tensor), devices=list(topo.devices))
    net = models.create_model("nemotron_h_30b_a3b", dtype=jnp.bfloat16, cfg_overrides=MIXER)
    tokens = jnp.zeros((4, 256), jnp.int32)
    params = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0), tokens, train=False)["params"])
    on = lambda spec: lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, spec))
    loss = lambda p, t: jnp.sum(net.apply({"params": p}, t, train=False).astype(jnp.float32) ** 2)
    # the plan and the interpreter follow the default backend, which is the CPU here: the described chips'
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with mesh:
        calls, _ = mosaic_calls_compiled_for(
            jax.grad(loss), jax.tree.map(on(P()), params), on(P(comm.mesh.BATCH_AXES))(tokens))
    roles = ("causal_conv_fwd", "causal_conv_bwd", "ssd_fwd", "ssd_bwd")
    bare = lambda result: re.sub(r"\{[^}]*\}", "", result.replace(" ", ""))        # shapes without their layouts
    results = {role: bare(result) for name, result in calls for role in roles if role in name}
    assert sorted(results) == sorted(roles) and len(calls) == 4, calls
    rows, groups = 4 // data, MIXER["n_groups"] // tensor
    width = groups * (MIXER["mamba_num_heads"] // MIXER["n_groups"]) * MIXER["mamba_head_dim"]
    assert results["ssd_fwd"].startswith(f"(bf16[{rows},256,{width}]"), results
    assert results["ssd_bwd"].count(f"bf16[{rows},256,{groups * MIXER['ssm_state_size']}]") == 2, results
    # the convolution's pair (ISSUE 37) in its own ``shard_map``: a chip's rows, the channels whole — x, B and C
    # leave it as the in-projection's columns, which a split over ``tensor`` would cut elsewhere
    inner, shared = MIXER["mamba_num_heads"] * MIXER["mamba_head_dim"], MIXER["n_groups"] * MIXER["ssm_state_size"]
    assert results["causal_conv_fwd"].startswith(
        f"(bf16[{rows},256,{inner}]" + 2 * f",bf16[{rows},256,{shared}]"), results
    assert results["causal_conv_bwd"].startswith(
        f"(bf16[{rows},256,{inner + 2 * shared}],f32[{rows},4,{inner + 2 * shared}]"), results


def test_the_convolutions_pair_compiles_for_a_v5e_at_the_cells_shape(one_v5e):
    """``ops/causal_conv.py``'s pair at the Nemotron-H cell's call (kept in
    this file with the other compiles for a described chip): 8192 positions,
    ``xBC`` read in place at columns [4096, 10240) of the in-projection's
    10,304, K 4; ``x``, ``B`` and ``C`` come back as three arrays, the
    stream's cotangent as one."""
    from pytorch_distributed_training_tpu.ops import causal_conv as cc

    length, wide, offset, splits, kernel = 8192, 10304, 4096, (4096, 1024, 1024), 4
    plan = cc.conv_plan(length, sum(splits), kernel, 2, offset=offset, splits=splits, backend="tpu")
    assert plan.kind == "pallas" and not plan.interpret

    def pair(x, w, bias):
        y, vjp = jax.vjp(lambda *inputs: cc._conv_pallas(*inputs, offset, splits, plan.time_tile,
                                                         plan.channel_tile, False), x, w, bias)
        return y + vjp(y)

    shape = lambda dims, kind: jax.ShapeDtypeStruct(dims, kind, sharding=one_v5e)
    calls, _ = mosaic_calls_compiled_for(pair, shape((1, length, wide), jnp.bfloat16),
                                         shape((kernel, sum(splits)), jnp.float32), shape((sum(splits),), jnp.float32))
    bare = lambda result: re.sub(r"\{[^}]*\}", "", result.replace(" ", ""))
    results = {role: bare(result) for name, result in calls for role in cc.KERNEL_NAMES if role in name}
    assert sorted(results) == ["causal_conv_bwd", "causal_conv_fwd"] and len(calls) == 2, calls
    assert results["causal_conv_fwd"].startswith("(bf16[1,8192,4096]") and results["causal_conv_fwd"].count("bf16[1,8192,1024]") == 2
    assert results["causal_conv_bwd"].startswith("(bf16[1,8192,6144]") and "f32[1,4,6144]" in results["causal_conv_bwd"]


@pytest.mark.parametrize("width, inner, groups", [(2048, 768, 16), (2048, 1408, 8)])      # SDAR's, Instella's
def test_the_grouped_products_compile_for_a_v5e_at_the_cells_widths(one_v5e, width, inner, groups):
    """``ops/grouped_matmul.py``'s three kernels at a pass of the two cells
    (kept in this file with the other compiles for a described chip):
    ``ROWS_CHUNK`` sorted rows, a group's matrix a whole block, under the
    names and in the result shapes the benchmark's committed pattern reads
    off a device trace."""
    import json
    import os

    from pytorch_distributed_training_tpu.models import moe
    from pytorch_distributed_training_tpu.ops import grouped_matmul as gm

    rows = moe.ROWS_CHUNK
    plan = gm.grouped_plan(rows, width, inner, groups, jnp.bfloat16, backend="tpu")
    assert plan.kind == "pallas" and not plan.interpret

    def products(x, d_h, w, sizes, carry):
        h = gm._gmm(x, w, sizes, False, plan.row_tile, False)
        d_x = gm._gmm(d_h, w, sizes, True, plan.row_tile, False)
        # the widest call of an expert layer's backward: two products, the rows' own operands, four results
        again = gm._gmm_call((x,), (w, w), (d_h, carry[0, 0] * jnp.ones((rows, 1))), sizes, lhs_of=(0, 0),
                             epilogue=moe._into_experts_again("silu"), outs=(("bfloat16", False),) * 3 + (("float32", True),),
                             transposed=False, row_tile=plan.row_tile, name=gm.REFWD, interpret=False)
        return (h, d_x, *again) + tuple(gm._wgrad_call(x, d_h, sizes, jnp.zeros_like(w), carry, jnp.zeros((2,), jnp.int32),
                                                       plan.row_tile, False))

    shape = lambda dims, kind: jax.ShapeDtypeStruct(dims, kind, sharding=one_v5e)
    calls, _ = mosaic_calls_compiled_for(
        products, shape((rows, width), jnp.bfloat16), shape((rows, inner), jnp.bfloat16),
        shape((groups, width, inner), jnp.bfloat16), shape((groups,), jnp.int32), shape((width, inner), jnp.float32))
    bare = lambda result: re.sub(r"\{[^}]*\}", "", result.replace(" ", ""))
    results = {name.strip().split(".")[0]: bare(result) for name, result in calls}
    assert results == {
        "%ragged-dot-held-fwd": f"bf16[{rows},{inner}]", "%ragged-dot-held-dgrad": f"bf16[{rows},{width}]",
        "%ragged-dot-held-refwd": "(" + 3 * f"bf16[{rows},{inner}]," + f"f32[{rows},1])",
        "%ragged-dot-held-wgrad": f"(bf16[{groups},{width},{inner}],f32[{width},{inner}])"}, calls
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for layer in ("kernel.ragged_dot_roofline.train", "moe.grouped_matmul_share.train"):
        rx = re.compile(json.load(open(os.path.join(root, "benchmark", "layers", layer + ".json")))["args"]["pattern"])
        for name, result in calls:          # as ``benchmark/tracered.short_name`` spells a trace's event
            assert rx.search(f"{name.strip()} = {re.sub(r'[{][^}]*[}]', '', result)} custom-call tpu_custom_call"), name


def test_the_combine_compiles_for_a_v5e_at_the_cells_size(one_v5e):
    """``ops/grouped_matmul.add_rows`` at both cells' call: ``ROWS_CHUNK``
    rows of 2048 added to 8192 tokens, every token's float32 sums of 512
    columns in VMEM; under a name the products' pattern does not read."""
    from pytorch_distributed_training_tpu.models import moe
    from pytorch_distributed_training_tpu.ops import grouped_matmul as gm

    plan = gm.add_rows_plan(8192, moe.ROWS_CHUNK, 2048, jnp.bfloat16, backend="tpu")
    assert plan.kind == "pallas" and not plan.interpret
    shape = lambda dims, kind: jax.ShapeDtypeStruct(dims, kind, sharding=one_v5e)
    calls, _ = mosaic_calls_compiled_for(
        lambda into, token_of, rows, n: gm._add_rows_call(into, token_of, rows, n, plan.row_tile, False),
        shape((8192, 2048), jnp.bfloat16), shape((moe.ROWS_CHUNK,), jnp.int32),
        shape((moe.ROWS_CHUNK, 2048), jnp.bfloat16), shape((), jnp.int32))
    ((name, result),) = calls
    assert name.strip().replace("ROOT ", "").startswith("%held-rows-add") and result.startswith("bf16[8192,2048]"), calls


def test_an_expert_layers_gradient_lowers_for_four_v5es(monkeypatch):
    """Sorted rows have no batch axis to split, and Mosaic calls "cannot be
    automatically partitioned": under ``data`` 4 the grouped products go
    into a ``shard_map`` with everything whole.  ``jax.grad`` through one
    ``TopKMoe`` at lane-aligned toy sizes, compiled for a described 2x2 of
    v5es with the batch sharded: the forward's 2 calls, the backward's 3, 3
    weight gradients and the combine twice, each under its role's name with
    no transformation's prefix."""
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_training_tpu import comm
    from pytorch_distributed_training_tpu.models import moe

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = comm.make_mesh(comm.MeshConfig(data=4), devices=list(topo.devices))
    layer = moe.TopKMoe(num_experts=8, num_experts_per_tok=2, mlp_dim=128, experts_held=(2, 4), dtype=jnp.bfloat16)
    x = jnp.zeros((4, 128, 256), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x)["params"])
    on = lambda spec: lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, spec))
    loss = lambda p, x: jnp.sum(layer.apply({"params": p}, x, mutable=["moe_counters"])[0].astype(jnp.float32) ** 2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")      # the plan's: the described chips'
    with mesh:
        calls, text = mosaic_calls_compiled_for(
            jax.grad(loss, (0, 1)), jax.tree.map(on(P()), params), on(P(comm.mesh.BATCH_AXES))(x))
    names = sorted(name.strip().split(".")[0] for name, _ in calls)
    assert names == ["%held-rows-add"] * 2 + ["%ragged-dot-held-dgrad"] * 2 + ["%ragged-dot-held-fwd"] * 2 + [
        "%ragged-dot-held-refwd"] + ["%ragged-dot-held-wgrad"] * 3, calls
    rows = 4 * 128 * 2                                      # every device runs the whole pass
    assert sum(result.startswith(f"bf16[{rows},128]") for _, result in calls) == 2, calls


@pytest.mark.parametrize("data, tensor", [(2, 2), (4, 1), (1, 4)])
def test_under_a_mesh_of_several_devices_the_convolutions_pair_runs_a_shard(data, tensor):
    """Under GSPMD the pair goes into a ``shard_map``, the batch over
    ``data`` where it divides (4 rows: 2 or 4 ways; under ``tensor`` 4 every
    device runs the whole): ``y`` and the three gradients equal the
    unsharded call's, ``dw`` and ``db`` summed over the batch's shards."""
    from pytorch_distributed_training_tpu import comm
    from test_causal_conv_pallas import NAMES, conv_inputs, gradients, pair

    args = conv_inputs(4, bsz=4, t=256)
    both = lambda *inputs: pair(*inputs) + gradients(pair, inputs)
    want = both(*args)
    mesh = comm.make_mesh(comm.MeshConfig(data=data, tensor=tensor), devices=jax.devices()[:data * tensor])
    with mesh:
        got = jax.jit(both)(*args)
        assert "shard_map" in str(jax.make_jaxpr(both)(*args))
    for name, g_, w_ in zip(["x", "B", "C"] + ["d" + n for n in NAMES], got, want):
        np.testing.assert_allclose(g_, w_, rtol=1e-6, atol=1e-6 * float(jnp.abs(w_).max()), err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("q_len,k_len,heads,dim,itemsize,kind", [
    (1024, 1024, 12, 64, 2, "grouped"), (1024, 1024, 12, 64, 4, "grouped"),
    (197, 197, 12, 64, 2, "single"), (512, 512, 12, 64, 2, "single"),
    (640, 640, 2, 64, 4, "grouped"), (384, 768, 2, 64, 4, "grouped"),
    (256, 1024, 16, 1024, 4, "transposed"), (2048, 2048, 12, 64, 2, "tabled"),
    (8192, 8192, 16, 128, 2, "tabled"),         # Instella's call
    (32768, 32768, 16, 128, 2, "transposed"),   # past the fused backward's fit
])
def test_flash_plan_is_what_the_dispatch_runs(
        monkeypatch, causal, q_len, k_len, heads, dim, itemsize, kind):
    """The generation each shape takes, as a literal: asserted on the plan
    and on the entry function ``flash_attention`` really calls (``_flash``
    for both transposed kinds: ``_flash_fwd`` / ``_flash_bwd`` ask
    ``_takes_tabled``, as the plan does)."""
    taken = []
    monkeypatch.setattr(pa, "_flash_nlhd", lambda q, *a: taken.append("single") or q)
    monkeypatch.setattr(pa, "_flash_nlhd_grouped",
                        lambda q, *a: taken.append("grouped") or q)
    monkeypatch.setattr(pa, "_flash", lambda q, *a: taken.append("transposed") or q)
    dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    q = jax.ShapeDtypeStruct((1, q_len, heads, dim), dtype)
    kv = jax.ShapeDtypeStruct((1, k_len, heads, dim), dtype)
    jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, causal=causal), q, kv, kv)
    assert taken == [{"tabled": "transposed"}.get(kind, kind)]
    plan = pa.flash_plan(q_len, k_len, heads, heads, dim, itemsize,
                         causal=causal, block_diffusion=None)
    assert plan.kind == kind
    assert (plan.group is not None) == (kind == "grouped")


# Which generation each registered transformer reaches at padded lengths
# 256, 512, 1024, by itemsize.  Native everywhere except gpt2_xl: 25 heads of
# 64 have no lane-aligned head group, and all 25 at once fit the whole-heads
# pair only at 256 in bf16 (ROADMAP D14, D16).
LENGTHS = (256, 512, 1024)
REGISTRY_KINDS = {
    "vit_s16": {2: ("single", "single", "grouped"), 4: ("single", "single", "grouped")},
    "vit_b16": {2: ("single", "single", "grouped"), 4: ("single", "grouped", "grouped")},
    "vit_l16": {2: ("single", "single", "grouped"), 4: ("single", "grouped", "grouped")},
    "gpt2": {2: ("single", "single", "grouped"), 4: ("single", "grouped", "grouped")},
    "gpt2_medium": {2: ("single", "single", "grouped"), 4: ("single", "grouped", "grouped")},
    "gpt2_large": {2: ("single", "grouped", "grouped"), 4: ("single", "grouped", "grouped")},
    "gpt2_xl": {2: ("single", "transposed", "transposed"),
                4: ("transposed", "transposed", "transposed")},
    # its 32 query heads of 128 as a plain shape (its cell runs ``tabled``)
    "sdar_30b_a3b": {2: ("grouped", "grouped", "grouped"), 4: ("grouped", "grouped", "grouped")},
}


def _attention_shape(name):
    """(heads, head_dim) from the registered model's own config."""
    from pytorch_distributed_training_tpu.models import create_model

    model = create_model(name)
    cfg = getattr(model, "cfg", model)
    if hasattr(cfg, "num_attention_heads"):
        return cfg.num_attention_heads, cfg.head_dim
    return cfg.num_heads, cfg.hidden_dim // cfg.num_heads


@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("length", LENGTHS)
@pytest.mark.parametrize("name", REGISTRY_KINDS)
def test_generation_each_registered_model_reaches(name, length, itemsize):
    heads, dim = _attention_shape(name)
    want = REGISTRY_KINDS[name][itemsize][LENGTHS.index(length)]
    for causal in (False, True):
        plan = pa.flash_plan(length, length, heads, heads, dim, itemsize,
                             causal=causal, block_diffusion=None)
        assert plan.kind == want, (causal, plan)


def test_sdar_cell_shape_plans_the_tabled_pair():
    """8192 positions, 32 / 4 heads of 128, block 4: the cell's call."""
    plan = pa.flash_plan(8192, 8192, 32, 4, 128, 2, causal=False,
                         block_diffusion=(4096, 4))
    assert plan == pa.FlashPlan(8192, 8192, 1024, 1024, "tabled", None)
