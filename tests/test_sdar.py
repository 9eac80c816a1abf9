"""SDAR-MoE on the normal path at a toy size (float32, CPU): the
block-diffusion mask of the flash kernels against its dense definition, the
dropless top-k experts and their shares, the noising function, and the step
that finds the objective on the model."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_training_tpu import models, train
from pytorch_distributed_training_tpu.models import moe
from pytorch_distributed_training_tpu.ops import attention, pallas_attention as pa
from pytorch_distributed_training_tpu.train import block_diffusion

TOY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=2,
           moe_intermediate_size=32, block_length=4)


def dense_definition(seq_len, block):
    """The mask by its words: loops, no array trick shared with the code."""
    m = np.zeros((2 * seq_len, 2 * seq_len), bool)
    for i in range(2 * seq_len):
        for j in range(2 * seq_len):
            qi, kj = i % seq_len // block, j % seq_len // block
            if i < seq_len and j < seq_len:
                m[i, j] = qi == kj
            elif i < seq_len:
                m[i, j] = kj < qi
            elif j >= seq_len:
                m[i, j] = kj <= qi
    return m


@pytest.mark.parametrize("seq_len, block, tile", [(32, 4, 16), (48, 8, 32), (256, 4, 128)])
def test_tile_predicate_and_in_tile_mask_equal_the_dense_definition(seq_len, block, tile):
    want = dense_definition(seq_len, block)
    np.testing.assert_array_equal(attention.block_diffusion_mask(seq_len, block), want)
    n = 2 * seq_len // tile
    for qi in range(n):
        for ki in range(n):
            cut = want[qi * tile:(qi + 1) * tile, ki * tile:(ki + 1) * tile]
            live = bool(pa._bd_live_block(qi, ki, tile, tile, seq_len, block))
            full = bool(pa._bd_full_block(qi, ki, tile, tile, seq_len, block))
            assert live == cut.any() and full == cut.all(), (qi, ki)
            if live:
                got = pa._bd_mask(qi * tile, ki * tile, tile, tile, seq_len, block)
                np.testing.assert_array_equal(got, cut)
    kv_of = pa._live_table(n, n, causal=False, causal_offset=0, kv_len=None,
                           block_q=tile, block_k=tile, bd=(seq_len, block))
    tiles = want.reshape(n, tile, n, tile).any(axis=(1, 3))
    assert all(tiles[q, kv_of[q, k]] and (not tiles[q, k] or kv_of[q, k] == k)
               for q in range(n) for k in range(n))


def flash_and_xla_under_the_mask(seq_len, block, tile, batch, kv_heads):
    """(out, dq, dk, dv) of the kernels through the Pallas interpreter and of
    the dense-mask XLA path, 4 query heads over ``kv_heads``."""
    keys = jax.random.split(jax.random.PRNGKey(seq_len), 3)
    q = jax.random.normal(keys[0], (batch, 2 * seq_len, 4, 16))
    k, v = (jax.random.normal(x, (batch, 2 * seq_len, kv_heads, 16)) for x in keys[1:])

    def run(flash):
        def loss(q, k, v):
            if flash:
                o = pa.flash_attention(q, k, v, block_q=tile, block_k=tile,
                                       block_diffusion=(seq_len, block))
            else:
                o = attention.dot_product_attention(
                    q, k, v, use_flash=False, block_diffusion=(seq_len, block))
            return jnp.sum(o * jnp.cos(o)), o
        (_, o), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (o, *grads)

    return run(True), run(False)


@pytest.mark.parametrize("seq_len, block, tile", [(32, 4, 1024), (48, 8, 1024), (256, 4, 128)])
def test_flash_kernels_under_the_mask_match_the_xla_path(seq_len, block, tile):
    """The forward and the fused backward kernel through the Pallas interpreter,
    grouped K/V heads, against the dense-mask XLA path."""
    for got, want in zip(*flash_and_xla_under_the_mask(seq_len, block, tile, 2, 2)):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=3e-5)


@pytest.mark.parametrize("seq_len, block, tile, sub, frontier_tiles, same_tiles", [
    (4096, 4, 1024, 256, 8, 4),     # SDAR's call, the frontier class's sub
    (4096, 4, 1024, 128, 8, 4),     # and the same-block class's
    (1024, 32, 512, 128, 4, 2),     # block_len 32
    (600, 4, 256, 128, 0, 2),       # padded: 1200 keys of 1280, tile 2 straddles the halves
    (512, 4, 256, 256, 0, 0),       # a tile of ``sub`` rows: the whole tile, as before
    (768, 12, 256, 64, 0, 0),       # a block_len that does not divide ``sub``
    (384, 4, 256, 64, 0, 1),        # halves that are no whole tiles: no frontier class
])
def test_sub_tile_classes_and_ranges_against_the_dense_mask(
        seq_len, block, tile, sub, frontier_tiles, same_tiles):
    """Every tile of a diagonal class is live and not full; its visited
    ranges hold every live pair, an unmasked range no dead one, and a masked
    range is the in-tile mask's own; the classes never overlap, and where the
    static numbers rule a class out every tile keeps the whole-tile form."""
    padded = -(-2 * seq_len // 128) * 128
    want = np.zeros((padded, padded), bool)
    want[:2 * seq_len, :2 * seq_len] = attention.block_diffusion_mask(seq_len, block)
    n = padded // tile
    counts = {True: 0, False: 0}
    for qi in range(n):
        for ki in range(n):
            cut = want[qi * tile:(qi + 1) * tile, ki * tile:(ki + 1) * tile]
            hits = []
            for frontier in (True, False):
                hit = pa._bd_sub_class(frontier, qi, ki, tile, tile, seq_len, block, sub)
                if hit is not None and bool(hit):
                    hits.append(frontier)
            assert len(hits) <= 1, (qi, ki)
            if not hits:
                continue
            counts[hits[0]] += 1
            assert cut.any() and not cut.all(), (qi, ki)
            assert (ki + 1) * tile <= 2 * seq_len       # no padded key: no kv_len mask
            visited = np.zeros_like(cut)
            ranges = pa._bd_sub_ranges(hits[0], tile, sub)
            assert len(ranges) == tile // sub
            for r, parts in enumerate(ranges):
                rows = slice(r * sub, (r + 1) * sub)
                for lo, hi, masked in parts:
                    assert 0 <= lo < hi <= tile and not visited[rows, lo:hi].any()
                    visited[rows, lo:hi] = True
                    if masked:
                        np.testing.assert_array_equal(
                            pa._bd_mask(qi * tile + r * sub, ki * tile + lo, sub, hi - lo,
                                        seq_len, block),
                            cut[rows, lo:hi])
                    else:
                        assert cut[rows, lo:hi].all(), (qi, ki, r, lo)
            assert not (cut & ~visited).any(), (qi, ki)
    assert counts == {True: frontier_tiles, False: same_tiles}
    if (seq_len, block, tile) == (4096, 4, 1024):
        # SDAR's grid: whatever is live and neither full nor of a class
        live = sum(bool(pa._bd_live_block(q, k, tile, tile, seq_len, block))
                   for q in range(n) for k in range(n))
        full = sum(bool(pa._bd_full_block(q, k, tile, tile, seq_len, block))
                   for q in range(n) for k in range(n))
        assert (live, full) == (24, 12) and live - full == frontier_tiles + same_tiles


@pytest.mark.parametrize("seq_len, block, tile, reaches", [
    (1024, 4, 512, {"full", "frontier", "same"}),
    (512, 32, 512, {"frontier", "same"}),
    (600, 4, 256, {"full", "same", "whole"}),     # padded keys, a tile across the halves
])
def test_flash_kernels_in_the_sub_tile_forms_match_the_xla_path(seq_len, block, tile, reaches):
    """Forward, dq, dk and dv through the interpreter at tiles wide enough
    for the diagonal classes, K/V grouped 4:1 as SDAR's, against XLA."""
    padded = -(-2 * seq_len // 128) * 128
    n, forms = padded // tile, set()
    for qi in range(n):
        for ki in range(n):
            if not pa._bd_live_block(qi, ki, tile, tile, seq_len, block) or ki * tile >= 2 * seq_len:
                continue
            hit = {name for subs in pa._SUBS.values()        # either kernel's
                   for name, frontier, sub in zip(("frontier", "same"), (True, False), subs)
                   if pa._bd_sub_class(frontier, qi, ki, tile, tile, seq_len, block, sub)}
            full = bool(pa._bd_full_block(qi, ki, tile, tile, seq_len, block))
            forms |= hit or {"full" if full else "whole"}
    assert forms == reaches

    for got, want in zip(*flash_and_xla_under_the_mask(seq_len, block, tile, 1, 1)):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=5e-5)


def test_xla_path_equals_attention_with_repeated_heads():
    """Grouped K/V: head h reads K/V head h // group, on the definition."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, 64, 4, 16))
    k, v = (jax.random.normal(x, (1, 64, 2, 16)) for x in keys[1:])
    got = attention.dot_product_attention(q, k, v, block_diffusion=(32, 4), use_flash=False)
    mask = dense_definition(32, 4)
    kr, vr = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / 4.0
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1), vr)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_attention_arguments_are_checked():
    x = jnp.zeros((1, 64, 4, 16))
    kv = jnp.zeros((1, 64, 2, 16))
    # grouped K/V run under the causal and the empty mask too (tests/test_nemotron_h.py holds their values) ...
    assert attention.dot_product_attention(x, kv, kv).shape == x.shape
    assert attention.dot_product_attention(x, kv, kv, causal=True).shape == x.shape
    odd = jnp.zeros((1, 64, 3, 16))
    with pytest.raises(ValueError):
        attention.dot_product_attention(x, odd, odd, causal=True)                 # ... where the heads divide
    with pytest.raises(ValueError):
        pa.flash_attention(x, odd, odd, causal=True)                              # and in the kernels alike
    with pytest.raises(ValueError):
        attention.dot_product_attention(x, kv, kv, block_diffusion=(48, 4))       # 2L != 64
    with pytest.raises(ValueError):
        attention.dot_product_attention(x, kv, kv, causal=True, block_diffusion=(32, 4))
    with pytest.raises(ValueError):
        attention.dot_product_attention(x, kv[:, :, :1], kv, block_diffusion=(32, 4))   # k and v disagree


def layer(held=None, e=8, k=2):
    return moe.TopKMoe(num_experts=e, num_experts_per_tok=k, mlp_dim=32, experts_held=held)


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    """The toy layers take several passes of the expert loop, as a layer the
    routing favours does at full size."""
    monkeypatch.setattr(moe, "ROWS_CHUNK", 64)


def test_topk_routing_drops_nothing_under_a_biased_router():
    """A router that sends every token to the same two experts: all 2·T
    assignments are computed (the old top-1 layer would drop past capacity)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 64))
    net = layer()
    params = net.init(jax.random.PRNGKey(1), x)["params"]
    x = jnp.abs(x)                               # every logit of experts 3 and 6 is large
    params = {**params, "router": jnp.zeros((64, 8)).at[:, 3].set(5.0).at[:, 6].set(4.0)}
    out, sown = net.apply({"params": params}, x, mutable=["moe_counters"])
    counters = {k: float(v[0]) for k, v in sown["moe_counters"].items()}
    assert counters == {"moe_held_assignments": 256.0, "moe_load_max": 128.0}
    tokens = x.reshape(-1, 64)
    w, idx, _ = moe.topk_route(tokens @ params["router"], 2)
    assert set(np.unique(idx)) == {3, 6}
    want = sum(
        w[:, j:j + 1] * ((jax.nn.silu(tokens @ params["w_gate"][e]) * (tokens @ params["w_up"][e]))
                         @ params["w_down"][e])
        for j, e in ((0, 3), (1, 6)))
    np.testing.assert_allclose(out.reshape(-1, 64), want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("rows_chunk", [8, 48, 4096])
def test_a_share_under_a_biased_router_needs_no_room(rows_chunk, monkeypatch):
    """Expert 3 is the only one of the share (2, 3) any token picks, and every
    token picks it: four times the share's expected rows, all computed,
    whatever the chunk (smaller than, unaligned with, larger than the rows)."""
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (1, 64, 64)))
    monkeypatch.setattr(moe, "ROWS_CHUNK", rows_chunk)
    net = layer(held=(2, 2))
    params = net.init(jax.random.PRNGKey(1), x)["params"]
    params = {**params, "router": jnp.zeros((64, 8)).at[:, 3].set(5.0).at[:, 6].set(4.0)}
    out, sown = net.apply({"params": params}, x, mutable=["moe_counters"])
    assert float(sown["moe_counters"]["moe_held_assignments"][0]) == 64.0
    assert float(sown["moe_counters"]["moe_load_max"][0]) == 64.0
    tokens = x[0]
    w, idx, _ = moe.topk_route(tokens @ params["router"], 2)
    want = w[:, :1] * ((jax.nn.silu(tokens @ params["w_gate"][1]) * (tokens @ params["w_up"][1]))
                       @ params["w_down"][1])
    assert set(np.unique(idx[:, 0])) == {3}
    np.testing.assert_allclose(out[0], want, rtol=1e-4, atol=1e-6)


def test_gradients_flow_through_the_grouped_products(monkeypatch):
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 64))
    monkeypatch.setattr(moe, "ROWS_CHUNK", 16)
    net = layer(held=(0, 4))
    params = net.init(jax.random.PRNGKey(1), x)["params"]
    grads = jax.grad(lambda p: jnp.sum(jnp.square(net.apply({"params": p}, x))))(params)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree_util.tree_leaves(grads))


def test_noise_masks_the_drawn_share_and_follows_its_key():
    cfg = models.sdar.SdarConfig(**TOY)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (64, 256), 0, 511)
    noisy, masked, p = block_diffusion.noise(tokens, jax.random.PRNGKey(1), cfg)
    again = block_diffusion.noise(tokens, jax.random.PRNGKey(1), cfg)
    other = block_diffusion.noise(tokens, jax.random.PRNGKey(2), cfg)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip((noisy, masked, p), again))
    assert not bool(jnp.array_equal(masked, other[1]))
    assert bool(jnp.all((p >= block_diffusion.NOISE_EPS) & (p <= 1.0)))
    assert bool(jnp.all(jnp.where(masked, noisy == 511, noisy == tokens)))     # the last row is the mask id
    # each sequence's masked fraction is its own p (256 draws: 4 sigma of a Bernoulli mean)
    frac = jnp.mean(masked, axis=1)
    assert float(jnp.max(jnp.abs(frac - p))) < 4 * 0.5 / 16
    assert abs(float(jnp.mean(p)) - 0.5) < 0.15            # t is uniform


def test_the_lm_step_reads_the_objective_from_the_model():
    net = models.create_model("sdar_30b_a3b", cfg_overrides=dict(TOY, remat=True))
    assert models.MODEL_REGISTRY["sdar_30b_a3b"].kind == "lm"
    sample = jnp.zeros((2, 32), jnp.int32)
    state = train.create_train_state(net, jax.random.PRNGKey(0), sample, optax.adamw(1e-3),
                                     init_kwargs={"train": False})
    from pytorch_distributed_training_tpu.train.step import lm_objective

    assert lm_objective(state)[0] == "block_diffusion"
    gpt2 = models.create_model("gpt2", cfg_overrides=dict(num_layers=1, hidden_dim=32, num_heads=2,
                                                          vocab_size=64, max_seq_len=16))
    gpt2_state = train.create_train_state(gpt2, jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32),
                                          optax.sgd(0.1), init_kwargs={"train": False})
    assert lm_objective(gpt2_state) == ("next_token", None)

    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, 511)}
    step = train.make_train_step(kind="lm", num_microbatches=2, base_rng=jax.random.PRNGKey(1))
    first = None
    for _ in range(8):
        state, metrics = step(state, batch)
        first = float(metrics["loss"]) if first is None else first
    assert set(metrics) >= {"loss", "masked_tokens", "moe_held_assignments", "moe_load_max"}
    # a step's totals: 4 sequences as 2 microbatches x layers x positions x k, all held
    assert float(metrics["moe_held_assignments"]) == 4 * 2 * 64 * 2
    assert 0 < float(metrics["masked_tokens"]) <= 4 * 32 and float(metrics["masked_tokens"]).is_integer()
    assert np.isfinite(float(metrics["loss"]))
    with pytest.raises(ValueError, match="base_rng"):
        train.make_train_step(kind="lm")(state, batch)


def test_cli_trains_the_toy_size(tmp_path):
    import os
    import subprocess
    import sys

    overrides = ",".join(f"{k}={v}" for k, v in TOY.items())
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tpu.cli.main", "--use-cpu",
         "--model", "sdar_30b_a3b", "--dataset", "synthetic-tokens", "--seq-len", "32",
         "--model-overrides", overrides, "--batch-size", "4", "--accum-steps", "2",
         "--num-workers", "0", "--steps-per-epoch", "3", "--learning-rate", "1e-3"],
        capture_output=True, text=True, timeout=600,
        # one CPU device: the suite's 8-device XLA_FLAGS would want a batch of 8
        env={k: v for k, v in {**os.environ, "JAX_PLATFORMS": "cpu"}.items() if k != "XLA_FLAGS"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-3000:] + out.stdout[-2000:]
    assert "training started" in out.stdout


def test_masked_kernels_lower_under_their_names():
    q = jnp.ones((1, 256, 4, 16), jnp.float32)
    kv = jnp.ones((1, 256, 2, 16), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(pa.flash_attention(q, k, v, block_diffusion=(128, 4)) ** 2)

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).as_text(debug_info=True)
    for name in ("flash_bd_fwd", "flash_bd_bwd"):
        assert name in text and name in pa.KERNEL_NAMES
    # the tabled pair serves the causal mask too, under the causal names
    # (tests/test_flash_tabled_causal.py): never here
    assert set(re.findall(r"(flash_\w+)\)*/pallas_call\b", text)) == {"flash_bd_fwd", "flash_bd_bwd"}
    # (that the causal kernels' metric patterns do not match these names:
    # tests/benchmark_tests/test_bench_sdar.py, on the trace's own spelling)


def test_compiled_step_names_the_new_phases():
    import re

    from pytorch_distributed_training_tpu.obs.schema import METRICS
    from pytorch_distributed_training_tpu.obs.trace import PHASES
    from pytorch_distributed_training_tpu.train.step import STEP_COUNTERS

    net = models.create_model("sdar_30b_a3b", cfg_overrides=TOY)
    state = train.create_train_state(net, jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32),
                                     optax.sgd(0.1), init_kwargs={"train": False})
    step = train.make_train_step(kind="lm", num_microbatches=2, base_rng=jax.random.PRNGKey(1))
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32)}
    op_names = re.findall(r'op_name="([^"]+)"', step.lower(state, batch).compile().as_text())
    for phase in ("train/noise", "attn/block_diffusion", "moe/route", "moe/experts"):
        assert phase in PHASES and any(phase in name for name in op_names), phase
    assert set(STEP_COUNTERS) <= set(METRICS)
