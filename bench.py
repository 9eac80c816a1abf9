"""Benchmark: ResNet-50 training throughput on the available accelerator.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric matches BASELINE.json ("ImageNet ResNet-50 images/sec/chip"): a full
jitted train step (fwd + bwd + Adam update) on synthetic 224×224 data in
bf16 compute, timed both as a per-step dispatch loop and as the
framework's scan-over-steps epoch form; the faster form is reported
("loop_form" records which won).  ``vs_baseline`` divides by 2500
images/sec/chip — the 8×A100 DDP AMP ResNet-50 throughput per GPU the
north star targets, since the reference publishes no numbers of its own
(SURVEY.md §6).

``python bench.py --pipeline`` runs the loader-fed variant instead: the
same train step fed by the real input pipeline (packed uint8 records →
native batched RandomResizedCrop/flip/normalize → double-buffered
device_put), demonstrating the input path sustains the chip rate
(VERDICT r1 item 2).  ``--device-cache`` measures the HBM-resident
dataset path (zero steady-state H2D; data/device_cache.py).
"""

from __future__ import annotations

import json
import sys
import time

BASELINE_IMG_PER_SEC_PER_CHIP = 2500.0

# Median-of-N protocol (VERDICT r3 weak #1): a single-shot draw can sit a
# couple of percent off the median, enough to fake a regression.  Every
# headline artifact records all draws and reports the median.
BENCH_ROUNDS = 5


def _flag(name: str, default, cast):
    """Value of ``--name X`` from argv (cast), else ``default``."""
    argv = sys.argv[1:]
    if name in argv:
        return cast(argv[argv.index(name) + 1])
    return default


def _int_flag(name: str, default: int | None) -> int | None:
    return _flag(name, default, int)


def _float_flag(name: str, default: float | None) -> float | None:
    return _flag(name, default, float)


from statistics import median as _median


def _runs_fields(times: list[float], units_per_run: float) -> dict:
    """Rate stats for the artifact: every draw, the median, and the spread
    ((max-min)/median) so a future regression can't hide behind jitter."""
    rates = sorted(units_per_run / t for t in times)
    med = _median(rates)
    return {
        "runs": [round(r, 2) for r in rates],
        "spread": round((rates[-1] - rates[0]) / med, 4) if med else None,
    }


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_training_tpu.models import resnet50
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_policy, make_train_step,
    )

    on_tpu = jax.default_backend() == "tpu"
    # Batch 128 is the measured v5e sweet spot: stage-1 activations get
    # batch-minor layouts whose lane dim is exactly the batch, so 128 fills
    # the 128-lane tiles without padding (sweep: 64:2284, 128:2458, 192:2221,
    # 256:2298 img/s on the plain model; the fused model tracks the same
    # shape).
    batch = _int_flag("--batch", 128 if on_tpu else 16)
    steps = 32 if on_tpu else 3
    stem_remat = "--stem-remat" in sys.argv[1:]

    model = resnet50(
        num_classes=1000, dtype=jnp.bfloat16,
        cfg_overrides={"stem_remat": stem_remat},
    )
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3), jnp.bfloat16),
        optax.adamw(1e-3), init_kwargs={"train": False},
    )
    step_fn = make_train_step(kind="image_classifier", policy=make_policy("bf16"))

    rng = np.random.default_rng(0)
    images = jnp.asarray(
        rng.standard_normal((batch, 224, 224, 3), np.float32), jnp.bfloat16
    )
    labels = jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32)
    b = {"image": images, "label": labels}

    # Warmup: compile + one full execution (the loss read waits for it).
    state, m = step_fn(state, b)
    assert np.isfinite(float(m["loss"]))

    # BENCH_ROUNDS draws per loop form; the artifact reports the median of
    # the better form plus every draw (median-of-N protocol, see top).  Each
    # round keeps the loop fully async and closes the timing window with one
    # loss fetch — the donated state chains every step, so that read
    # completes only after all ``steps`` executions have.
    perstep_times = []
    for _ in range(BENCH_ROUNDS):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step_fn(state, b)
        final_loss = float(m["loss"])
        perstep_times.append(time.perf_counter() - t0)
        assert np.isfinite(final_loss)

    # Scan-based variant: the framework's TPU-native epoch form (one
    # dispatch for all ``steps``), which removes per-step dispatch overhead
    # from the measurement.  Same math per step; report whichever loop form
    # has the better median, recorded in "loop_form".
    from jax import lax

    def run_steps(state, b):
        def body(st, _):
            st, m = step_fn(st, b)
            return st, m["loss"]
        return lax.scan(body, state, None, length=steps)

    run_steps = jax.jit(run_steps, donate_argnums=0)
    state, losses = run_steps(state, b)
    assert np.isfinite(float(losses[-1]))  # warm compile
    scan_times = []
    for _ in range(BENCH_ROUNDS):
        t0 = time.perf_counter()
        state, losses = run_steps(state, b)
        final_loss = float(losses[-1])
        scan_times.append(time.perf_counter() - t0)
        assert np.isfinite(final_loss)

    if _median(scan_times) <= _median(perstep_times):
        loop_form, times = "scan", scan_times
    else:
        loop_form, times = "per-step", perstep_times
    units = batch * steps
    imgs_per_sec = units / _median(times)
    _emit({
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(imgs_per_sec / BASELINE_IMG_PER_SEC_PER_CHIP, 4),
        "loop_form": loop_form,
        "protocol": f"median-of-{BENCH_ROUNDS}",
        **_runs_fields(times, units),
    }, None)


def _packed_bench_setup():
    """Shared setup for the loader-fed and device-cached variants: packed
    records on disk, a 1-axis data mesh, a mesh-sharded ResNet-50 bf16
    TrainState, and the jitted step.  The state must be sharded over the
    SAME mesh the batches use: mixing NamedSharding batches with
    default-placement state knocks jit off the committed-layout fast path
    and the whole donated state gets re-placed through the host every step.
    """
    import os
    import tempfile

    import jax
    import jax.numpy as jnp
    import optax

    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.data import synthesize_packed_images
    from pytorch_distributed_training_tpu.models import resnet50
    from pytorch_distributed_training_tpu.parallel.sharding import DDP_RULES
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_policy, make_train_step,
    )

    on_tpu = jax.default_backend() == "tpu"
    sizes = {
        "on_tpu": on_tpu,
        "batch": 128 if on_tpu else 16,
        "n_images": 4096 if on_tpu else 64,
        "epochs": 3 if on_tpu else 2,  # epoch 0 is warmup; >=1 measured
    }
    packed = os.path.join(
        tempfile.gettempdir(), f"bench_packed_{sizes['n_images']}.bin"
    )
    if not os.path.exists(packed):
        synthesize_packed_images(
            packed, n=sizes["n_images"], size=232, num_classes=1000
        )
    mesh = make_mesh(MeshConfig(data=-1))
    model = resnet50(num_classes=1000, dtype=jnp.bfloat16)
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3), jnp.bfloat16),
        optax.adamw(1e-3), mesh=mesh, rules=DDP_RULES,
        init_kwargs={"train": False},
    )

    def step_for(normalize):
        return make_train_step(
            kind="image_classifier", policy=make_policy("bf16"),
            input_normalize=normalize,
        )

    return packed, mesh, state, step_for, sizes


def main_pipeline():
    """Loader-fed variant: train step consuming the real input pipeline."""
    import jax
    import numpy as np

    from pytorch_distributed_training_tpu.data import (
        DataLoader, DataLoaderConfig, PackedImages, prefetch_to_device,
    )

    packed, mesh, state, step_for, sizes = _packed_bench_setup()
    batch, epochs = sizes["batch"], sizes["epochs"]
    # uint8 output: crop/resize/flip native, ToTensor+Normalize on device.
    ds = PackedImages(packed, train=True, crop_size=224, output_dtype="uint8")
    loader = DataLoader(ds, DataLoaderConfig(batch_size=batch, num_workers=0))
    step_fn = step_for((ds.mean, ds.std))

    # Host-pipeline-only rate first: can the loader (decode + native
    # augmentation + collate) produce batches at the chip's rate?
    loader.set_epoch(0)
    t0 = time.perf_counter()
    n_host = 0
    for _ in iter(loader):
        n_host += batch
    loader_only = n_host / (time.perf_counter() - t0)

    # Warmup epoch 0 (compile + loader warm), then measure full epochs.
    best = float("inf")
    with mesh:
        for epoch in range(epochs):
            loader.set_epoch(epoch)
            t0 = time.perf_counter()
            n = 0
            for b in prefetch_to_device(iter(loader), mesh):
                state, m = step_fn(state, b)
                n += batch
            final_loss = float(m["loss"])  # closes the async window
            dt = time.perf_counter() - t0
            assert np.isfinite(final_loss)
            if epoch > 0:
                best = min(best, dt / n)
    imgs_per_sec = 1.0 / best
    out = {
        "metric": "resnet50_train_images_per_sec_per_chip_loaderfed",
        "value": round(imgs_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(imgs_per_sec / BASELINE_IMG_PER_SEC_PER_CHIP, 4),
        "loader_only_images_per_sec": round(loader_only, 2),
    }
    _emit(out, None)


def main_device_cache():
    """Device-cached variant: the dataset lives in HBM (uploaded once,
    before any execution), and gather/crop/flip run on-device — zero
    steady-state H2D.  The TPU-native answer to host-feed limits."""
    import numpy as np

    from pytorch_distributed_training_tpu.data import (
        DeviceCachedImages, PackedImages,
    )

    packed, mesh, state, step_for, sizes = _packed_bench_setup()
    batch, epochs = sizes["batch"], sizes["epochs"]
    src = PackedImages(packed, train=True, crop_size=224, output_dtype="uint8")
    ds = DeviceCachedImages(src, mesh=mesh, crop_size=224, train=True)
    step_fn = step_for((ds.mean, ds.std))

    # Default crop semantics == the CLI --device-cache path (one crop box
    # per batch, per-sample flips; data/device_cache.py) — same math, same
    # speed.  Measured here with per_sample_crop=True instead: 1206 img/s
    # vs ~2540, the windowed per-sample gather is a 2x end-to-end tax.
    run_epoch = ds.make_epoch_fn(step_fn, batch)
    steps = len(ds) // batch
    epochs = 1 + BENCH_ROUNDS if sizes["on_tpu"] else epochs  # ep 0 = warmup
    times = []
    with mesh:
        for epoch in range(epochs):
            t0 = time.perf_counter()
            state, m = run_epoch(state, epoch)
            final_loss = float(m["loss"])
            dt = time.perf_counter() - t0
            assert np.isfinite(final_loss)
            if epoch > 0:
                times.append(dt)
    units = steps * batch
    imgs_per_sec = units / _median(times)
    _emit({
        "metric": "resnet50_train_images_per_sec_per_chip_devicecached",
        "value": round(imgs_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(imgs_per_sec / BASELINE_IMG_PER_SEC_PER_CHIP, 4),
        "protocol": f"median-of-{len(times)}-epochs",
        **_runs_fields(times, units),
        "note": (
            "same augmentation math as the CLI --device-cache path "
            "(per-batch crop box, per-sample flips); dispatch form is the "
            "epoch-as-one-scan here vs per-step in the Trainer loop"
        ),
    }, None)


def _bench_steps(step_fn, state, batch, steps, rounds=BENCH_ROUNDS):
    """Wall times of ``rounds`` draws of ``steps`` chained step_fn calls.

    Each round keeps dispatch fully async and closes the timing window with
    one loss fetch (the donated state chains every step, so that read
    completes only after all executions have).  Returns (state, times) —
    callers report the median and record all draws (median-of-N protocol).
    """
    import numpy as np

    state, m = step_fn(state, batch)
    assert np.isfinite(float(m["loss"]))
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step_fn(state, batch)
        final_loss = float(m["loss"])
        times.append(time.perf_counter() - t0)
        assert np.isfinite(final_loss)
    return state, times


_FINGERPRINT_CACHE: dict | None = None


def _fingerprint() -> dict:
    """Session fingerprint for every bench artifact (VERDICT r4 #3):
    platform identity plus a canonical chip-speed probe, so cross-session
    drift (measured 1.012→1.034 on the same code across rounds — larger
    than the 0.002 within-run spread) is quantifiable instead of silently
    folded into headline deltas.  The probe is a fixed 4096³ bf16 matmul
    timed median-of-5; comparing ``matmul_probe_tflops`` across two
    artifacts separates "the chip/session was faster" from "the code got
    faster"."""
    global _FINGERPRINT_CACHE
    if _FINGERPRINT_CACHE is not None:
        return _FINGERPRINT_CACHE
    import platform

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    fp = {
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "jax": jax.__version__,
        "python": platform.python_version(),
    }
    if jax.default_backend() == "tpu":
        # Chip-speed probe: chained 4096³ bf16 matmuls in one dispatch, at
        # two rep counts; the slope (t_hi - t_lo)/(reps_hi - reps_lo)
        # cancels the fixed dispatch + scalar-fetch overhead of a call.
        # That overhead is recorded too: session drift can live in
        # either number.
        from functools import partial

        from jax import lax

        n = 4096
        x = jnp.ones((n, n), jnp.bfloat16)

        @partial(jax.jit, static_argnums=1)
        def f(a, reps):
            return lax.fori_loop(0, reps, lambda i, y: (y @ a) / n, a)[0, 0]

        def timed(reps):
            float(f(x, reps))
            draws = []
            for _ in range(5):
                t0 = time.perf_counter()
                float(f(x, reps))
                draws.append(time.perf_counter() - t0)
            return _median(draws)

        lo, hi = 32, 160
        t_lo, t_hi = timed(lo), timed(hi)
        per_matmul = max((t_hi - t_lo) / (hi - lo), 1e-9)
        fp["matmul_probe_tflops"] = round(2 * n**3 / per_matmul / 1e12, 1)
        fp["dispatch_fetch_overhead_ms"] = round(
            max(t_lo - lo * per_matmul, 0.0) * 1e3, 1
        )
    _FINGERPRINT_CACHE = fp
    return fp


def _emit(out: dict, save_path: str | None) -> None:
    """Print the one-line JSON; persist only when ``save_path`` is given
    (callers gate it on the TPU backend so CPU smoke runs never clobber
    the published artifacts with toy-model numbers).  Every emitted
    artifact carries the session fingerprint (``_fingerprint``)."""
    out = {**out, "session": _fingerprint()}
    print(json.dumps(out))
    if save_path is not None:
        with open(save_path, "w") as f:
            json.dump(out, f)


def main_gpt2(moe: bool = False):
    """GPT-2 124M training throughput (BASELINE configs[3]: DP + grad
    accumulation): tokens/sec/chip on synthetic token batches, bf16
    compute, flash attention, full jitted step with accumulation
    microbatches.  Reports model FLOPs utilization (6*N*T fwd+bwd
    approximation over the v5e bf16 peak) for the dense model.

    ``moe=True`` benches the Switch-MoE variant (gpt2_moe, 8 experts,
    top-1 routing, aux loss) with the identical harness — the EP
    capability bench.  Its MFU uses routed FLOPs: 6 * N_activated * T
    (every token runs ONE expert, so N_activated = dense params +
    expert params / E) plus the router matmul — 6*N*T over *total*
    params would overstate top-1 compute ~E-fold on the expert share.
    ``--capacity-factor F`` overrides Switch's 1.25; the measured
    token-drop rate at that capacity is reported alongside."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_training_tpu.models import create_model
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_policy, make_train_step,
    )

    on_tpu = jax.default_backend() == "tpu"
    # Defaults ARE the headline configs, so a bare --save reproduces the
    # committed artifacts: batch 512 / accum 64 for both variants —
    # GPT-2's canonical ~0.5M-token training batch over the measured
    # 8192-token microbatch optimum (per-microbatch traffic scales with
    # TOTAL params — grad accumulation + expert weights — so 8192 beats
    # 4096/16384, MOE_ROOFLINE.json accum sweep).  At fixed microbatch,
    # more microbatches amortize the per-step optimizer cost: dense
    # 147.8k (batch 128) → 149.0k (256) → 149.6k (512); MoE 119.2k
    # (batch 32) → 127.5k (128) → 130.0k (512) tok/s.
    batch = _int_flag("--batch", 512 if on_tpu else 2)
    seq = _int_flag("--seq", 1024 if on_tpu else 128)
    accum = _int_flag("--accum", 64 if on_tpu else 2)
    # Chunked CE keeps the (B, L, vocab) logits out of HBM (the batch-32
    # full-logits step OOMs a 16 GB chip); remat trades FLOPs for
    # activation bytes.
    ce_chunk = _int_flag("--ce-chunk", None)
    remat = "--remat" in sys.argv[1:]
    steps = 12 if on_tpu else 2
    cf = _float_flag("--capacity-factor", None)
    # Long-context runs (--seq beyond GPT-2's native 1024) stretch the
    # learned position table to match.
    overrides = dict(remat=remat, max_seq_len=max(seq, 1024)) if on_tpu else dict(
        num_layers=2, hidden_dim=64, num_heads=2, vocab_size=512,
        max_seq_len=seq, remat=remat, **({"num_experts": 4} if moe else {}),
    )
    if moe:
        # Single-chip bench: experts are not mesh-sharded, so the scatter
        # dispatch (no (T,E,C) one-hots, no dispatch matmul FLOPs —
        # models/moe.py) is the right formulation; EP meshes keep
        # "einsum".  Parity-tested (tests/test_moe.py).
        overrides["moe_dispatch"] = "scatter"
    if moe and cf is not None:
        overrides["moe_capacity_factor"] = cf

    model = create_model(
        "gpt2_moe" if moe else "gpt2", cfg_overrides=overrides,
        dtype=jnp.bfloat16,
    )
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32),
        optax.adamw(3e-4), init_kwargs={"train": False},
    )
    step_fn = make_train_step(
        kind="lm", policy=make_policy("bf16"), num_microbatches=accum,
        base_rng=jax.random.PRNGKey(1), lm_loss_chunk=ce_chunk,
    )
    rng = np.random.default_rng(0)
    b = {"tokens": jnp.asarray(
        rng.integers(0, model.cfg.vocab_size, (batch, seq)), jnp.int32
    )}
    units = batch * seq * steps
    state, times = _bench_steps(step_fn, state, b, steps)
    tokens_per_sec = units / _median(times)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    drop_rate = None
    if moe:
        # One synced step for the sown drop-rate metric (the timing loop
        # reads only the loss to stay async).
        _, m = step_fn(state, b)
        drop_rate = float(m.get("moe_drop_rate", float("nan")))
    if on_tpu:
        from pytorch_distributed_training_tpu.obs.cost import require_peaks

        peak_flops, _ = require_peaks()
    if on_tpu and not moe:
        mfu = (6 * n_params * tokens_per_sec) / peak_flops
    elif on_tpu:
        # Routed FLOPs: top-1 activates one expert per token, so the
        # expert share of 6NT scales by 1/E; the router adds a (d x E)
        # matmul (fwd+bwd ~ 6 * d * E per token).
        e = model.cfg.num_experts
        expert_params = sum(
            leaf.size
            for path, leaf in jax.tree_util.tree_leaves_with_path(state.params)
            if any(getattr(k, "key", None) in ("w_up", "w_down") for k in path)
        )
        activated = n_params - expert_params + expert_params // e
        router_flops_per_tok = 6 * model.cfg.hidden_dim * e * (
            model.cfg.num_layers // 2  # MoE every other block
        )
        mfu = (
            (6 * activated + router_flops_per_tok) * tokens_per_sec
        ) / peak_flops
    else:
        mfu = None
    out = {
        "metric": (
            "gpt2_moe_train_tokens_per_sec_per_chip" if moe
            else "gpt2_124m_train_tokens_per_sec_per_chip"
        ),
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        "batch": batch,
        "seq": seq,
        "accum_steps": accum,
        "ce_chunk": ce_chunk,
        "remat": remat,
        "mfu_vs_v5e_bf16_peak": round(mfu, 4) if mfu else None,
        "protocol": f"median-of-{BENCH_ROUNDS}",
        **_runs_fields(times, units),
    }
    if moe:
        out["num_experts"] = model.cfg.num_experts
        out["total_params"] = n_params
        out["capacity_factor"] = model.cfg.moe_capacity_factor
        out["token_drop_rate"] = (
            round(drop_rate, 4) if drop_rate == drop_rate else None
        )
        out["mfu_accounting"] = (
            "routed FLOPs: 6 * (dense + expert/E params) * tok/s + router"
        )
    save = "MOE_BENCH.json" if moe else "GPT2_BENCH.json"
    _emit(out, save if on_tpu and "--save" in sys.argv[1:] else None)


def main_vit():
    """ViT-B/16 training throughput (BASELINE configs[2]: DP + bf16, the
    AMP-equivalent path): images/sec/chip at 224px, low-memory XLA
    attention on the L=197 token sequence (below the flash kernel's
    measured L>=1024 win threshold), full jitted step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_training_tpu.models import vit_b16
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_policy, make_train_step,
    )

    on_tpu = jax.default_backend() == "tpu"
    # Batch 352 = 8 accumulation microbatches of 44 — the microbatch IS
    # the r4 residency optimum (1038-1073 img/s standalone; 48 and 128
    # measured worse), and accumulation amortizes the Adam step on 86M
    # params (~7% of a bare batch-44 step): 1063 -> 1117 img/s.
    batch = _int_flag("--batch", 352 if on_tpu else 8)
    accum = _int_flag("--accum", 8 if on_tpu else 1)
    steps = (24 // accum if on_tpu else 2) or 3
    overrides = {} if on_tpu else dict(depth=2, hidden_dim=64, num_heads=2,
                                       mlp_dim=128)
    # --remat: rematerialized blocks — trades ~33% forward FLOPs for an
    # order-of-magnitude cut in saved-activation HBM traffic; on a
    # bandwidth-bound step that is a throughput *win* (VERDICT r2 item 3).
    remat = "--remat" in sys.argv[1:]
    # (B, H, L, Dh)-contract attention A/B (VERDICT r4 #4; VIT_ROOFLINE
    # (deleted: not measured on the current machine)
    # "analysis"): bhld2 (head-major q/k/v straight from the projection
    # GEMMs) is the measured winner at the batch-44 headline and the
    # model default; --attn-layout auto/bhld reproduce the A/B legs.
    attn_layout = _flag("--attn-layout", "bhld2", str)
    overrides["attn_layout"] = attn_layout

    model = vit_b16(num_classes=1000, cfg_overrides=overrides,
                    dtype=jnp.bfloat16, remat=remat)
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3), jnp.bfloat16),
        optax.adamw(1e-3), init_kwargs={"train": False},
    )
    step_fn = make_train_step(
        kind="image_classifier", policy=make_policy("bf16"),
        num_microbatches=accum,
    )
    rng = np.random.default_rng(0)
    b = {"image": jnp.asarray(
        rng.standard_normal((batch, 224, 224, 3), np.float32), jnp.bfloat16
    ), "label": jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32)}
    units = batch * steps
    state, times = _bench_steps(step_fn, state, b, steps)
    imgs_per_sec = units / _median(times)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    # fwd+bwd FLOPs ~ 6 * params * tokens-per-image (196 patches + CLS).
    mfu = None
    if on_tpu:
        from pytorch_distributed_training_tpu.obs.cost import require_peaks

        mfu = (6 * n_params * 197 * imgs_per_sec) / require_peaks()[0]
    _emit({
        "metric": "vit_b16_train_images_per_sec_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "images/sec/chip",
        "mfu_vs_v5e_bf16_peak": round(mfu, 4) if mfu else None,
        "batch": batch,
        "accum_steps": accum,
        "remat": remat,
        "attn_layout": attn_layout,
        "protocol": f"median-of-{BENCH_ROUNDS}",
        **_runs_fields(times, units),
    }, "VIT_BENCH.json" if on_tpu and "--save" in sys.argv[1:] else None)


def main_generate():
    """KV-cache decode throughput: tokens/sec generating from GPT-2 124M
    with the scan decoder (models/generate.py) — the inference-side
    capability number."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tpu.models import gpt2_124m
    from pytorch_distributed_training_tpu.models.generate import (
        generate, uses_approx_top_k,
    )

    on_tpu = jax.default_backend() == "tpu"
    batch = _int_flag("--batch", 32 if on_tpu else 2)
    prompt_len, new_tokens = (32, 224) if on_tpu else (4, 8)
    overrides = None if on_tpu else dict(
        num_layers=2, hidden_dim=64, num_heads=2, vocab_size=512,
    )
    model = gpt2_124m(cfg_overrides=overrides, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, model.cfg.vocab_size, (batch, prompt_len)), jnp.int32
    )
    variables = model.init(jax.random.PRNGKey(0), prompt, train=False)
    # Inference reads every weight once per tick; serving casts params to
    # bf16 (halves the 496 MB/tick fp32 weight traffic — the train-state
    # fp32 tree is a training artifact).  --fp32-params restores the r4
    # measurement condition.
    params = variables["params"]
    if "--fp32-params" not in sys.argv[1:]:
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params
        )

    top_k = _int_flag("--top-k", 40) or None  # 0 -> full-vocab sampling
    exact_top_k = "--exact-top-k" in sys.argv[1:]

    def measure(prompt_b):
        def run(key):
            return generate(
                model, params, prompt_b,
                max_new_tokens=new_tokens, rng=key, temperature=1.0,
                top_k=top_k, exact_top_k=exact_top_k,
            )

        np.asarray(run(jax.random.PRNGKey(1)))  # sync (compile + first run)
        times = []
        for i in range(BENCH_ROUNDS):
            t0 = time.perf_counter()
            np.asarray(run(jax.random.PRNGKey(2 + i)))
            times.append(time.perf_counter() - t0)
        return times

    times = measure(prompt)
    units = batch * new_tokens
    toks_per_sec = units / _median(times)
    # Scaling row: batch-32 decode is kernel-count-bound (GEN_ROOFLINE
    # (deleted: not measured on the current machine)
    # accounting), so the serving-throughput number is the large-batch one.
    scale_batch = 128 if on_tpu else 4
    prompt_big = jnp.asarray(
        rng.integers(0, model.cfg.vocab_size, (scale_batch, prompt_len)),
        jnp.int32,
    )
    times_big = measure(prompt_big)
    toks_big = scale_batch * new_tokens / _median(times_big)
    _emit({
        "metric": "gpt2_124m_generate_tokens_per_sec",
        "value": round(toks_per_sec, 1),
        "unit": "tokens/sec",
        "protocol": f"median-of-{BENCH_ROUNDS}",
        **_runs_fields(times, units),
        "batch": batch,
        "new_tokens": new_tokens,
        "params_dtype": (
            "fp32" if "--fp32-params" in sys.argv[1:] else "bf16"
        ),
        "sampling": f"temperature=1.0, top_k={top_k}",
        "top_k_threshold": (
            None if top_k is None
            else ("lax.approx_max_k (recall>=0.95)"
                  if uses_approx_top_k(exact_top_k) else "exact lax.top_k")
        ),
        "scaling_row": {
            "batch": scale_batch,
            "tokens_per_sec": round(toks_big, 1),
        },
        "roofline": (
            "round-5 analysis (tools/gen_diag.py; its record is deleted "
            "and none of it is measured on the current machine): byte bound "
            "(params + KV reads) is 47.6k tok/s at batch 32; the batch-32 "
            "step is kernel-count-bound (~15-20 fused kernels/layer x "
            "launch overhead ~= 2x the component-sum time), so "
            "throughput scales with batch to ~0.5 of the byte bound"
        ),
        "note": (
            "KV-cache scan decode (models/generate.py). The exact "
            "full-vocab lax.top_k sort measured 45% of the decode step at "
            "GPT-2's 50k vocab (6.5k tok/s exact vs 11.3k approx vs 11.8k "
            "full-vocab sampling at batch 32); --exact-top-k restores the "
            "exact cut."
        ),
    }, "GEN_BENCH.json" if on_tpu and "--save" in sys.argv[1:] else None)


def main_serve():
    """Continuous-batching serving bench (SERVE_BENCH.json): an offered-load
    sweep over a FIXED mixed-length workload — per load point, the
    iteration-level engine (serve/) and the static-batch baseline
    (models/generate.py in arrival-order groups of ``slots``) serve the
    SAME requests and arrival trace, recording TTFT/TPOT p50/p99, queue
    depth, and goodput (completed-request tokens per second).

    The static baseline is measured, not modeled: each group's
    ``generate()`` call is timed live (one compiled shape — prompts padded
    to the global max, shared budget = the global max, which IS static
    batching's waste: every row decodes to the longest budget and prefills
    one token per tick).  Its timeline composes measured durations with the
    arrival constraints (a group starts when its last member has arrived
    and the previous group finished; tokens materialize only at group end —
    that cliff is exactly what iteration-level scheduling removes).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tpu.models import gpt2_124m
    from pytorch_distributed_training_tpu.models.generate import generate
    from pytorch_distributed_training_tpu.serve import (
        ContinuousScheduler, Request, ServingEngine, summarize_records,
    )
    from pytorch_distributed_training_tpu.serve.metrics import percentile

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        overrides, dtype = None, jnp.bfloat16
        slots = _int_flag("--slots", 32)
        chunk, n_requests = 64, 128
        p_lo, p_hi, b_lo, b_hi = 16, 192, 32, 192
        # Mean ~112 tok/request against the measured ~12k tok/s batch-32
        # decode rate (GEN_BENCH): saturation sits around ~100 rps — the
        # sweep brackets it (latency regime below, goodput regime above).
        rates = [16.0, 64.0, 256.0]
    else:
        # CPU proxy: sized so per-tick model compute (not Python dispatch)
        # dominates — measured: at d128 the dispatched per-tick loop loses
        # its algorithmic win to overhead, at d256 it shows through.
        overrides = dict(num_layers=4, hidden_dim=256, num_heads=4,
                         vocab_size=4096, max_seq_len=160)
        dtype = jnp.float32
        slots = _int_flag("--slots", 4)
        chunk, n_requests = 16, 24
        p_lo, p_hi, b_lo, b_hi = 8, 96, 8, 48
        rates = [4.0, 16.0, 64.0]
    model = gpt2_124m(cfg_overrides=overrides, dtype=dtype)
    rng = np.random.default_rng(0)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False
    )["params"]
    params = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)

    # Fixed mixed-length workload, shared by every sweep point and both
    # serving disciplines; only the arrival trace changes with the rate.
    prompts = [
        rng.integers(0, model.cfg.vocab_size,
                     (int(rng.integers(p_lo, p_hi + 1)),)).astype(np.int32)
        for _ in range(n_requests)
    ]
    budgets = rng.integers(b_lo, b_hi + 1, n_requests)
    p_pad = max(p.size for p in prompts)
    shared_new = int(budgets.max())

    engine = ServingEngine(
        model, params, num_slots=slots, max_len=model.cfg.max_seq_len,
        prefill_chunk=chunk, temperature=0.0, seed=0,
    )

    def run_continuous(arrivals):
        engine.reset()
        sched = ContinuousScheduler(engine, max_queue=n_requests)
        t0 = time.monotonic()
        recs = sched.run([
            Request(i, prompts[i], int(budgets[i]), float(t0 + arrivals[i]))
            for i in range(n_requests)
        ])
        # elapsed=None: summarize derives first-arrival → last-finish from
        # the records — the SAME interval definition the static timeline
        # uses, so the goodput denominators are comparable.
        return summarize_records(
            recs, elapsed=None,
            queue_depth_samples=sched.queue_depth_samples,
            rejected=sched.rejected,
        )

    def static_batch(group):
        toks = np.zeros((slots, p_pad), np.int32)
        lens = np.full((slots,), p_pad, np.int32)
        for j, i in enumerate(group):
            toks[j, :prompts[i].size] = prompts[i]
            lens[j] = prompts[i].size
        out = generate(
            model, params, jnp.asarray(toks), max_new_tokens=shared_new,
            rng=jax.random.PRNGKey(1), prompt_lengths=jnp.asarray(lens),
            temperature=0.0,
        )
        np.asarray(out)  # block: the timed unit is one full batch

    def run_static(arrivals):
        groups = [
            list(range(g, min(g + slots, n_requests)))
            for g in range(0, n_requests, slots)
        ]
        static_batch(groups[0])  # warm the one compiled shape
        t_end_prev = 0.0
        ttfts, group_durs, group_ends = [], [], []
        for group in groups:
            t0 = time.perf_counter()
            static_batch(group)
            dur = time.perf_counter() - t0
            start = max(t_end_prev, max(arrivals[i] for i in group))
            t_end_prev = start + dur
            group_durs.append(dur)
            group_ends.append(t_end_prev)
            for i in group:
                ttfts.append(t_end_prev - arrivals[i])
        useful = int(budgets.sum())  # each row's OWN budget counts as useful
        elapsed = max(group_ends) - float(min(arrivals))
        return {
            "completed": n_requests,
            "generated_tokens": useful,
            "elapsed_s": round(elapsed, 4),
            "goodput_tok_per_s": round(useful / elapsed, 2),
            "ttft_p50_s": round(percentile(ttfts, 50), 6),
            "ttft_p99_s": round(percentile(ttfts, 99), 6),
            # Tokens materialize only at batch end: the per-token pace is
            # the batch duration spread over its shared decode budget.
            "tpot_p50_s": round(
                percentile([d / shared_new for d in group_durs], 50), 6
            ),
        }

    # Warm the continuous path (AOT compile happened at engine init; one
    # short trace warms the host loop) before any timed sweep point.
    run_continuous(np.zeros(n_requests))

    sweep = []
    for rate in rates:
        arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
        cont = run_continuous(arrivals)
        stat = run_static(arrivals)
        sweep.append({
            "offered_rps": rate,
            "continuous": cont,
            "static": stat,
            "goodput_gain": round(
                cont["goodput_tok_per_s"] / stat["goodput_tok_per_s"], 3
            ),
            "ttft_p50_speedup": round(
                stat["ttft_p50_s"] / cont["ttft_p50_s"], 2
            ) if cont["ttft_p50_s"] else None,
        })

    # ------------------------------------------------------------------ #
    # Paged-vs-contiguous at a FIXED cache byte budget: the contiguous
    # pool reserves max_len per slot up front, so the budget caps its slot
    # count; the paged pool spends the same positions as fixed-size blocks
    # allocated on demand, so the same bytes sustain more live requests
    # (and the block table lifts the per-slot prompt+budget bound).
    # ------------------------------------------------------------------ #
    max_len = model.cfg.max_seq_len
    block_size = 16
    budget_positions = slots * max_len  # == the contiguous pool's bytes
    paged_slots = 2 * slots
    paged_engine = ServingEngine(
        model, params, num_slots=paged_slots, max_len=max_len,
        prefill_chunk=chunk, temperature=0.0, seed=0,
        paged=True, block_size=block_size,
        num_blocks=budget_positions // block_size,
    )

    def run_engine(eng, arrivals):
        eng.reset()
        sched = ContinuousScheduler(eng, max_queue=n_requests)
        t0 = time.monotonic()
        recs = sched.run([
            Request(i, prompts[i], int(budgets[i]), float(t0 + arrivals[i]))
            for i in range(n_requests)
        ])
        return summarize_records(
            recs, elapsed=None,
            queue_depth_samples=sched.queue_depth_samples,
            rejected=sched.rejected,
            active_slot_samples=sched.active_slot_samples,
            engine_stats=eng.stats(),
        )

    run_engine(paged_engine, np.zeros(n_requests))  # warm host loop
    burst = np.zeros(n_requests)  # heaviest pressure: everything at t=0
    paged_burst = run_engine(paged_engine, burst)
    cont_burst = run_engine(engine, burst)
    paged_vs_contiguous = {
        "cache_budget_positions": budget_positions,
        "block_size": block_size,
        "contiguous": {"num_slots": slots, **cont_burst},
        "paged": {"num_slots": paged_slots, **paged_burst},
        "live_slots_gain": round(
            paged_burst["live_slots_max"] / cont_burst["live_slots_max"], 3
        ),
        "goodput_gain": round(
            paged_burst["goodput_tok_per_s"]
            / cont_burst["goodput_tok_per_s"], 3
        ),
        "protocol": (
            "identical burst trace (all arrivals at t=0) through both "
            "pools holding the SAME cache positions: contiguous "
            f"{slots} x {max_len}, paged "
            f"{budget_positions // block_size} x {block_size} blocks over "
            f"{paged_slots} slots; live_slots_max is the concurrency the "
            "pool actually sustained"
        ),
    }

    # ------------------------------------------------------------------ #
    # Prefix caching: a shared system prompt at 0% / 50% / 90% hit rates.
    # Offered prompt tokens are identical across legs (same lengths);
    # only the SHARING differs, so computed-prefill deltas are pure
    # cache effect.  FLOPs ≈ 2 * params * computed prompt tokens.
    # ------------------------------------------------------------------ #
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    flops_per_token = 2 * n_params
    sys_len = 4 * block_size  # 64 tokens = 4 full shareable blocks
    n_prefix = max(n_requests - 4, 10)
    tail_lens = rng.integers(8, 17, n_prefix)
    sys_prompt = rng.integers(
        0, model.cfg.vocab_size, (sys_len,)
    ).astype(np.int32)
    # The prefix pool gets headroom (2x the budget leg): this workload
    # measures the CACHE effect, and under a starved pool the refcount-0
    # sys blocks would be evicted between sharers, conflating the two
    # axes the artifact separates (eviction pressure is the
    # paged_vs_contiguous leg's job).
    prefix_engine = ServingEngine(
        model, params, num_slots=paged_slots, max_len=max_len,
        prefill_chunk=chunk, temperature=0.0, seed=0,
        paged=True, block_size=block_size,
        num_blocks=2 * budget_positions // block_size,
    )
    prefix_legs = []
    for frac in (0.0, 0.5, 0.9):
        prefix_engine.reset()  # clears the prefix cache between legs
        shared = int(round(frac * n_prefix))
        reqs = []
        for i in range(n_prefix):
            tail = rng.integers(
                0, model.cfg.vocab_size, (int(tail_lens[i]),)
            ).astype(np.int32)
            if i < shared:
                head = sys_prompt
            else:  # unique head of the same length: same offered tokens
                head = rng.integers(
                    0, model.cfg.vocab_size, (sys_len,)
                ).astype(np.int32)
            reqs.append(Request(
                i, np.concatenate([head, tail]).astype(np.int32), 8
            ))
        # Request 0 arrives alone and warms the cache (blocks register
        # only once their K/V are fully written, so identical requests
        # admitted the SAME tick as the cold one cannot hit it); the
        # bulk arrives after — the steady-state shape of a shared system
        # prompt under live traffic.
        t0 = time.monotonic()
        sched = ContinuousScheduler(prefix_engine, max_queue=n_prefix)
        recs = sched.run([
            Request(r.id, r.prompt, r.max_new_tokens,
                    t0 if r.id == 0 else t0 + 2.0)
            for r in reqs
        ])
        st = prefix_engine.stats()
        prefix_legs.append({
            "shared_fraction": frac,
            "completed": len(recs),
            "prefill_tokens_offered": st["prefill_tokens_offered"],
            "prefill_tokens_computed": st["prefill_tokens_computed"],
            "prefill_flops": st["prefill_tokens_computed"] * flops_per_token,
            "prefix_hit_rate": round(
                st["prefix_hit_tokens"] / st["prefix_lookup_tokens"], 4
            ),
            "ttft_p50_s": summarize_records(recs)["ttft_p50_s"],
        })
    prefix_caching = {
        "system_prompt_tokens": sys_len,
        "requests": n_prefix,
        "num_blocks": 2 * budget_positions // block_size,
        "block_size": block_size,
        "legs": prefix_legs,
        "prefill_flops_saved_at_90pct": round(
            prefix_legs[0]["prefill_flops"] / prefix_legs[-1]["prefill_flops"],
            3,
        ),
        "note": (
            "identical offered prompt tokens per leg; only the shared "
            "fraction changes, so the computed-FLOPs ratio is the pure "
            "prefix-cache effect.  Request 0 arrives alone to warm the "
            "cache (blocks register when fully written; identical "
            "requests admitted the same tick as the cold one cannot hit "
            "it), the rest arrive together 2s later."
        ),
    }

    # ------------------------------------------------------------------ #
    # Speculative decoding: the spec engine (prompt-lookup drafter +
    # multi-token verify program) vs the plain engine on IDENTICAL
    # mixed-length burst traces, in the two n-gram regimes that bracket
    # it: repetitive tails (draftable — the drafter's target workload)
    # and random tails under temperature-1 sampling (adversarial — the
    # drafter almost never fires, pinning its overhead).  Both engines
    # emit the same token count per trace (greedy is token-exact;
    # sampled runs share fixed budgets with no EOS), so the wall-clock
    # ratio IS the accepted-tokens/sec ratio.  Paired alternating-order
    # rounds + median-of-ratios: this sandbox's CPU carries multi-second
    # scheduling drift that a fixed leg order would convert into a fake
    # win for whichever leg runs second (the PR 3 telemetry-bench
    # lesson).
    # ------------------------------------------------------------------ #
    import gc

    # The earlier legs' engines pin several full KV pools; release them
    # so the paired timing below isn't fighting their memory footprint
    # (sched/recs still reference prefix_engine through
    # ContinuousScheduler.engine, so they must go too).
    del engine, paged_engine, prefix_engine, sched, recs
    gc.collect()

    # k=5 is the CPU-proxy sweet spot (bench-swept: k=4 under-fills the
    # verify width the short-period cycles can use, k>=6 pays more
    # LM-head width than the acceptance tail returns).
    spec_k, spec_ngram = 5, 4
    if on_tpu:
        s_model, s_params = model, params
        s_max_len, s_slots, s_n, s_rounds = model.cfg.max_seq_len, slots, 32, 3
        sp_lo, sp_hi, sb_lo, sb_hi = 16, 48, 192, 256
    else:
        # Longer-context proxy than the sweep model: speculation's win is
        # in the decode tail, so budgets dominate prompts here.
        s_over = dict(num_layers=4, hidden_dim=256, num_heads=4,
                      vocab_size=4096, max_seq_len=256)
        s_model = gpt2_124m(cfg_overrides=s_over, dtype=dtype)
        s_params = jax.tree_util.tree_map(
            lambda x: x.astype(dtype),
            s_model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                train=False,
            )["params"],
        )
        s_max_len, s_slots, s_n, s_rounds = 256, 4, 10, 9
        sp_lo, sp_hi, sb_lo, sb_hi = 8, 24, 160, 224

    srng = np.random.default_rng(7)

    def spec_workload(repetitive):
        ps, bs = [], []
        for _ in range(s_n):
            plen = int(srng.integers(sp_lo, sp_hi + 1))
            if repetitive:
                # Short repetition periods (2-4 tokens): the drafter
                # locks onto the cycle within one period, so acceptance
                # reflects draftable structure rather than lock-on lag.
                pat = srng.integers(
                    0, s_model.cfg.vocab_size, (int(srng.integers(2, 5)),)
                )
                p = np.tile(pat, -(-plen // pat.size))[:plen]
            else:
                p = srng.integers(0, s_model.cfg.vocab_size, (plen,))
            ps.append(p.astype(np.int32))
            bs.append(int(srng.integers(sb_lo, sb_hi + 1)))
        return ps, bs

    def spec_run(eng, ps, bs):
        eng.reset()
        sched = ContinuousScheduler(eng, max_queue=s_n)
        t0 = time.monotonic()
        recs = sched.run(
            [Request(i, ps[i], bs[i], t0) for i in range(s_n)]
        )
        el = time.monotonic() - t0
        return el, summarize_records(
            recs, elapsed=el, engine_stats=eng.stats()
        )

    spec_legs = {}
    for regime, temp in (("repetitive", 0.0), ("adversarial", 1.0)):
        e_kw = dict(num_slots=s_slots, max_len=s_max_len,
                    prefill_chunk=chunk, temperature=temp, seed=0)
        e_base = ServingEngine(s_model, s_params, **e_kw)
        e_spec = ServingEngine(
            s_model, s_params, spec_k=spec_k, spec_ngram=spec_ngram, **e_kw
        )
        ps, bs = spec_workload(regime == "repetitive")
        spec_run(e_base, ps, bs)  # warm host loops
        spec_run(e_spec, ps, bs)
        t_base, t_spec = [], []
        for r in range(s_rounds):
            if r % 2 == 0:
                tb, _ = spec_run(e_base, ps, bs)
                ts, ssum = spec_run(e_spec, ps, bs)
            else:
                ts, ssum = spec_run(e_spec, ps, bs)
                tb, _ = spec_run(e_base, ps, bs)
            t_base.append(tb)
            t_spec.append(ts)
        sp = ssum.get("spec") or {}
        spec_legs[regime] = {
            "temperature": temp,
            "requests": s_n,
            "slots": s_slots,
            "prompt_len_range": [sp_lo, sp_hi],
            "max_new_range": [sb_lo, sb_hi],
            "base_times_s": [round(x, 3) for x in t_base],
            "spec_times_s": [round(x, 3) for x in t_spec],
            # Headline estimator: best-of-N per leg.  Each leg's minimum
            # is its scheduling-noise floor; per-round ratios let ONE
            # stalled leg poison a round, and this sandbox's bursts run
            # multi-second (the PR 3 telemetry-bench lesson, sharpened).
            "accepted_tokens_per_sec_ratio": round(
                min(t_base) / min(t_spec), 3
            ),
            "ratio_median_of_rounds": round(
                float(np.median([b / s for b, s in zip(t_base, t_spec)])),
                3,
            ),
            "acceptance_rate": sp.get("acceptance_rate"),
            "tokens_per_slot_tick": sp.get("tokens_per_slot_tick"),
            "spec_goodput_tok_per_s": ssum.get("goodput_tok_per_s"),
        }
    speculative = {
        "spec_k": spec_k,
        "spec_ngram": spec_ngram,
        "model": (
            "gpt2_124m" if on_tpu else "gpt2-tiny-256ctx(cpu-proxy)"
        ),
        "legs": spec_legs,
        "headline_speedup": spec_legs["repetitive"][
            "accepted_tokens_per_sec_ratio"
        ],
        "adversarial_ratio": spec_legs["adversarial"][
            "accepted_tokens_per_sec_ratio"
        ],
        "protocol": (
            "identical burst traces through spec and plain engines; "
            "wall-clock ratio == accepted-tokens/sec ratio because both "
            "emit the same token count; alternating leg order, "
            "best-of-rounds per leg (each leg's min is its scheduling-"
            "noise floor; median-of-round-ratios cross-checked); "
            "repetitive tails = tiled 2-4-token patterns (greedy), "
            "adversarial = uniform-random prompts at temperature 1.0 "
            "(rejection-sampled verify, drafter almost never fires); "
            "tokens_per_slot_tick and acceptance_rate are counter-exact "
            "(no clocks)"
        ),
    }

    # ------------------------------------------------------------------ #
    # Replica scaling + affinity routing (serve/router.py): two engine
    # replicas behind the prefix-affinity router vs one engine, at
    # PROPORTIONAL offered load (N replicas get N x the request rate).
    # Scaling leg: the offered rate is calibrated to ~45% of the measured
    # single-replica saturated goodput, so each replica runs inside its
    # capacity and tier goodput tracks offered load — the claim is that
    # the tier SUSTAINS proportional load with flat SLOs.  On this CPU
    # proxy the replicas share one host's compute (sequential ticks), so
    # saturated-regime chip scaling is a TPU-leg question (chip-session
    # queue); sub-saturation sustainment is what the proxy can honestly
    # pin.  Affinity leg: a 90%-shared-system-prompt trace through 2
    # paged replicas with affinity routing on vs off — counter-exact
    # prefix-hit rates, no clocks.
    # ------------------------------------------------------------------ #
    from pytorch_distributed_training_tpu.serve import ReplicaRouter

    if on_tpu:
        r_model, r_params = model, params
        r_slots, r_n, r_b_lo, r_b_hi = 16, 48, 48, 96
    else:
        r_model, r_params = s_model, s_params
        r_slots, r_n, r_b_lo, r_b_hi = 2, 14, 24, 40
    rrng = np.random.default_rng(11)

    def r_workload(n):
        ps = [
            rrng.integers(
                0, r_model.cfg.vocab_size,
                (int(rrng.integers(8, 17)),)
            ).astype(np.int32)
            for _ in range(n)
        ]
        bs = [int(rrng.integers(r_b_lo, r_b_hi + 1)) for _ in range(n)]
        return ps, bs

    def mk_router_engine(**kw):
        base = dict(
            num_slots=r_slots, max_len=r_model.cfg.max_seq_len,
            prefill_chunk=chunk, temperature=0.0, seed=0,
        )
        base.update(kw)
        return ServingEngine(r_model, r_params, **base)

    def run_router(engines_list, ps, bs, arrivals, affinity=True):
        for e in engines_list:
            e.reset()
        router = ReplicaRouter(
            engines_list, max_queue=len(ps), affinity=affinity
        )
        t0 = time.monotonic()
        recs = router.run([
            Request(i, ps[i], bs[i], float(t0 + arrivals[i]))
            for i in range(len(ps))
        ])
        return router, summarize_records(recs, elapsed=None)

    eng_r1 = [mk_router_engine()]
    eng_r2 = eng_r1 + [mk_router_engine()]
    ps_cal, bs_cal = r_workload(8)
    run_router(eng_r1, ps_cal, bs_cal, np.zeros(8))  # warm host loop
    _, cal = run_router(eng_r1, ps_cal, bs_cal, np.zeros(8))
    c1 = cal["goodput_tok_per_s"]
    ps1, bs1 = r_workload(r_n)
    ps2, bs2 = r_workload(2 * r_n)
    base_rate = 0.45 * c1 / float(np.mean(bs1))
    g1s, g2s, t1s, t2s = [], [], [], []
    for rnd in range(3):
        for leg in ((1, 2) if rnd % 2 == 0 else (2, 1)):
            if leg == 1:
                arr = np.cumsum(rrng.exponential(1.0 / base_rate, r_n))
                _, s1 = run_router(eng_r1, ps1, bs1, arr)
                g1s.append(s1["goodput_tok_per_s"])
                t1s.append(s1["ttft_p50_s"])
            else:
                arr = np.cumsum(
                    rrng.exponential(1.0 / (2 * base_rate), 2 * r_n)
                )
                _, s2 = run_router(eng_r2, ps2, bs2, arr)
                g2s.append(s2["goodput_tok_per_s"])
                t2s.append(s2["ttft_p50_s"])
    scaling = {
        "slots_per_replica": r_slots,
        "single_replica_saturated_goodput": c1,
        "offered_rps_per_replica": round(base_rate, 3),
        "requests": [r_n, 2 * r_n],
        "goodput_1_replica": [round(g, 2) for g in g1s],
        "goodput_2_replicas": [round(g, 2) for g in g2s],
        # Best-of-rounds per leg (each leg's max goodput is its
        # scheduling-noise floor — the PR 7 estimator, inverted for a
        # maximize-metric).
        "goodput_scaling_1_to_2": round(max(g2s) / max(g1s), 3),
        "ttft_p50_1_replica": min(t1s),
        "ttft_p50_2_replicas": min(t2s),
        "protocol": (
            "offered load calibrated to ~45% of measured 1-replica "
            "saturated goodput, scaled proportionally with replicas "
            "(N replicas serve N x requests at N x rate); goodput from "
            "first arrival to last finish; 3 alternating rounds, "
            "best-of-rounds per leg; CPU replicas share one host "
            "(sequential ticks) so this pins proportional-load "
            "SUSTAINMENT — flat TTFT at 2x load — not chip-count "
            "compute scaling (TPU leg: chip-session queue)"
        ),
    }

    # Affinity leg: two shared 4-block system prompts, 90% shared tails.
    # The trace must be BUSY enough that least-loaded actually alternates
    # replicas (an idle tier ties every decision to replica 0 and the
    # control leg degenerates into affinity-by-accident): arrivals at
    # ~4x the per-request service rate keep the last request in flight
    # when the next routes, so the control spreads hot prompts onto cold
    # replicas and pays the prefix recompute affinity avoids.
    aff_block = 16
    aff_sys = [
        rrng.integers(
            0, r_model.cfg.vocab_size, (4 * aff_block,)
        ).astype(np.int32)
        for _ in range(2)
    ]
    n_aff = 20
    aff_engines = [
        mk_router_engine(
            num_slots=max(r_slots, 3), paged=True, block_size=aff_block,
            num_blocks=48,
        )
        for _ in range(2)
    ]
    aff_reqs = []
    for i in range(n_aff):
        tail = rrng.integers(
            0, r_model.cfg.vocab_size, (int(rrng.integers(8, 17)),)
        ).astype(np.int32)
        head = aff_sys[i % 2] if i < int(0.9 * n_aff) else rrng.integers(
            0, r_model.cfg.vocab_size, (4 * aff_block,)
        ).astype(np.int32)
        aff_reqs.append((np.concatenate([head, tail]), 32))
    # Requests 0/1 arrive alone and warm one replica each; the rest
    # arrive at sustained load so routing sees the registered blocks —
    # the steady-state shape of shared system prompts under live traffic.
    aff_arrivals = np.array(
        [0.0, 0.3] + [1.0 + 0.05 * i for i in range(n_aff - 2)]
    )
    aff_legs = {}
    for mode in ("affinity", "least_loaded"):
        router, _ = run_router(
            aff_engines,
            [p for p, _ in aff_reqs], [b for _, b in aff_reqs],
            aff_arrivals, affinity=(mode == "affinity"),
        )
        st = router.engine_stats()
        aff_legs[mode] = {
            "prefix_hit_rate": round(
                st["prefix_hit_tokens"] / st["prefix_lookup_tokens"], 4
            ),
            "prefill_tokens_computed": st["prefill_tokens_computed"],
            "routed": router.stats()["routed"],
            "affinity_hits": router.affinity_hits,
            "rebalanced": router.rebalanced,
        }
    replica_router = {
        "scaling": scaling,
        "affinity": {
            "system_prompt_tokens": 4 * aff_block,
            "requests": n_aff,
            "shared_fraction": 0.9,
            "legs": aff_legs,
            "hit_rate_gain": round(
                aff_legs["affinity"]["prefix_hit_rate"]
                - aff_legs["least_loaded"]["prefix_hit_rate"], 4
            ),
            "note": (
                "identical trace through 2 paged replicas; affinity "
                "routing lands every hot-prefix prompt on the replica "
                "holding its blocks (counter-exact hit rates, no "
                "clocks); least-loaded spreads them, re-computing the "
                "prefix on the cold replica"
            ),
        },
    }

    # ------------------------------------------------------------------ #
    # Disaggregated prefill/decode (serve/disagg.py): the role split vs
    # the interleaved engine under a LONG-PROMPT BURST, at equal offered
    # load and equal slot budget.  The interleaved engine's per-tick cost
    # always includes its full-width (S, C) prefill program while any
    # prompt is chunking in; the disagg decode pool's tick rides a
    # (P, C) prefill with P << S — so co-scheduled requests' decode TPOT
    # stops paying for strangers' prompts.  Wall-clock legs: paired
    # alternating-order rounds, best-of-rounds per leg (this box's noise
    # discipline).  Headline = short-request decode TPOT p99 ratio.
    # ------------------------------------------------------------------ #
    from pytorch_distributed_training_tpu.serve import (
        DisaggServingEngine, VirtualClock,
    )

    dg_total = 5  # equal slot budget: 5 interleaved == 1 prefill + 4 decode
    # FEWER shorts than slots: the interleaved engine must have a free
    # slot for each long prompt WHILE the shorts decode, or the burst
    # never overlaps them and both legs measure an unburdened decode.
    n_short, n_long = 4, 4
    short_prompts = [
        rng.integers(0, model.cfg.vocab_size,
                     (int(rng.integers(8, 13)),)).astype(np.int32)
        for _ in range(n_short)
    ]
    long_prompts = [
        rng.integers(0, model.cfg.vocab_size, (120,)).astype(np.int32)
        for _ in range(n_long)
    ]
    short_budget, long_budget = 40, 4
    short_ids = set(range(n_short))

    def mk_interleaved():
        return ServingEngine(
            model, params, num_slots=dg_total,
            max_len=model.cfg.max_seq_len, prefill_chunk=chunk,
            temperature=0.0, seed=0, paged=True, block_size=block_size,
        )

    def mk_disagg():
        return DisaggServingEngine(
            model, params, prefill_slots=1, decode_slots=dg_total - 1,
            max_len=model.cfg.max_seq_len, prefill_chunk=chunk,
            temperature=0.0, seed=0, paged=True, block_size=block_size,
        )

    def run_burst(eng):
        eng.reset()
        sched = ContinuousScheduler(eng, max_queue=n_short + n_long)
        t0 = time.monotonic()
        reqs = [
            Request(i, short_prompts[i], short_budget, t0)
            for i in range(n_short)
        ] + [
            # The burst: long prompts land while the shorts decode.
            Request(n_short + j, long_prompts[j], long_budget,
                    t0 + 0.05 * (j + 1))
            for j in range(n_long)
        ]
        recs = sched.run(reqs)
        tpots = [
            r["tpot"] for r in recs
            if r["id"] in short_ids and r["tpot"] is not None
        ]
        return {
            "tpot_p50_s": round(percentile(tpots, 50), 6),
            "tpot_p99_s": round(percentile(tpots, 99), 6),
        }

    inter_eng, disagg_eng = mk_interleaved(), mk_disagg()
    run_burst(inter_eng)  # warm both host loops
    run_burst(disagg_eng)
    burst_rounds = {"interleaved": [], "disagg": []}
    for rnd in range(3):
        order = (
            [("interleaved", inter_eng), ("disagg", disagg_eng)]
            if rnd % 2 == 0
            else [("disagg", disagg_eng), ("interleaved", inter_eng)]
        )
        for name, eng in order:
            burst_rounds[name].append(run_burst(eng))
    burst_best = {
        name: min(rounds, key=lambda r: r["tpot_p99_s"])
        for name, rounds in burst_rounds.items()
    }
    del inter_eng, disagg_eng
    gc.collect()

    # Tiered KV store: hierarchy hit rate with the host tier ON vs OFF
    # on a 90%-shared-prefix trace under eviction pressure (big disjoint
    # requests whose worst-case span reclaims the whole pool between
    # sharers).  Counter-exact, virtual clock — no wall time involved:
    # with the tier OFF an evicted sys prefix recomputes; ON it spills
    # to host RAM and restores on the hash-chain hit.
    sys_prompt_t = rng.integers(
        0, model.cfg.vocab_size, (4 * block_size,)
    ).astype(np.int32)
    n_tier = 10  # 9 share the sys head, 1 unique = the 10% cold share
    tier_reqs = []
    for k in range(n_tier):
        if k:  # pressure between sharers: span == the whole pool
            tier_reqs.append((rng.integers(
                0, model.cfg.vocab_size, (150,)
            ).astype(np.int32), 8))
        head = sys_prompt_t if k != n_tier - 1 else rng.integers(
            0, model.cfg.vocab_size, (4 * block_size,)
        ).astype(np.int32)
        tail = rng.integers(
            0, model.cfg.vocab_size, (int(rng.integers(8, 17)),)
        ).astype(np.int32)
        tier_reqs.append((np.concatenate([head, tail]), 8))
    tier_legs = {}
    for host_on in (False, True):
        tier = DisaggServingEngine(
            model, params, prefill_slots=1, decode_slots=1,
            max_len=model.cfg.max_seq_len, prefill_chunk=chunk,
            temperature=0.0, seed=0, paged=True, block_size=block_size,
            num_blocks=10, kv_host_mb=8.0 if host_on else None,
        )
        clock = VirtualClock()
        sched = ContinuousScheduler(
            tier, max_queue=len(tier_reqs), clock=clock,
        )
        sched.run(
            [Request(i, p, b) for i, (p, b) in enumerate(tier_reqs)],
            sleep=clock.advance,
        )
        st = tier.stats()
        tier_legs["host_on" if host_on else "host_off"] = {
            "hierarchy_hit_rate": round(
                st["prefix_hit_tokens"] / st["prefix_lookup_tokens"], 4
            ),
            "prefill_tokens_computed": st["prefill_tokens_computed"],
            "blocks_evicted": st["blocks_evicted"],
            "blocks_spilled": st.get("blocks_spilled", 0),
            "blocks_restored": st.get("blocks_restored", 0),
            "handoffs": st["handoffs"],
        }
        del tier, sched
        gc.collect()
    disagg_bench = {
        "long_prompt_burst": {
            "slots": {
                "interleaved": dg_total,
                "disagg": f"1 prefill + {dg_total - 1} decode",
            },
            "short_requests": n_short,
            "long_requests": n_long,
            "long_prompt_tokens": 120,
            "legs": burst_best,
            "rounds": burst_rounds,
            "tpot_p99_gain": round(
                burst_best["interleaved"]["tpot_p99_s"]
                / burst_best["disagg"]["tpot_p99_s"], 3
            ),
            "protocol": (
                "identical requests + arrivals, equal slot budget "
                f"({dg_total}); short requests decode while "
                f"{n_long} long prompts chunk in; TPOT over short "
                "requests only; 3 alternating-order rounds, "
                "best-of-rounds per leg (box noise discipline)"
            ),
        },
        "kv_host_tier": {
            "shared_fraction": 0.9,
            "num_blocks": 10,
            "legs": tier_legs,
            "hit_rate_gain": round(
                tier_legs["host_on"]["hierarchy_hit_rate"]
                - tier_legs["host_off"]["hierarchy_hit_rate"], 4
            ),
            "protocol": (
                "identical 90%-shared-prefix trace through the 1p+1d "
                "tier at a 10-block pool; disjoint whole-pool-span "
                "requests force eviction between sharers; host tier "
                "OFF = evicted prefixes recompute, ON = spill + "
                "bit-identical restore (counter-exact, virtual clock)"
            ),
        },
    }

    _emit({
        "metric": "gpt2_serve_continuous_vs_static",
        "value": max(r["goodput_gain"] for r in sweep),
        "unit": "goodput gain vs static batching (best sweep point)",
        "model": "gpt2_124m" if on_tpu else "gpt2-tiny(cpu-proxy)",
        "slots": slots,
        "prefill_chunk": chunk,
        "requests": n_requests,
        "prompt_len_range": [p_lo, p_hi],
        "max_new_range": [b_lo, b_hi],
        "static_padding": {
            "prompt_pad": p_pad, "shared_max_new": shared_new,
        },
        "sweep": sweep,
        "paged_vs_contiguous": paged_vs_contiguous,
        "prefix_caching": prefix_caching,
        "speculative": speculative,
        "replica_router": replica_router,
        "disagg": disagg_bench,
        "protocol": (
            "fixed workload seed; one trace per offered load, both "
            "disciplines on identical requests + arrivals; static "
            "durations measured live per batch, timeline composed with "
            "arrival constraints"
        ),
        "note": (
            "goodput counts each request's OWN budget; static batching "
            "decodes every row to the shared max budget and prefills one "
            "token per tick, which is the waste iteration-level "
            "scheduling (Orca/vLLM-style) reclaims"
        ),
    }, "SERVE_BENCH.json" if "--save" in sys.argv[1:] else None)


def main_serve_failover():
    """Failover leg (SERVE_BENCH.json ``failover`` key, merged into the
    existing artifact): a scripted replica kill through a 2-replica paged
    tier at equal offered load, failover ON vs the no-failover CONTROL.

    The clock is virtual (the kv_host_tier leg's protocol): the headline
    is COMPLETION accounting — what fraction of the accepted work the
    tier still finishes, and at what goodput, when one replica dies
    mid-run — not wall speed, so the leg is deterministic and immune to
    this box's scheduling noise.  With failover the dead replica's
    queued and in-flight requests requeue onto the survivor (token-exact
    re-prefill) and the replica respawns after backoff; without it they
    strand forever, which is exactly the pre-failover tier's behavior.
    """
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tpu.models import gpt2_124m
    from pytorch_distributed_training_tpu.resilience import (
        ServeFaultInjector,
    )
    from pytorch_distributed_training_tpu.serve import (
        FailoverController, ReplicaRouter, Request, ServingEngine,
        VirtualClock,
    )
    from pytorch_distributed_training_tpu.utils.backoff import BackoffPolicy

    overrides = dict(num_layers=4, hidden_dim=256, num_heads=4,
                     vocab_size=4096, max_seq_len=160)
    model = gpt2_124m(cfg_overrides=overrides)
    rng = np.random.default_rng(0)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False
    )["params"]
    slots, chunk, n_requests = 4, 16, 24
    prompts = [
        rng.integers(0, 4096, (int(rng.integers(8, 49)),)).astype(np.int32)
        for _ in range(n_requests)
    ]
    budgets = rng.integers(8, 25, n_requests)
    dt = 0.025                      # virtual seconds per router tick
    arrivals = 0.05 * np.arange(n_requests)   # sustained offered load
    # Fixed measurement window for BOTH legs (equal offered load, equal
    # denominator): goodput = tokens completed within the window / the
    # window — the control's stranded work simply never lands.
    kill_tick, horizon = 30, 200
    engines = [
        ServingEngine(
            model, params, num_slots=slots, max_len=160,
            prefill_chunk=chunk, temperature=0.0, paged=True,
            block_size=16, num_blocks=48,
        )
        for _ in range(2)
    ]

    def run(failover: bool) -> dict:
        for e in engines:
            e.reset()
        clock = VirtualClock()
        ctrl = FailoverController(
            retry_budget=2, miss_threshold=3,
            backoff=BackoffPolicy(base_s=2.0, jitter=0.0),
        ) if failover else None
        router = ReplicaRouter(
            engines, max_queue=n_requests, clock=clock,
            chaos=ServeFaultInjector.from_spec(
                f"replica_crash@{kill_tick}:1"
            ),
            failover=ctrl,
        )
        reqs = [
            Request(i, prompts[i], int(budgets[i]), float(arrivals[i]))
            for i in range(n_requests)
        ]
        i = 0
        for _ in range(horizon):
            now = clock()
            while i < n_requests and arrivals[i] <= now:
                router.submit(reqs[i])
                i += 1
            router.tick()
            clock.advance(dt)
        done = [
            r for r in router.completed
            if r.get("finish_reason") in ("eos", "length")
        ]
        tokens = sum(r["generated"] for r in done)
        elapsed = horizon * dt
        out = {
            "completed": len(done),
            "stranded": n_requests - len(done),
            "generated_tokens": int(tokens),
            "elapsed_virtual_s": round(elapsed, 4),
            "goodput_tok_per_s": round(tokens / elapsed, 2),
            "ticks": router.tick_index,
        }
        if ctrl is not None:
            fo = ctrl.stats()
            out["failover"] = {
                k: fo[k] for k in (
                    "requeued", "retried", "duplicates_suppressed",
                    "failed", "respawns", "replica_deaths",
                )
            }
            out["death_tick"] = fo["deaths"][0]["tick"]
        return out

    control = run(failover=False)
    with_failover = run(failover=True)
    gain = (
        with_failover["goodput_tok_per_s"] / control["goodput_tok_per_s"]
        if control["goodput_tok_per_s"] else float("inf")
    )
    leg = {
        "kill_tick": kill_tick,
        "replicas": 2,
        "slots_per_replica": slots,
        "requests": n_requests,
        "control_no_failover": control,
        "failover": with_failover,
        "goodput_gain": round(gain, 3),
        "strictly_better": (
            with_failover["goodput_tok_per_s"]
            > control["goodput_tok_per_s"]
            and with_failover["completed"] >= control["completed"]
        ),
        "protocol": (
            "identical workload + arrival trace + scripted "
            "replica_crash@tick through the same 2-replica paged tier; "
            "virtual clock (completion accounting, noise-free); control "
            "strands the dead replica's work, failover requeues it "
            "token-exactly onto the survivor and respawns after backoff"
        ),
    }
    save = "SERVE_BENCH.json" if "--save" in sys.argv[1:] else None
    if save is not None and os.path.exists(save):
        with open(save) as f:
            full = json.load(f)
        full["failover"] = leg
        full.pop("session", None)
        _emit(full, save)
    else:
        _emit({
            "metric": "gpt2_serve_failover",
            "value": leg["goodput_gain"],
            "unit": "goodput vs no-failover control through a replica kill",
            "failover": leg,
        }, save)


def main_serve_autoscale():
    """Autoscale leg (SERVE_BENCH.json ``autoscale`` key, merged into the
    existing artifact): a burst-then-drain trace through a 2-replica
    paged tier, closed-loop controller ON (floor of 1 active replica,
    spare parked) vs the FIXED small fleet an operator would provision
    for the trickle (1 replica), at equal offered load.

    The clock is virtual (the failover leg's protocol), so the leg is
    deterministic: the controller's action log (ticks + causes) is
    run-to-run identical, and the headline is goodput x p99-TTFT through
    the burst — the scaled tier must beat the fixed fleet on BOTH.  The
    whole fleet compiles up front (MPMD program-per-role), so every
    controller action is a park/unpark: the leg pins zero new compiles
    across the run.
    """
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tpu.analysis.signature import (
        PROGRAM_REGISTRY,
    )
    from pytorch_distributed_training_tpu.models import gpt2_124m
    from pytorch_distributed_training_tpu.serve import (
        AutoscaleController, FailoverController, ReplicaRouter, Request,
        ServingEngine, VirtualClock,
    )
    from pytorch_distributed_training_tpu.serve.metrics import percentile

    overrides = dict(num_layers=4, hidden_dim=256, num_heads=4,
                     vocab_size=4096, max_seq_len=160)
    model = gpt2_124m(cfg_overrides=overrides)
    rng = np.random.default_rng(0)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False
    )["params"]
    slots, n_requests = 4, 32
    prompts = [
        rng.integers(0, 4096, (int(rng.integers(8, 49)),)).astype(np.int32)
        for _ in range(n_requests)
    ]
    budgets = rng.integers(8, 17, n_requests)
    # Burst-then-drain offered load: a trickle the floor fleet handles
    # comfortably, then 24 requests land at once, then silence — the
    # drain tail is long enough for the controller to park the spare
    # again after the burst clears.
    arrivals = np.concatenate([
        0.2 * np.arange(8),               # trickle: t = 0.0 .. 1.4
        np.full(n_requests - 8, 1.5),     # burst: all at t = 1.5
    ])
    dt = 0.025
    # The window bites: the scaled tier clears the burst well inside it
    # (and has re-parked the spare by the end); the fixed fleet is still
    # chewing through backlog when it closes, so goodput — completed
    # tokens inside the window — separates the two.
    horizon = 120                         # 3 virtual seconds
    engines = [
        ServingEngine(
            model, params, num_slots=slots, max_len=160,
            prefill_chunk=16, temperature=0.0, paged=True,
            block_size=16, num_blocks=48,
        )
        for _ in range(2)
    ]

    def run(autoscale: bool) -> dict:
        for e in engines:
            e.reset()
        clock = VirtualClock()
        fleet = engines if autoscale else engines[:1]
        ctrl = AutoscaleController(
            min_replicas=1, up_queue_depth=4, down_idle_ticks=12,
            cooldown_ticks=6, ladder_patience_ticks=64,
        ) if autoscale else None
        router = ReplicaRouter(
            fleet, max_queue=n_requests, clock=clock,
            failover=FailoverController(respawn=False),
            autoscale=ctrl,
        )
        reqs = [
            Request(i, prompts[i], int(budgets[i]), float(arrivals[i]))
            for i in range(n_requests)
        ]
        i = 0
        for _ in range(horizon):
            now = clock()
            while i < n_requests and arrivals[i] <= now:
                router.submit(reqs[i])
                i += 1
            router.tick()
            clock.advance(dt)
        done = [
            r for r in router.completed
            if r.get("finish_reason") in ("eos", "length")
        ]
        tokens = sum(r["generated"] for r in done)
        elapsed = horizon * dt
        ttfts = [r["ttft"] for r in done if r.get("ttft") is not None]
        out = {
            "completed": len(done),
            "generated_tokens": int(tokens),
            "elapsed_virtual_s": round(elapsed, 4),
            "goodput_tok_per_s": round(tokens / elapsed, 2),
            "ttft_p50_s": round(percentile(ttfts, 50), 4),
            "ttft_p99_s": round(percentile(ttfts, 99), 4),
            "ticks": router.tick_index,
        }
        if ctrl is not None:
            out["autoscale"] = {
                k: ctrl.stats()[k] for k in (
                    "actions", "scale_ups", "scale_downs",
                    "ladder_moves", "replicas_active", "replicas_parked",
                )
            }
            out["action_log"] = [
                {"tick": a["tick"], "action": a["action"],
                 "cause": a["cause"]["signal"]}
                for a in ctrl.history
            ]
        return out

    control = run(autoscale=False)
    before = dict(PROGRAM_REGISTRY.counts())
    scaled = run(autoscale=True)
    new_compiles = sum(
        dict(PROGRAM_REGISTRY.counts()).get(k, 0) - v
        for k, v in before.items()
    ) + sum(
        v for k, v in dict(PROGRAM_REGISTRY.counts()).items()
        if k not in before
    )
    gain = (
        scaled["goodput_tok_per_s"] / control["goodput_tok_per_s"]
        if control["goodput_tok_per_s"] else float("inf")
    )
    leg = {
        "replicas_compiled": 2,
        "replicas_floor": 1,
        "slots_per_replica": slots,
        "requests": n_requests,
        "burst_at_s": 1.5,
        "control_fixed_fleet": control,
        "autoscaled": scaled,
        "goodput_gain": round(gain, 3),
        "new_compiles_during_scaling": int(new_compiles),
        "strictly_better": (
            scaled["goodput_tok_per_s"] > control["goodput_tok_per_s"]
            and scaled["ttft_p99_s"] <= control["ttft_p99_s"]
            and new_compiles == 0
        ),
        "protocol": (
            "identical workload + burst-then-drain arrival trace at "
            "equal offered load; virtual clock (deterministic action "
            "log); control is the fixed floor fleet, the autoscaled "
            "tier parks a pre-compiled spare and the controller "
            "revives it from queue-depth pressure, then drains and "
            "re-parks it after the burst — zero new compiles"
        ),
    }
    save = "SERVE_BENCH.json" if "--save" in sys.argv[1:] else None
    if save is not None and os.path.exists(save):
        with open(save) as f:
            full = json.load(f)
        full["autoscale"] = leg
        full.pop("session", None)
        _emit(full, save)
    else:
        _emit({
            "metric": "gpt2_serve_autoscale",
            "value": leg["goodput_gain"],
            "unit": "goodput vs fixed floor fleet through a burst",
            "autoscale": leg,
        }, save)


def main_serve_quant():
    """Quantized-KV serving legs (SERVE_BENCH.json ``kv_quant`` key,
    merged into the existing artifact):

    1. **live-slots-at-fixed-byte-budget** — one HBM byte budget, three
       storage dtypes (bf16-native vs int8 vs int4): the quantized pools
       hold proportionally more physical blocks (int8 ~3.8x, int4 ~7.1x
       on the f32 CPU proxy; ~2x/4x on a bf16 TPU pool), so the SAME
       bytes sustain more concurrent requests on an identical burst
       trace.  The quantized-capacity face of the PR 4
       paged_vs_contiguous protocol.
    2. **fused-prefill vs XLA-prefill tick cost** — the chunked-prefill
       Pallas kernel (PDT_DECODE_ATTN=pallas) against the XLA gather
       prefill on the same trace.  CPU PROXY CAVEAT: off-TPU the kernel
       runs in interpret mode (a per-grid-point emulation), so this leg
       measures correctness-path cost only and UNDERSTATES the kernel —
       the compiled-TPU A/B rides the chip-session queue.
    """
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tpu.models import gpt2_124m
    from pytorch_distributed_training_tpu.obs.cost import (
        kv_block_model_bytes,
    )
    from pytorch_distributed_training_tpu.serve import (
        ContinuousScheduler, Request, ServingEngine, summarize_records,
    )

    on_tpu = jax.default_backend() == "tpu"
    overrides = None if on_tpu else dict(
        num_layers=4, hidden_dim=256, num_heads=4, vocab_size=4096,
        max_seq_len=160,
    )
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    model = gpt2_124m(cfg_overrides=overrides, dtype=dtype)
    cfg = model.cfg
    rng = np.random.default_rng(0)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False
    )["params"]
    params = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
    max_len = cfg.max_seq_len
    block_size = 16
    slots, chunk, n_requests = 16, 16, 24
    prompts = [
        rng.integers(0, cfg.vocab_size,
                     (int(rng.integers(8, 49)),)).astype(np.int32)
        for _ in range(n_requests)
    ]
    budgets = rng.integers(8, 25, n_requests)

    head_dim = cfg.hidden_dim // cfg.num_heads
    model_kw = dict(
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        head_dim=head_dim, block_size=block_size,
        itemsize=dtype.dtype.itemsize,
    )
    # The byte budget: what a 20-block native pool costs — small enough
    # that blocks (not the slot array) bind every leg.
    budget_bytes = 20 * kv_block_model_bytes(**model_kw)

    def run_leg(kv_dtype):
        per_block = kv_block_model_bytes(
            **model_kw, dtype=None if kv_dtype == "bf16" else kv_dtype
        )
        num_blocks = budget_bytes // per_block
        eng = ServingEngine(
            model, params, num_slots=slots, max_len=max_len,
            prefill_chunk=chunk, temperature=0.0, seed=0, paged=True,
            block_size=block_size, num_blocks=int(num_blocks),
            kv_dtype=kv_dtype,
        )
        assert eng.pool.blocks.block_bytes == per_block
        sched = ContinuousScheduler(eng, max_queue=n_requests)
        t0 = time.monotonic()
        recs = sched.run([
            Request(i, prompts[i], int(budgets[i]), t0)  # burst at t=0
            for i in range(n_requests)
        ])
        summary = summarize_records(
            recs, elapsed=None,
            queue_depth_samples=sched.queue_depth_samples,
            rejected=sched.rejected,
            active_slot_samples=sched.active_slot_samples,
        )
        return {
            "kv_dtype": kv_dtype,
            "num_blocks": int(num_blocks),
            "block_bytes": per_block,
            "pool_bytes": per_block * int(num_blocks),
            "live_slots_max": summary["live_slots_max"],
            "completed": summary["completed"],
            "goodput_tok_per_s": summary["goodput_tok_per_s"],
            "ttft_p50_s": summary["ttft_p50_s"],
        }

    legs = {kv: run_leg(kv) for kv in ("bf16", "int8", "int4")}
    slots_gain = {
        kv: round(
            legs[kv]["live_slots_max"] / legs["bf16"]["live_slots_max"], 3
        )
        for kv in ("int8", "int4")
    }

    # ---- fused-prefill vs XLA-prefill tick cost ---- #
    long_prompt = rng.integers(0, cfg.vocab_size, (96,)).astype(np.int32)

    def prefill_cost():
        eng = ServingEngine(
            model, params, num_slots=2, max_len=max_len,
            prefill_chunk=chunk, temperature=0.0, seed=0, paged=True,
            block_size=block_size, num_blocks=20,
        )
        # Warm the host loop + executable once.
        eng.start("warm", long_prompt, 2)
        while eng.busy:
            eng.step()
        eng.reset()
        eng.start("r", long_prompt, 2)
        ticks = []
        while eng._live("prefill"):
            t0 = time.perf_counter()
            eng.prefill_step()
            ticks.append(time.perf_counter() - t0)
        while eng.busy:
            eng.step()
        return float(np.mean(ticks)), len(ticks)

    # Force EACH leg's dispatch explicitly: on TPU (or under a stray
    # PDT_DECODE_ATTN in the caller's env) the default path is already
    # the fused kernel, and an unforced baseline would measure
    # pallas-vs-pallas.
    prev = os.environ.get("PDT_DECODE_ATTN")
    try:
        os.environ["PDT_DECODE_ATTN"] = "xla"
        jax.clear_caches()
        xla_cost, n_ticks = prefill_cost()
        os.environ["PDT_DECODE_ATTN"] = "pallas"
        jax.clear_caches()
        fused_cost, _ = prefill_cost()
    finally:
        if prev is None:
            del os.environ["PDT_DECODE_ATTN"]
        else:
            os.environ["PDT_DECODE_ATTN"] = prev
        jax.clear_caches()

    leg = {
        "byte_budget": budget_bytes,
        "block_size": block_size,
        "num_slots": slots,
        "requests": n_requests,
        "native_itemsize": dtype.dtype.itemsize,
        "legs": legs,
        "live_slots_gain": slots_gain,
        "fused_prefill": {
            "prompt_len": int(long_prompt.size),
            "prefill_chunk": chunk,
            "ticks": n_ticks,
            "xla_prefill_tick_s": round(xla_cost, 6),
            "fused_prefill_tick_s": round(fused_cost, 6),
            "backend": jax.default_backend(),
            "note": (
                "off-TPU the fused kernel runs in INTERPRET mode — this "
                "leg pins the correctness path only and understates the "
                "kernel; compiled-TPU A/B in the chip-session queue"
            ) if not on_tpu else "compiled TPU kernels",
        },
        "protocol": (
            "identical burst trace through three paged engines holding "
            "ONE byte budget; per-dtype num_blocks = budget // "
            "kv_block_model_bytes(dtype) (int8/int4 pay their "
            "per-position bf16 scales in the same budget); "
            "live_slots_max is the concurrency the pool sustained"
        ),
    }
    save = "SERVE_BENCH.json" if "--save" in sys.argv[1:] else None
    if save is not None and os.path.exists(save):
        with open(save) as f:
            full = json.load(f)
        full["kv_quant"] = leg
        full.pop("session", None)
        _emit(full, save)
    else:
        _emit({
            "metric": "gpt2_serve_kv_quant",
            "value": slots_gain["int8"],
            "unit": "live-slot gain at a fixed byte budget (int8 vs bf16)",
            "kv_quant": leg,
        }, save)


def main_telemetry_overhead():
    """Telemetry-overhead bench (TELEMETRY_BENCH.json): the SAME train loop
    through ``Trainer`` with the obs/ emitter disabled vs enabled (per-step
    JSONL events + counters + step annotations), reporting the relative
    step-time overhead, plus a tracing leg (--trace spans full vs sampled
    vs off over the live emitter).  Target: <1% with JSONL on, and <1%
    again for the span layer on top.

    CPU proxy sizing follows the serve-bench lesson (d=256, 4 layers): the
    model must be big enough that per-step compute dominates Python
    dispatch, else the ratio measures the interpreter, not the emitter.
    Interleaved A/B rounds (off, on, off, on, ...) so drift in the shared
    machine cancels instead of landing on one leg.
    """
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_training_tpu.models import create_model
    from pytorch_distributed_training_tpu.obs import MetricsEmitter
    from pytorch_distributed_training_tpu.train import (
        Trainer, TrainerConfig, create_train_state, make_policy,
        make_train_step,
    )

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        overrides, dtype, batch, seq = None, jnp.bfloat16, 32, 1024
        steps = 24
    else:
        # Big enough that per-step compute dominates dispatch (the serve
        # lesson), small enough that a leg is seconds — this shared
        # sandbox carries multi-second scheduling noise, so the protocol
        # below reports best-of-N legs, not medians of noisy draws.
        overrides = dict(num_layers=2, hidden_dim=128, num_heads=4,
                         vocab_size=2048, max_seq_len=128)
        dtype, batch, seq = jnp.float32, 8, 128
        steps = 40
    model = create_model("gpt2", cfg_overrides=overrides, dtype=dtype)
    state0 = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32),
        optax.adamw(1e-3), init_kwargs={"train": False},
    )
    step_fn = make_train_step(
        kind="lm", policy=make_policy("bf16" if on_tpu else "f32"),
        base_rng=jax.random.PRNGKey(1),
    )
    rng = np.random.default_rng(0)
    b = {"tokens": jnp.asarray(
        rng.integers(0, model.cfg.vocab_size, (batch, seq)), jnp.int32
    )}
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    cfg = TrainerConfig(progress=False, log_every=10_000, prefetch=0)

    held = {"state": state0}

    def leg(emitter, spans=None, slo=None):
        """One epoch of ``steps`` chained steps; returns its wall time.
        The donated state threads through ``held`` so every leg reuses the
        same compiled step on live buffers."""
        trainer = Trainer(
            held["state"], step_fn, mesh, cfg, emitter=emitter, spans=spans,
            anatomy={"microbatches": 1, "grad_sync": "flat"}, slo=slo,
        )
        t0 = time.perf_counter()
        trainer.run_epoch([b] * steps)  # closes with a loss fetch
        dt = time.perf_counter() - t0
        held["state"] = trainer.state
        return dt

    leg(None)  # compile + warm
    with tempfile.TemporaryDirectory() as td:
        emitter = MetricsEmitter(td, rank=0, world=1)
        emitter.set_step_counters({"dcn_bytes": 0.0})
        off_times, on_times = [], []
        # Paired A/B with alternating order: a fixed off-then-on order
        # turns any monotonic machine drift into a systematic bias on one
        # leg (measured: ON "won" by 6% under a warming CPU).  Alternating
        # the order and taking the median of per-round ratios cancels
        # linear drift; remaining noise is symmetric around the truth.
        rounds = BENCH_ROUNDS + 2
        for r in range(rounds):
            if r % 2 == 0:
                off = leg(None)
                on = leg(emitter)
            else:
                on = leg(emitter)
                off = leg(None)
            off_times.append(off)
            on_times.append(on)
        emitter.summary()
        emitter.close()
        events = sum(1 for _ in open(emitter.path))
    ratios = [on / off for on, off in zip(on_times, off_times)]
    overhead = _median(ratios) - 1.0
    t_off, t_on = _median(off_times), _median(on_times)

    # Tracing legs (--trace, obs/spans.py): the span layer's MARGINAL
    # cost over the live emitter.  FULL records every step's train/step
    # span; SAMPLED (--trace-sample-rate 0.25) runs the deterministic
    # per-corr gate on every step but records ~1/4; the baseline leg is
    # the emitter alone.  Leg order rotates per round (same drift-
    # cancelling idea as the paired A/B above, three-way).
    from pytorch_distributed_training_tpu.obs import SpanRecorder

    trace_sample_rate = 0.25
    with tempfile.TemporaryDirectory() as td:
        tem = MetricsEmitter(td, rank=0, world=1)
        tem.set_step_counters({"dcn_bytes": 0.0})
        full = SpanRecorder(tem, sample_rate=1.0)
        samp = SpanRecorder(tem, sample_rate=trace_sample_rate)
        trace_times = {"base": [], "full": [], "sampled": []}
        legs = [("base", None), ("full", full), ("sampled", samp)]
        for r in range(BENCH_ROUNDS):
            for name, rec in legs[r % 3:] + legs[:r % 3]:
                trace_times[name].append(leg(tem, spans=rec))
        spans_per_step = full.recorded / (BENCH_ROUNDS * steps)
        sampled_fraction = samp.recorded / max(
            1, samp.recorded + samp.sampled_out
        )
        full.close()
        samp.close()
        tem.summary()
        tem.close()
    t_base = _median(trace_times["base"])

    # Isolated deterministic per-span cost (start + end + the deferred
    # flush, amortized): the headline for the tracing bar, same reasoning
    # as the emitter's isolated measure — the three-way ratio above is
    # noise-bounded on this sandbox and only cross-checks.
    with tempfile.TemporaryDirectory() as td:
        iso_em = MetricsEmitter(td, rank=0, world=1)
        n_iso = 5000
        rec_full = SpanRecorder(iso_em, sample_rate=1.0)
        t0 = time.perf_counter()
        for i in range(n_iso):
            s = rec_full.start_span("train/step", corr=i, microbatches=1)
            rec_full.end_span(s)
        rec_full.close()
        per_span_s = (time.perf_counter() - t0) / n_iso
        rec_samp = SpanRecorder(iso_em, sample_rate=trace_sample_rate)
        t0 = time.perf_counter()
        for i in range(n_iso):
            s = rec_samp.start_span("train/step", corr=i, microbatches=1)
            rec_samp.end_span(s)
        rec_samp.close()
        per_span_sampled_s = (time.perf_counter() - t0) / n_iso
        iso_em.close()
    implied_trace = per_span_s * spans_per_step / (t_off / steps)

    # Isolated per-event cost: the A/B ratio above bounds the overhead by
    # the machine's noise floor; this times the emitter's step() (dict
    # build + counter deltas + json + write + flush) alone, giving the
    # deterministic number the ratio is too noisy to resolve.
    with tempfile.TemporaryDirectory() as td:
        iso = MetricsEmitter(td, rank=0, world=1)
        iso.set_step_counters({"dcn_bytes": 1.0, "dcn_syncs": 1.0})
        n_iso = 5000
        t0 = time.perf_counter()
        for i in range(n_iso):
            iso.step(i, dt=0.001)
        per_event_s = (time.perf_counter() - t0) / n_iso
        iso.close()
    implied = per_event_s / (t_off / steps)

    # Live-plane legs (--slo / --metrics-port, obs/live.py + obs/slo.py +
    # obs/http.py): the marginal cost of the aggregator+policy sinks (a
    # tee per metric call + one burn-rate evaluation per step) over the
    # live emitter, plus a SCRAPE-DURING-LOAD point — a background thread
    # hammering /metrics at ~40 Hz while the step loop runs, the worst
    # case a Prometheus scraper presents.  Headline = the isolated
    # per-step sink+evaluate cost over the off-leg step time (the wall
    # ratios cross-check, same noise argument as above).
    import threading
    import urllib.request

    from pytorch_distributed_training_tpu.obs import (
        LiveAggregator, OpsServer, SLOPolicy, parse_slo_spec,
    )

    def live_emitter(td):
        lem = MetricsEmitter(td, rank=0, world=1)
        lem.set_step_counters({"dcn_bytes": 0.0})
        lagg = LiveAggregator(clock=lem.clock)
        lpol = SLOPolicy(
            lagg, parse_slo_spec("step_time_p95=60s"), emitter=lem
        )
        lem.attach_sink(lagg)
        lem.attach_sink(lpol)
        return lem, lagg, lpol

    with tempfile.TemporaryDirectory() as td:
        lem, lagg, lpol = live_emitter(td)
        pem = MetricsEmitter(td + "-plain", rank=0, world=1)
        pem.set_step_counters({"dcn_bytes": 0.0})
        srv = OpsServer(lagg, lpol, port=0).start()
        stop = threading.Event()
        scrapes = {"n": 0}

        def scraper():
            while not stop.is_set():
                try:
                    urllib.request.urlopen(
                        srv.url + "/metrics", timeout=1.0
                    ).read()
                    scrapes["n"] += 1
                except Exception:
                    pass
                stop.wait(0.025)

        live_times = {"emitter": [], "live": [], "scraped": []}
        live_legs = [
            ("emitter", lambda: leg(pem)),
            ("live", lambda: leg(lem, slo=lpol)),
        ]
        for r in range(BENCH_ROUNDS):
            for name, fn in live_legs[r % 2:] + live_legs[:r % 2]:
                live_times[name].append(fn())
        thread = threading.Thread(target=scraper, daemon=True)
        thread.start()
        for _ in range(max(BENCH_ROUNDS - 2, 2)):
            live_times["scraped"].append(leg(lem, slo=lpol))
        stop.set()
        thread.join(timeout=5.0)
        srv.stop()
        lem.summary()
        lem.close()
        pem.close()

    # Isolated per-step live cost: the same emitter write path with vs
    # without the sinks+evaluation, timed alone — aggregation (counter
    # slot + histogram bucket) plus one two-window burn-rate evaluation.
    with tempfile.TemporaryDirectory() as td:
        plain = MetricsEmitter(td + "-a", rank=0, world=1)
        plain.set_step_counters({"dcn_bytes": 1.0})
        n_iso = 5000
        t0 = time.perf_counter()
        for i in range(n_iso):
            plain.observe("step_time_s", 0.001)
            plain.step(i, dt=0.001)
        per_plain_s = (time.perf_counter() - t0) / n_iso
        plain.close()
        wem, wagg, wpol = live_emitter(td + "-b")
        wem.set_step_counters({"dcn_bytes": 1.0})
        t0 = time.perf_counter()
        for i in range(n_iso):
            wem.observe("step_time_s", 0.001)
            wem.step(i, dt=0.001)
            wpol.evaluate()
        per_live_s = (time.perf_counter() - t0) / n_iso
        wem.close()
    iso_live_s = max(per_live_s - per_plain_s, 0.0)
    implied_live = iso_live_s * 1.0 / (t_off / steps)
    t_lem = _median(live_times["emitter"])
    _emit({
        "metric": "telemetry_emitter_overhead",
        # Headline = the deterministic isolated measure over the measured
        # step time; the end-to-end A/B ratio is reported alongside as the
        # (noise-bounded) cross-check — on this shared sandbox its spread
        # dwarfs the true per-step cost.
        "value": round(implied, 6),
        "unit": "relative step-time overhead (jsonl per-step events on)",
        "target": "< 0.01",
        # Gate on the deterministic measures only (emitter, the span
        # layer, AND the live aggregation+scrape sink): the A/B ratios'
        # observed spread on this sandbox (±5-10%, see "ratios") is an
        # order of magnitude above the target and both signs occur —
        # they contextualize, they cannot gate.
        "pass": bool(
            implied < 0.01 and implied_trace < 0.01
            and implied_live < 0.01
        ),
        "ab_ratio_spread": [
            round(min(ratios) - 1.0, 4), round(max(ratios) - 1.0, 4),
        ],
        "steps_per_leg": steps,
        "batch": batch,
        "seq": seq,
        "per_step_ms": {
            "off": round(t_off / steps * 1e3, 3),
            "on": round(t_on / steps * 1e3, 3),
        },
        "events_written": events,
        "isolated_emit_us_per_step": round(per_event_s * 1e6, 2),
        "ab_ratio_overhead": round(overhead, 5),
        "protocol": (
            "headline: isolated per-event emit cost / median off-leg step "
            f"time; cross-check: median of {rounds} paired A/B ratios, "
            f"order alternated per round (cancels linear drift), {steps} "
            "chained steps per leg; per-step JSONL step events with "
            "counters + xprof step annotations on the ON leg"
        ),
        "ratios": [round(r, 4) for r in ratios],
        "off_runs": [round(t, 4) for t in off_times],
        "on_runs": [round(t, 4) for t in on_times],
        # --trace leg: spans on (full and sampled) vs the emitter-only
        # baseline, same step loop.  Headline = isolated per-span cost
        # (start+end+deferred flush) x spans/step over the off-leg step
        # time; the rotated three-way wall ratios cross-check.
        "tracing": {
            "implied_overhead": round(implied_trace, 6),
            "target": "< 0.01",
            "pass": bool(implied_trace < 0.01),
            "isolated_span_us": round(per_span_s * 1e6, 2),
            "isolated_span_us_sampled": round(per_span_sampled_s * 1e6, 2),
            "sample_rate": trace_sample_rate,
            "sampled_fraction_recorded": round(sampled_fraction, 4),
            "spans_per_step": round(spans_per_step, 3),
            "per_step_ms": {
                "emitter_only": round(t_base / steps * 1e3, 3),
                "spans_full": round(
                    _median(trace_times["full"]) / steps * 1e3, 3
                ),
                "spans_sampled": round(
                    _median(trace_times["sampled"]) / steps * 1e3, 3
                ),
            },
            "ab_ratio_overhead": {
                "full": round(
                    _median(trace_times["full"]) / t_base - 1.0, 5
                ),
                "sampled": round(
                    _median(trace_times["sampled"]) / t_base - 1.0, 5
                ),
            },
        },
        # --slo/--metrics-port leg: aggregator+policy sinks on vs the
        # plain emitter, plus the scrape-during-load point.  Headline =
        # isolated (sink tee + burn-rate evaluation) per-step cost over
        # the off-leg step time; the rotated wall ratios cross-check.
        "live": {
            "implied_overhead": round(implied_live, 6),
            "target": "< 0.01",
            "pass": bool(implied_live < 0.01),
            "isolated_live_us_per_step": round(iso_live_s * 1e6, 2),
            "isolated_plain_us_per_step": round(per_plain_s * 1e6, 2),
            "scrapes_during_load": scrapes["n"],
            "per_step_ms": {
                "emitter_only": round(t_lem / steps * 1e3, 3),
                "live_sinks": round(
                    _median(live_times["live"]) / steps * 1e3, 3
                ),
                "live_sinks_scraped": round(
                    _median(live_times["scraped"]) / steps * 1e3, 3
                ),
            },
            "ab_ratio_overhead": {
                "live": round(
                    _median(live_times["live"]) / t_lem - 1.0, 5
                ),
                "scraped": round(
                    _median(live_times["scraped"]) / t_lem - 1.0, 5
                ),
            },
        },
    }, "TELEMETRY_BENCH.json" if "--save" in sys.argv[1:] else None)


def main_goodput():
    """Goodput-ledger bench (GOODPUT_BENCH.json): two legs.

    **Attribution** (deterministic): the graftcheck ledger audit's
    scripted virtual-clock fault trace — crash, supervisor backoff,
    restore, rework — asserting every category's integer-ns attribution
    and the ``sum(categories) == wall`` identity EXACT, twice.  Pass =
    zero findings; the expected/got tables are the evidence.

    **Overhead**: the SAME train loop through ``Trainer`` with the
    ledger off vs on (iterator wrap + per-step classification + the
    progress-file write).  Protocol follows TELEMETRY_BENCH: headline =
    isolated deterministic per-step hook cost over the off-leg step
    time (target <1%), interleaved order-alternating A/B wall ratios as
    the noise-bounded cross-check.
    """
    import os
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_training_tpu.analysis.ledger_audit import (
        run_ledger_audit,
    )
    from pytorch_distributed_training_tpu.models import create_model
    from pytorch_distributed_training_tpu.obs import GoodputLedger
    from pytorch_distributed_training_tpu.train import (
        Trainer, TrainerConfig, create_train_state, make_policy,
        make_train_step,
    )

    audit_findings, audit_report = run_ledger_audit()

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        overrides, dtype, batch, seq = None, jnp.bfloat16, 32, 1024
        steps = 24
    else:
        # Same CPU-proxy sizing as the telemetry bench: compute must
        # dominate Python dispatch or the ratio prices the interpreter.
        overrides = dict(num_layers=2, hidden_dim=128, num_heads=4,
                         vocab_size=2048, max_seq_len=128)
        dtype, batch, seq = jnp.float32, 8, 128
        steps = 40
    model = create_model("gpt2", cfg_overrides=overrides, dtype=dtype)
    state0 = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32),
        optax.adamw(1e-3), init_kwargs={"train": False},
    )
    step_fn = make_train_step(
        kind="lm", policy=make_policy("bf16" if on_tpu else "f32"),
        base_rng=jax.random.PRNGKey(1),
    )
    rng = np.random.default_rng(0)
    b = {"tokens": jnp.asarray(
        rng.integers(0, model.cfg.vocab_size, (batch, seq)), jnp.int32
    )}
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    cfg = TrainerConfig(progress=False, log_every=10_000, prefetch=0)

    held = {"state": state0}

    def leg(ledger):
        trainer = Trainer(
            held["state"], step_fn, mesh, cfg, ledger=ledger,
            anatomy={"microbatches": 1, "grad_sync": "flat"},
        )
        t0 = time.perf_counter()
        trainer.run_epoch([b] * steps)
        dt = time.perf_counter() - t0
        held["state"] = trainer.state
        return dt

    leg(None)  # compile + warm
    with tempfile.TemporaryDirectory() as td:
        progress = os.path.join(td, ".progress")
        off_times, on_times = [], []
        rounds = BENCH_ROUNDS + 2
        for r in range(rounds):
            ledger = GoodputLedger(progress_path=progress)
            ledger.set_grad_sync_model(1e-4, ici_share=0.5)
            if r % 2 == 0:
                off = leg(None)
                on = leg(ledger)
            else:
                on = leg(ledger)
                off = leg(None)
            ledger.finalize()
            off_times.append(off)
            on_times.append(on)
        final_snap = ledger.finalize()

        # Isolated deterministic per-step hook cost: the exact sequence
        # the trainer drives per step — close the tail, charge the pull,
        # classify the interval, write the progress watermark.
        iso = GoodputLedger(
            progress_path=os.path.join(td, ".progress-iso")
        )
        iso.set_grad_sync_model(1e-4, ici_share=0.5)
        iso.begin_step(0)  # retire the compile classification
        n_iso = 5000
        t0 = time.perf_counter()
        for i in range(1, n_iso + 1):
            iso._switch("data_wait")
            iso._switch("step", step=None, cls="step_compute")
            iso.begin_step(i)
            iso.note_progress(i)
        per_hook_s = (time.perf_counter() - t0) / n_iso
        iso.finalize()
    ratios = [on / off for on, off in zip(on_times, off_times)]
    t_off = _median(off_times)
    implied = per_hook_s / (t_off / steps)

    _emit({
        "metric": "goodput_ledger",
        # Headline = the deterministic isolated per-step hook cost over
        # the measured step time; the A/B wall ratios cross-check (their
        # spread on this sandbox dwarfs the true cost — they cannot
        # gate, same argument as TELEMETRY_BENCH).
        "value": round(implied, 6),
        "unit": "relative step-time overhead (ledger hooks on)",
        "target": "< 0.01",
        "pass": bool(implied < 0.01 and not audit_findings),
        "attribution": {
            **audit_report,
            "pass": not audit_findings,
            "findings": [f.format() for f in audit_findings],
        },
        "identity_ok": bool(final_snap["identity_ok"]),
        "steps_per_leg": steps,
        "batch": batch,
        "seq": seq,
        "per_step_ms": {
            "off": round(t_off / steps * 1e3, 3),
            "on": round(_median(on_times) / steps * 1e3, 3),
        },
        "isolated_hook_us_per_step": round(per_hook_s * 1e6, 2),
        "ab_ratio_overhead": round(_median(ratios) - 1.0, 5),
        "ab_ratio_spread": [
            round(min(ratios) - 1.0, 4), round(max(ratios) - 1.0, 4),
        ],
        "protocol": (
            "attribution: scripted virtual-clock fault trace (graftcheck "
            "ledger pass), category totals pinned EXACT in integer ns, "
            "run twice; overhead headline: isolated per-step hook cost / "
            f"median off-leg step time; cross-check: {rounds} paired A/B "
            "ratios, order alternated per round"
        ),
        "ratios": [round(r, 4) for r in ratios],
    }, "GOODPUT_BENCH.json" if "--save" in sys.argv[1:] else None)


def _time_to_recover_leg():
    """Deterministic time-to-recover comparison for the elastic plane
    (resilience/elastic.py): one scripted ``slice_lost`` on the
    simulated 2-slice mesh, then three recovery paths priced in the
    SAME integer-ns virtual clock — peer-RAM one-hop restore (measured
    from the episode's ledger), the disk-manifest fallback, and a full
    supervised restart (backoff + cold compile + disk walk).  The
    rework term (steps re-executed since the last committed snapshot)
    is the episode's measured ``rework`` category and is common to all
    three paths, so the ratios isolate the restore transports.

    Needs the 8-device simulated mesh; on a smaller backend (the 1-chip
    sandbox the overhead legs run on) the episode is replayed in a
    subprocess on a forced-CPU 8-device backend — the clock is virtual,
    so the numbers are identical either way.
    """
    import jax

    from pytorch_distributed_training_tpu.resilience import (
        run_elastic_episode,
    )
    from pytorch_distributed_training_tpu.resilience.elastic import (
        BACKOFF_BASE_S, COMPILE_S, DISK_RESTORE_S, RESHAPE_COMPILE_S,
    )

    if len(jax.devices()) < 8:
        import json as _json
        import os
        import subprocess

        proc = subprocess.run(
            [sys.executable, "-c", (
                "import json, sys\n"
                "sys.path.insert(0, %r)\n"
                "from pytorch_distributed_training_tpu.compat import ("
                "set_cpu_device_count)\n"
                "set_cpu_device_count(8)\n"
                "import bench\n"
                "print('TTR ' + json.dumps(bench._time_to_recover_leg()))\n"
            ) % os.path.dirname(os.path.abspath(__file__))],
            capture_output=True, text=True, timeout=600,
            # This process holds the chip: the child is held to the CPU
            # by its ENVIRONMENT, before its jax is even imported.
            env={**os.environ, "PYTHONPATH": "", "JAX_PLATFORMS": "cpu"},
        )
        for line in proc.stdout.splitlines():
            if line.startswith("TTR "):
                return _json.loads(line[4:])
        return {"skipped": (
            f"needs 8 devices, have {len(jax.devices())}; CPU-mesh "
            f"subprocess failed (rc={proc.returncode})"
        )}
    report = run_elastic_episode(faults="slice_lost@4:1", n_steps=8)
    cats = report["ledger"]["categories_ns"]
    rework_s = cats["rework"] / 1e9
    restore_s = cats["ckpt_restore"] / 1e9  # the measured peer hop
    peer = restore_s + RESHAPE_COMPILE_S + rework_s
    disk = DISK_RESTORE_S + RESHAPE_COMPILE_S + rework_s
    restart = BACKOFF_BASE_S + DISK_RESTORE_S + COMPILE_S + rework_s
    return {
        "unit": "seconds from loss detection to training resumed at "
                "the pre-loss watermark (virtual clock)",
        "peer_ram_s": round(peer, 6),
        "disk_s": round(disk, 6),
        "supervised_restart_s": round(restart, 6),
        "speedup_vs_disk": round(disk / peer, 3),
        "speedup_vs_restart": round(restart / peer, 3),
        "rework_s": round(rework_s, 6),
        "restore_bit_identical": bool(report["restore_bit_identical"]),
        "identity_ok": bool(report["ledger"]["identity_ok"]),
        "protocol": (
            "scripted slice_lost@4:1 episode, snapshot cadence 2; peer "
            "path measured from the episode ledger (ckpt_restore + "
            "reshape recompile + replayed rework); disk / restart paths "
            "swap the restore hop for the disk-manifest walk / the "
            "supervised rejoin (backoff + cold compile + disk walk), "
            "same clock, same rework term"
        ),
    }


def main_resilience_overhead():
    """Resilience-overhead bench (RESILIENCE_BENCH.json): the SAME train
    loop with the skip/rollback machinery off vs on — the jit-safe anomaly
    gate (global grad norm + lax.cond) inside the step plus the host
    snapshot staging at its cadence.  Target: <1% relative step time.

    Protocol follows TELEMETRY_BENCH: interleaved A/B rounds with
    alternating order (cancels the shared sandbox's warming drift), plus
    an isolated deterministic measure — one snapshot staging, timed alone,
    amortized over the cadence — as the headline the noisy ratio
    cross-checks.

    A third leg, ``time_to_recover``, prices the elastic plane's three
    recovery paths (peer-RAM vs disk vs full supervised restart) on the
    scripted virtual-clock episode — deterministic, merged into the same
    artifact.
    """
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_training_tpu.models import create_model
    from pytorch_distributed_training_tpu.resilience import (
        AnomalyPolicy, RecoveryConfig, RecoveryManager, init_resilience_state,
    )
    from pytorch_distributed_training_tpu.train import (
        Trainer, TrainerConfig, create_train_state, make_policy,
        make_train_step,
    )

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        overrides, dtype, batch, seq = None, jnp.bfloat16, 32, 1024
        steps = 24
    else:
        # Same CPU-proxy sizing as the telemetry bench: compute must
        # dominate Python dispatch or the ratio prices the interpreter.
        overrides = dict(num_layers=2, hidden_dim=128, num_heads=4,
                         vocab_size=2048, max_seq_len=128)
        dtype, batch, seq = jnp.float32, 8, 128
        steps = 40
    snapshot_every = 10
    model = create_model("gpt2", cfg_overrides=overrides, dtype=dtype)

    def fresh_state(policy_on):
        state = create_train_state(
            model, jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32),
            optax.adamw(1e-3), init_kwargs={"train": False},
        )
        if policy_on:
            state = state.replace(resilience=init_resilience_state())
        return state

    policy = make_policy("bf16" if on_tpu else "f32")
    step_off = make_train_step(
        kind="lm", policy=policy, base_rng=jax.random.PRNGKey(1),
    )
    step_on = make_train_step(
        kind="lm", policy=policy, base_rng=jax.random.PRNGKey(1),
        anomaly_policy=AnomalyPolicy(grad_norm_threshold=1e9),
    )
    rng = np.random.default_rng(0)
    b = {"tokens": jnp.asarray(
        rng.integers(0, model.cfg.vocab_size, (batch, seq)), jnp.int32
    )}
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    cfg = TrainerConfig(progress=False, log_every=10_000, prefetch=0)
    held = {False: fresh_state(False), True: fresh_state(True)}

    def leg(policy_on):
        recovery = (
            RecoveryManager(RecoveryConfig(snapshot_every_steps=snapshot_every))
            if policy_on else None
        )
        trainer = Trainer(
            held[policy_on], step_on if policy_on else step_off, mesh, cfg,
            recovery=recovery,
        )
        t0 = time.perf_counter()
        trainer.run_epoch([b] * steps)  # closes with a loss fetch
        dt = time.perf_counter() - t0
        held[policy_on] = trainer.state
        return dt

    leg(False)  # compile + warm both programs
    leg(True)
    off_times, on_times = [], []
    rounds = BENCH_ROUNDS + 2
    for r in range(rounds):
        if r % 2 == 0:
            off = leg(False)
            on = leg(True)
        else:
            on = leg(True)
            off = leg(False)
        off_times.append(off)
        on_times.append(on)
    ratios = [on / off for on, off in zip(on_times, off_times)]
    overhead = _median(ratios) - 1.0
    t_off, t_on = _median(off_times), _median(on_times)

    # Isolated snapshot-staging cost: device_get of the learned state,
    # timed alone, amortized over the cadence — the deterministic number
    # the A/B ratio is too noisy to resolve on this sandbox.
    rec = RecoveryManager(RecoveryConfig(snapshot_every_steps=snapshot_every))
    rec.stage(held[True], 0)  # warm
    n_iso = 20
    t0 = time.perf_counter()
    for i in range(n_iso):
        rec.stage(held[True], i)
    per_stage_s = (time.perf_counter() - t0) / n_iso
    implied = (per_stage_s / snapshot_every) / (t_off / steps)
    _emit({
        "metric": "resilience_overhead",
        # Headline = isolated snapshot cost amortized over the cadence,
        # over the measured off-leg step time; the end-to-end A/B ratio
        # (which also carries the in-jit gate) is the noise-bounded
        # cross-check.
        "value": round(implied, 6),
        "unit": "relative step-time overhead (skip policy + snapshots on)",
        "target": "< 0.01",
        "pass": bool(implied < 0.01),
        "snapshot_every_steps": snapshot_every,
        "steps_per_leg": steps,
        "batch": batch,
        "seq": seq,
        "per_step_ms": {
            "off": round(t_off / steps * 1e3, 3),
            "on": round(t_on / steps * 1e3, 3),
        },
        "snapshot_stage_ms": round(per_stage_s * 1e3, 3),
        "ab_ratio_overhead": round(overhead, 5),
        "ab_ratio_spread": [
            round(min(ratios) - 1.0, 4), round(max(ratios) - 1.0, 4),
        ],
        "protocol": (
            "headline: isolated snapshot-staging cost / cadence / median "
            f"off-leg step time; cross-check: median of {rounds} paired "
            "A/B ratios, order alternated per round (cancels linear "
            f"drift), {steps} chained steps per leg; ON leg = lax.cond "
            "anomaly gate (grad-norm threshold armed, nothing firing) + "
            f"host snapshot every {snapshot_every} steps"
        ),
        "ratios": [round(r, 4) for r in ratios],
        "off_runs": [round(t, 4) for t in off_times],
        "on_runs": [round(t, 4) for t in on_times],
        "time_to_recover": _time_to_recover_leg(),
    }, "RESILIENCE_BENCH.json" if "--save" in sys.argv[1:] else None)


if __name__ == "__main__":
    from pytorch_distributed_training_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    if "--pipeline" in sys.argv[1:]:
        main_pipeline()
    elif "--device-cache" in sys.argv[1:]:
        main_device_cache()
    elif "--gpt2" in sys.argv[1:]:
        main_gpt2()
    elif "--vit" in sys.argv[1:]:
        main_vit()
    elif "--moe" in sys.argv[1:]:
        main_gpt2(moe=True)
    elif "--generate" in sys.argv[1:]:
        main_generate()
    elif "--serve" in sys.argv[1:] and "--autoscale" in sys.argv[1:]:
        # Autoscale leg only: merged into the existing SERVE_BENCH.json
        # under "autoscale" (same independent-leg contract as the
        # failover key; virtual-clock deterministic).
        main_serve_autoscale()
    elif "--serve" in sys.argv[1:] and "--failover" in sys.argv[1:]:
        # Failover leg only: merged into the existing SERVE_BENCH.json
        # (the other serving legs are untouched — this leg is virtual-
        # clock deterministic and can regenerate independently).
        main_serve_failover()
    elif "--serve" in sys.argv[1:] and "--kv-quant" in sys.argv[1:]:
        # Quantized-KV legs only: merged into the existing
        # SERVE_BENCH.json under "kv_quant" (same independent-leg
        # contract as the failover key).
        main_serve_quant()
    elif "--serve" in sys.argv[1:]:
        main_serve()
    elif "--telemetry-overhead" in sys.argv[1:]:
        main_telemetry_overhead()
    elif "--goodput" in sys.argv[1:]:
        main_goodput()
    elif "--resilience-overhead" in sys.argv[1:]:
        main_resilience_overhead()
    elif "--grad-sync-diag" in sys.argv[1:]:
        # Gradient-sync accounting (GRAD_SYNC_BENCH.json): per-mode parity
        # + compiled cost + DCN byte tables for the full compression
        # ladder (bf16/int8/int4/topk), the auto-bucket recommendation,
        # the top-k transmitted-fraction sweep leg, and the compressed+EF
        # convergence runs.  Runs on the simulated 2-slice mesh, so the
        # CPU device count must be set before the backend initializes (a
        # no-op when a TPU is attached — the option only sizes the CPU
        # backend).
        from pytorch_distributed_training_tpu.compat import (
            set_cpu_device_count,
        )

        set_cpu_device_count(8)
        from tools.grad_sync_diag import main as main_grad_sync_diag

        main_grad_sync_diag()
    else:
        main()
