"""Gradient-sync diagnosis: per-mode DCN bytes/step + parity + compiled cost.

Closes the ISSUE-1 accounting requirement: the hierarchical sync
(comm/hierarchical.py, ``--grad-sync``) claims a compressed cross-slice hop,
so the artifact must show (a) the slice-boundary byte count per mode, (b)
that the explicit two-tier formulation is numerically a drop-in for the flat
GSPMD psum, and (c) what the reformulation costs in compiled FLOPs/bytes.

Everything measurable here runs on the simulated 2-slice hybrid mesh the
multichip dryrun leg uses (8 CPU devices, ``data`` spanning two contiguous
granules); the DCN byte table is analytic (``dcn_bytes_per_sync``) and is
also evaluated at the GPT-2 124M / BASELINE 2x8 headline scale, where the
cross-slice hop is the bandwidth wall the compression targets.

Reports, per mode in {flat, hier, hier-bf16, hier-int8, hier-int4,
hier-topk}:
  * analytic DCN bytes per optimizer step (one sync/step; the overlapped
    per-microbatch variant multiplies by ``accum`` and is listed separately
    with its compute-hiding tradeoff),
  * measured max |grad - grad_flat| on the simulated 2-slice mesh,
  * compiled cost (XLA flops / bytes accessed) of the full train step and
    its delta vs flat,
plus the ``--grad-sync-bucket-mb auto`` recommendation per mode at the
GPT-2 124M headline scale, a top-k transmitted-fraction sweep (the bench's
sweep leg: bytes + one-step parity per fraction), and short compressed+EF
vs fp32 convergence runs (tiny ResNet on ShapeImages, the
tests/test_convergence_stack.py harness) showing the error-feedback
trajectories land in the fp32 loss band.

Usage: python tools/grad_sync_diag.py [--steps N] [--save]
--save writes GRAD_SYNC_BENCH.json.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GPT2_124M_PARAMS = 124_439_808


def _ensure_devices():
    import jax

    if jax.default_backend() != "tpu" and jax.local_device_count() < 8:
        raise SystemExit(
            "grad_sync_diag needs 8 devices: set JAX_PLATFORMS=cpu with "
            "the CPU device count applied before JAX initializes "
            "(compat.set_cpu_device_count)"
        )


def tiny_lm_setup(mesh, mode, accum=1, *, zero1=False, seed=0,
                  bucket_mb=0.002, topk_frac=0.1, stripe="off",
                  phase_overlap=False):
    """Tiny GPT-2 state + step on ``mesh`` under sync ``mode``.

    The CANONICAL parity harness: tests/test_hier_sync.py runs its
    exactness assertions on exactly this setup, and the published
    GRAD_SYNC_BENCH.json parity numbers come from it too — one body, so
    the artifact can't silently desynchronize from the test that vouches
    for it.  The tiny ``bucket_mb`` makes the ~80k-param model span
    multiple buckets (the bucketed path, not the single-bucket degenerate
    case — asserted here for every non-flat mode)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_training_tpu.comm import GradSync, GradSyncConfig
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2, GPT2Config
    from pytorch_distributed_training_tpu.parallel.sharding import DDP_RULES
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_train_step,
    )

    cfg = GPT2Config(
        vocab_size=128, max_seq_len=16, num_layers=2, num_heads=2,
        hidden_dim=32,
    )
    state = create_train_state(
        GPT2(cfg=cfg), jax.random.PRNGKey(seed),
        jnp.zeros((8, 16), jnp.int32),
        optax.adam(1e-3), mesh=mesh, rules=DDP_RULES,
        init_kwargs={"train": False},
    )
    sync = None
    if mode != "flat":
        sync = GradSync(
            mesh, state.params,
            GradSyncConfig(
                mode=mode, n_slices=2, bucket_mb=bucket_mb, zero1=zero1,
                topk_frac=topk_frac, stripe=stripe,
                phase_overlap=phase_overlap,
            ),
        )
        assert sync.layout.n_buckets > 1
        state = state.replace(grad_sync_residual=sync.init_residual())
    step = make_train_step(kind="lm", num_microbatches=accum, grad_sync=sync)
    # Inside the sync's shard_map the batch dim is per-device (global / 8),
    # and each device must still split it into ``accum`` microbatches.
    rows = 8 * max(accum, 2)
    batch = {
        "tokens": np.random.default_rng(7).integers(0, 128, (rows, 16), np.int32)
    }
    return state, step, batch, sync


def _grads_for(mesh, mode, topk_frac=0.1):
    """One step's raw gradient under ``mode`` (accum=1), as a flat vector."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch

    state, step, batch, _ = tiny_lm_setup(mesh, mode, 1, topk_frac=topk_frac)
    p0 = jax.tree_util.tree_map(np.asarray, state.params)
    with mesh:
        state, _ = step(state, shard_batch(batch, mesh))
    p1 = jax.tree_util.tree_map(np.asarray, state.params)
    # Adam with fixed lr: the first-step update is lr*sign-ish, but the
    # PARAM DELTA comparison below is done flat-vs-mode on identical math,
    # so returning params-after-one-step is the right parity probe.
    return np.concatenate([
        (np.asarray(a) - np.asarray(b)).ravel()
        for a, b in zip(
            jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p0)
        )
    ])


def _compiled_cost(mesh, mode, accum):
    import jax

    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch

    state, step, batch, sync = tiny_lm_setup(mesh, mode, accum)
    with mesh:
        compiled = step.lower(state, shard_batch(batch, mesh)).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }, sync


def _min_time(fn, repeats=5):
    """min-of-N wall of ``fn()`` (blocks on the result) — the estimator
    least sensitive to host scheduling noise on the CPU backend."""
    import time

    import jax

    jax.block_until_ready(fn())  # warm / compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def phase_walls(mesh, sync, repeats=5):
    """Measured per-phase walls of ONE sync's tiers on the simulated mesh.

    Jits two shard_map programs over the sync's split mesh — the ICI legs
    (RS + AG over the real bucket matrix) and the DCN leg (encode +
    cross-slice hop + decode on the scattered shards, EF residual
    included) — and times each in isolation.  The point: the simulated
    CPU mesh executes every collective on ONE fabric (host memory), so an
    end-to-end wall cannot exhibit ICI/DCN concurrency; what IS
    measurable is each fabric's phase time, and the overlap wall model
    (``obs.cost.grad_sync_wall_model``'s max-plus-bubble shape) evaluated
    on the MEASURED per-bucket times is the measured overlap ratio.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from pytorch_distributed_training_tpu.compat import shard_map

    nb, elems = sync.layout.n_buckets, sync.layout.bucket_elems
    buckets = jnp.ones((nb, elems), jnp.float32)
    part = jnp.ones((nb, elems // sync.ici_size), jnp.float32)
    resid = sync.init_residual()
    resid_spec = (
        P((sync.dcn_axis, sync.ici_axis), None, None)
        if sync.has_residual else P()
    )

    ici_fn = jax.jit(shard_map(
        lambda b: sync._ag(sync._rs(b)),
        mesh=sync.smesh, in_specs=P(), out_specs=P(), check_vma=False,
    ))

    def _dcn_local(p, r):
        summed, r_out = sync._dcn_allreduce(
            p, r[0] if sync.has_residual else ()
        )
        return summed, (r_out[None] if sync.has_residual else ())

    dcn_fn = jax.jit(shard_map(
        _dcn_local,
        mesh=sync.smesh, in_specs=(P(), resid_spec),
        out_specs=(P(), resid_spec), check_vma=False,
    ))

    with mesh:
        t_ici = _min_time(lambda: ici_fn(buckets), repeats)
        t_dcn = _min_time(lambda: dcn_fn(part, resid)[0], repeats)
    u, v = t_ici / nb, t_dcn / nb
    return {
        "ici_s": t_ici,
        "dcn_s": t_dcn,
        "wall_serial_s": t_ici + t_dcn,
        "wall_overlap_s": nb * max(u, v) + min(u, v),
        "overlap_ratio": (t_ici + t_dcn) / (nb * max(u, v) + min(u, v)),
    }


def striping_sweep(mesh, mode="hier-int8", repeats=5):
    """Overlap on/off × stripe-count sweep (the tentpole's bench leg).

    Per config: bitwise parity of params-after-one-step vs the serial
    unstriped schedule, the MODELED walls (analytic bytes through
    ``grad_sync_wall_model``), the MEASURED per-phase walls
    (``phase_walls``) with the overlap ratio they imply, and the raw
    end-to-end step wall (which on the one-fabric CPU backend grows with
    stripe/overlap op count rather than shrinking — recorded for honesty,
    not as the overlap evidence)."""
    import jax
    import numpy as np

    from pytorch_distributed_training_tpu.obs import grad_sync_wall_model
    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch

    def run(stripe, overlap):
        import time

        state, step, batch, sync = tiny_lm_setup(
            mesh, mode, 1, stripe=stripe, phase_overlap=overlap
        )
        with mesh:
            sb = shard_batch(batch, mesh)
            state, _ = step(state, sb)
            jax.block_until_ready(state.params)
            params = np.concatenate([
                np.asarray(l).ravel()
                for l in jax.tree_util.tree_leaves(state.params)
            ])
            # The step donates its state, so the timing loop must chain
            # the returned state instead of re-calling on a dead buffer.
            step_wall = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                state, _ = step(state, sb)
                jax.block_until_ready(state.params)
                step_wall = min(step_wall, time.perf_counter() - t0)
        return params, sync, step_wall

    base_params, base_sync, base_wall = run("off", False)
    out = {}
    for stripe, overlap in (
        ("off", False), ("off", True), (2, False), (2, True), (4, True)
    ):
        params, sync, step_wall = run(stripe, overlap)
        wall = grad_sync_wall_model(
            ici_bytes=sync.ici_bytes_per_sync(),
            dcn_bytes=sync.dcn_bytes_per_sync(),
            n_buckets=sync.layout.n_buckets,
            n_slices=sync.n_slices, ici_size=sync.ici_size,
            stripe=sync.stripe, phase_overlap=sync.phase_overlap,
        )
        key = f"stripe={stripe},overlap={'on' if overlap else 'off'}"
        out[key] = {
            "stripe": sync.stripe,
            "phase_overlap": sync.phase_overlap,
            "n_buckets": sync.layout.n_buckets,
            "bitwise_equal_vs_serial": bool(
                np.array_equal(params, base_params)
            ),
            "modeled": {
                k: round(v, 9) if isinstance(v, float) else v
                for k, v in wall.items()
            },
            "measured_phase": {
                k: round(v, 6) for k, v in phase_walls(
                    mesh, sync, repeats
                ).items()
            },
            "step_wall_measured_s": round(step_wall, 6),
        }
    return out, base_wall


def shapes_convergence(mesh, mode, steps, *, seed=0, optimizer="adam"):
    """Tiny ResNet on ShapeImages: loss trajectory under sync ``mode``.

    The CANONICAL compressed+EF convergence harness — shared by
    tests/test_convergence_stack.py (the fp32-band assertions) and the
    GRAD_SYNC_BENCH.json entries, so both report the identical run.

    ``optimizer``: ``"adam"`` (the int8/int4 ladder's harness) or
    ``"sgd-m"`` (SGD + momentum 0.9).  The top-k leg runs under sgd-m:
    error feedback's convergence guarantee is an SGD-class result, and
    under Adam the 1-in-1/frac spiky arrivals of EF-deferred coordinates
    fight the per-coordinate normalization — measured as a persistent
    ~10x slowdown on the unselected mass, where the sgd-m trajectory
    re-joins the fp32 band once the EF ramp warms up (the paired flat
    baseline uses the identical optimizer either way)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_training_tpu.comm import GradSync, GradSyncConfig
    from pytorch_distributed_training_tpu.data import ShapeImages
    from pytorch_distributed_training_tpu.models.resnet import (
        BasicBlock, ResNet,
    )
    from pytorch_distributed_training_tpu.parallel.sharding import (
        DDP_RULES, shard_batch,
    )
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_train_step,
    )

    model = ResNet(
        stage_sizes=(1, 1), block=BasicBlock, num_classes=10,
        num_filters=8, small_stem=True,
    )
    if optimizer == "adam":
        tx = optax.adam(3e-3)
    elif optimizer == "sgd-m":
        tx = optax.sgd(0.05, momentum=0.9)
    else:
        raise ValueError(f"unknown optimizer {optimizer!r}")
    state = create_train_state(
        model, jax.random.PRNGKey(seed),
        jnp.zeros((1, 32, 32, 3), jnp.float32), tx,
        mesh=mesh, rules=DDP_RULES, init_kwargs={"train": False},
    )
    sync = None
    if mode != "flat":
        sync = GradSync(
            mesh, state.params,
            GradSyncConfig(mode=mode, n_slices=2, bucket_mb=0.01),
        )
        assert sync.layout.n_buckets > 1  # multi-bucket EF, not degenerate
        state = state.replace(grad_sync_residual=sync.init_residual())
    step = make_train_step(kind="image_classifier", grad_sync=sync)
    ds = ShapeImages(n=64, seed=0)
    batch = {
        "image": (ds.images / np.float32(255.0)).astype(np.float32),
        "label": ds.labels,
    }
    losses = []
    with mesh:
        sb = shard_batch(batch, mesh)
        for _ in range(steps):
            state, m = step(state, sb)
            losses.append(float(m["loss"]))
    return losses


def main():
    import jax
    import numpy as np

    _ensure_devices()

    from pytorch_distributed_training_tpu.comm import (
        GRAD_SYNC_MODES, MeshConfig, make_hybrid_mesh,
    )
    from pytorch_distributed_training_tpu.comm.hierarchical import (
        dcn_bytes_per_sync,
    )

    steps = 24
    if "--steps" in sys.argv[1:]:
        steps = int(sys.argv[sys.argv.index("--steps") + 1])

    from pytorch_distributed_training_tpu.comm.compress import auto_bucket_mb

    mesh = make_hybrid_mesh(
        MeshConfig(data=-1), devices=jax.devices()[:8], n_slices=2
    )

    # --- parity: params-after-one-step vs flat, per mode -----------------
    base = _grads_for(mesh, "flat")
    parity = {}
    for mode in ("hier", "hier-bf16", "hier-int8", "hier-int4", "hier-topk"):
        dev = _grads_for(mesh, mode)
        parity[mode] = float(np.abs(dev - base).max())

    # --- compiled cost: full train step, accum=4, per mode ---------------
    accum = 4
    costs, layouts, ici = {}, {}, None
    for mode in GRAD_SYNC_MODES:
        cost, sync = _compiled_cost(mesh, mode, accum)
        costs[mode] = cost
        if sync is not None:
            layouts[mode] = (sync.layout.padded, sync.layout.n_buckets)
            ici = sync.ici_size
    flat_cost = costs["flat"]
    layout_elems = layouts["hier"][0]

    # --- DCN byte tables --------------------------------------------------
    def table(n_elems, n_slices, ici_size, buckets_of=None):
        """Per-mode bytes + vs-flat ratio; ``buckets_of(mode)`` supplies the
        per-bucket scale/selection granularity (1 when unknown)."""
        buckets_of = buckets_of or (lambda mode: 1)
        flat = dcn_bytes_per_sync(n_elems, n_slices, ici_size, "flat")
        return {
            mode: {
                "dcn_bytes_per_step": dcn_bytes_per_sync(
                    n_elems, n_slices, ici_size, mode,
                    n_buckets=buckets_of(mode),
                ),
                "vs_flat": round(
                    flat / max(
                        dcn_bytes_per_sync(
                            n_elems, n_slices, ici_size, mode,
                            n_buckets=buckets_of(mode),
                        ), 1,
                    ), 2,
                ),
            }
            for mode in GRAD_SYNC_MODES
        }

    # --- auto bucket sizing at the headline scale -------------------------
    # The ``--grad-sync-bucket-mb auto`` recommendation per mode: the DCN
    # latency x bandwidth crossover scaled by the codec's wire width
    # (comm.compress.auto_bucket_mb), evaluated for GPT-2 124M — and the
    # bucket counts it implies, which the headline byte table uses for its
    # per-bucket scale overhead.
    total_bytes_124m = 4 * GPT2_124M_PARAMS
    auto_sizes = {
        mode: auto_bucket_mb(total_bytes_124m, mode=mode)
        for mode in GRAD_SYNC_MODES
        if mode != "flat"
    }
    # Same ceil-div as _BucketLayout.build, so these counts equal the
    # n_buckets a live run at the auto size would build and record.
    auto_buckets = {
        mode: -(-GPT2_124M_PARAMS // max(int(mb * (1 << 20) / 4), 1))
        for mode, mb in auto_sizes.items()
    }
    gpt2_table = table(
        GPT2_124M_PARAMS, 2, 8,
        buckets_of=lambda mode: auto_buckets.get(mode, 1),
    )

    # --- top-k fraction sweep (the bench leg) ----------------------------
    # Bytes at the headline scale plus the measured one-Adam-step param
    # delta vs flat on the tiny harness, per transmitted fraction.
    topk_sweep = {}
    for frac in (0.05, 0.1, 0.25):
        bytes_124m = dcn_bytes_per_sync(
            GPT2_124M_PARAMS, 2, 8, "hier-topk",
            n_buckets=auto_buckets["hier-topk"], topk_frac=frac,
        )
        dev = _grads_for(mesh, "hier-topk", topk_frac=frac)
        topk_sweep[str(frac)] = {
            "dcn_bytes_gpt2_124m": bytes_124m,
            "vs_flat": round(
                dcn_bytes_per_sync(GPT2_124M_PARAMS, 2, 8, "flat")
                / bytes_124m, 2,
            ),
            "parity_max_param_delta": round(
                float(np.abs(dev - base).max()), 8
            ),
        }

    # --- striping + phase pipelining (the PR-16 tentpole's bench leg) -----
    from pytorch_distributed_training_tpu.comm import (
        ici_bytes_per_sync as ici_bytes_model,
    )
    from pytorch_distributed_training_tpu.obs import grad_sync_wall_model

    stripe_sweep, _ = striping_sweep(mesh)
    # Modeled walls at the headline scale: auto bucket sized FOR the
    # pipelined regime (the sizer caps the bucket so >= 3 are in flight),
    # stripe=auto(4) on the 2x8 topology.
    wall_124m = {}
    for m in ("hier", "hier-int8", "hier-topk"):
        mb = auto_bucket_mb(total_bytes_124m, mode=m, phase_overlap=True)
        nb = -(-GPT2_124M_PARAMS // max(int(mb * (1 << 20) / 4), 1))
        wall = grad_sync_wall_model(
            ici_bytes=ici_bytes_model(
                GPT2_124M_PARAMS, 2, 8, m, n_buckets=nb, stripe=4
            ),
            dcn_bytes=dcn_bytes_per_sync(
                GPT2_124M_PARAMS, 2, 8, m, n_buckets=nb
            ),
            n_buckets=nb, n_slices=2, ici_size=8,
            stripe=4, phase_overlap=True,
        )
        wall_124m[m] = {
            "auto_bucket_mb": mb, "n_buckets": nb, "stripe": 4,
            "wall_serial_s": round(wall["wall_serial_s"], 6),
            "wall_overlap_s": round(wall["wall_overlap_s"], 6),
            "bubble_s": round(wall["bubble_s"], 9),
            "overlap_ratio": round(wall["overlap_ratio"], 3),
        }

    # --- convergence: compressed+EF inside the fp32 band ------------------
    # int8/int4 pair against flat under the canonical adam harness; the
    # top-k pair runs under sgd-m for 3x the steps (see the
    # shapes_convergence docstring: EF is an SGD-class guarantee, and the
    # sparse stream needs its warm-up ramp before the band comparison is
    # meaningful — both sides of the pair share optimizer and horizon).
    conv_flat = shapes_convergence(mesh, "flat", steps)
    conv = {
        mode: shapes_convergence(mesh, mode, steps)
        for mode in ("hier-int8", "hier-int4")
    }
    topk_steps = 3 * steps
    conv_flat_sgdm = shapes_convergence(
        mesh, "flat", topk_steps, optimizer="sgd-m"
    )
    conv_topk = shapes_convergence(
        mesh, "hier-topk", topk_steps, optimizer="sgd-m"
    )

    def band(trace, ref):
        return bool(
            abs(trace[-1] - ref[-1])
            <= 0.15 * max(ref[0] - ref[-1], 1e-3) + 0.02
        )

    out = {
        "metric": "grad_sync_diagnosis",
        "mesh": "simulated 2-slice hybrid (8 CPU devices, data=8 over DCN)"
        if jax.default_backend() != "tpu" else f"{dict(mesh.shape)} 2-slice",
        "parity_max_param_delta_vs_flat_one_adam_step": {
            m: round(v, 8) for m, v in parity.items()
        },
        "parity_tolerances_documented": {
            "hier": 1e-5, "hier-bf16": 5e-2, "hier-int8": 2e-1,
            "hier-int4": 2e-1, "hier-topk": 2e-1,
        },
        "compiled_cost_accum4": {
            mode: {
                **{k: round(v, 1) for k, v in cost.items()},
                "flops_vs_flat": round(
                    cost["flops"] / max(flat_cost["flops"], 1), 3
                ),
                "bytes_vs_flat": round(
                    cost["bytes_accessed"]
                    / max(flat_cost["bytes_accessed"], 1), 3,
                ),
            }
            for mode, cost in costs.items()
        },
        "dcn_bytes_measured_model": {
            "n_elems_padded": layout_elems,
            "n_slices": 2,
            "ici": ici,
            "modes": table(
                layout_elems, 2, ici,
                buckets_of=lambda mode: layouts.get(mode, (0, 1))[1],
            ),
        },
        "dcn_bytes_gpt2_124m_2x8": {
            "n_elems": GPT2_124M_PARAMS,
            "n_slices": 2,
            "ici": 8,
            "auto_bucket_mb": auto_sizes,
            "auto_n_buckets": auto_buckets,
            "modes": gpt2_table,
        },
        "headline": {
            # The ISSUE-6 acceptance ratios, at the headline scale with
            # auto-sized buckets: int4 >= 8x and top-k(10%) >= 15x fewer
            # DCN bytes than the uncompressed hop.  Baseline is the
            # flat/f32 DDP hop — the series the whole ladder is quoted
            # against (bf16 2x, int8 4x, int4 8x, topk 17.8x); ratios vs
            # the bf16 payload are exactly half these.
            "baseline": "flat (uncompressed f32 DCN hop)",
            "int4_vs_flat": gpt2_table["hier-int4"]["vs_flat"],
            "topk10_vs_flat": gpt2_table["hier-topk"]["vs_flat"],
            "int4_vs_bf16": round(
                gpt2_table["hier-int4"]["vs_flat"]
                / gpt2_table["hier-bf16"]["vs_flat"], 2,
            ),
            "topk10_vs_bf16": round(
                gpt2_table["hier-topk"]["vs_flat"]
                / gpt2_table["hier-bf16"]["vs_flat"], 2,
            ),
            # PR-16 tentpole: wall ratio of the serialized bucket schedule
            # over the striped+pipelined one.  Modeled at the headline
            # scale; measured from the per-phase walls on the simulated
            # 2-slice mesh (striping_phase_pipelining.sweep).
            "overlap_ratio_modeled_hier_int8": wall_124m["hier-int8"][
                "overlap_ratio"
            ],
            "overlap_ratio_measured_phase_hier_int8": stripe_sweep[
                "stripe=2,overlap=on"
            ]["measured_phase"]["overlap_ratio"],
        },
        "topk_frac_sweep": topk_sweep,
        "striping_phase_pipelining": {
            # --grad-sync-stripe / --grad-sync-overlap (comm/striping.py):
            # per config, bitwise parity vs the serial unstriped schedule,
            # the modeled walls (analytic bytes through the two-resource
            # pipeline model), and the measured per-phase walls with the
            # overlap ratio THEY imply.  The simulated CPU mesh runs every
            # collective on one fabric, so the end-to-end step wall grows
            # with stripe/overlap op count there — the measured overlap
            # evidence is the per-phase timing, not the step wall.
            "sweep_mode": "hier-int8",
            "modeled_wall": "nb*max(ici, dcn) + min(ici, dcn) "
                            "(max of the fabrics + one fill/drain bubble)",
            "sweep": stripe_sweep,
            "modeled_gpt2_124m_2x8_stripe4_overlap": wall_124m,
        },
        "overlap_note": (
            "tables are one sync per optimizer step (accum=1, or "
            "overlap=False's no_sync contract); --grad-sync's default "
            "overlapped form syncs every microbatch — accum x the bytes, "
            "each transfer hidden under the next microbatch's compute"
        ),
        "convergence_compressed_ef": {
            "harness": "tiny ResNet (1-1 stages, 8 filters) on ShapeImages",
            "steps": steps,
            "loss_first": round(conv_flat[0], 4),
            "fp32_final_loss": round(conv_flat[-1], 4),
            **{
                f"{mode.split('-', 1)[1]}_ef_final_loss":
                    round(trace[-1], 4)
                for mode, trace in conv.items()
            },
            "within_fp32_band": {
                mode: band(trace, conv_flat)
                for mode, trace in conv.items()
            },
        },
        "convergence_topk_ef_sgdm": {
            "harness": "same tiny ResNet; sgd+momentum(0.9) lr=0.05 — the "
                       "EF-matched optimizer class (Adam's per-coordinate "
                       "normalization fights the sparse EF stream; "
                       "measured, see shapes_convergence docstring)",
            "steps": topk_steps,
            "topk_frac": 0.1,
            "fp32_final_loss": round(conv_flat_sgdm[-1], 4),
            "topk_ef_final_loss": round(conv_topk[-1], 4),
            "within_fp32_band": band(conv_topk, conv_flat_sgdm),
        },
    }
    print(json.dumps(out))
    if "--save" in sys.argv[1:]:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "GRAD_SYNC_BENCH.json",
        )
        with open(path, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    # Size the simulated CPU backend before it initializes; a no-op for
    # the device count when a real TPU backend wins platform selection.
    from pytorch_distributed_training_tpu.compat import set_cpu_device_count

    set_cpu_device_count(8)
    main()
