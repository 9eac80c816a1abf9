"""Training cells whose LM objective is block diffusion
(``train/block_diffusion.py``): ``kinds/train.py``'s run — the program's own
mesh, model, state, step, loader and ``Trainer.run_epoch``, the same warm-up,
calibration, N = floor(seconds / step time), two-epoch traced window and
``train_mfu`` formula — with two differences.

(a) The reference check.  The step draws its noise from its own key, so the
plain reference has to be handed the positions the step masked: one probe
sequence at the TIMED length goes through the program's own step (SGD of
rate 1: the gradient is old params minus new), the masks are rebuilt with
the program's public noising function from the key the step used
(``fold_in(fold_in(base_rng, step 0), microbatch 0)``), and the reference's
loss and gradient norm on those masks are compared.  The optimizer's slots
are built after the check: both do not fit beside the reference's gradients.

(b) The step's MoE counters (device scalars, fetched after the window like
the losses) go into ``facts["counters"]`` for the per-layer readers, and the
share of the routed assignments that ran on held experts goes into the
FLOPs count: a dropless layer's work follows the routing.

A later ``benchmark`` issue folds the two kinds into one (PERF.md section 7).
"""

from __future__ import annotations

import importlib
import itertools
import math

from ..harness import model_overrides
from .train import TimedBatches, _batch_source

COUNTERS = ("moe_held_assignments", "moe_load_max", "masked_tokens")


def _reference_check(ctx, mesh, net, state, step_kw, probe):
    import jax
    import jax.numpy as jnp
    import optax

    from pytorch_distributed_training_tpu import train
    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch
    from pytorch_distributed_training_tpu.train import block_diffusion

    from ..reference import global_norm

    check = ctx.cell["reference_check"]
    batch = probe(int(check["samples_per_device"]) * len(ctx.devices))
    sgd = optax.sgd(1.0)
    params = jax.tree_util.tree_map(jnp.copy, state.params)   # the step donates its state
    probe_state = state.replace(step=jnp.zeros((), jnp.int32), params=params,
                                opt_state=sgd.init(params), tx=sgd)
    step = train.make_train_step(num_microbatches=1, **step_kw)
    key = jax.random.fold_in(jax.random.fold_in(step_kw["base_rng"], 0), 0)
    ref = importlib.import_module(f"benchmark.reference.{ctx.config['system']['reference']}")
    with mesh:
        placed = shard_batch(batch, mesh)
        new_state, metrics = step(probe_state, placed)
        sys_norm = float(jax.jit(
            lambda old, new: global_norm(jax.tree_util.tree_map(jnp.subtract, old, new))
        )(state.params, new_state.params))
        sys_loss = float(metrics["loss"])
        del new_state, probe_state, params
        _, masked, p = jax.jit(
            lambda t, k: block_diffusion.noise(t, k, net.cfg))(placed["tokens"], key)
        ref_loss, ref_norm = (float(x) for x in jax.jit(
            lambda prm, t, m, q: ref.loss_and_grad_norm(prm, t, m, q, ctx.config)
        )(state.params, placed["tokens"], masked, p))
    loss_err = abs(sys_loss - ref_loss) / abs(ref_loss)
    norm_err = abs(sys_norm - ref_norm) / abs(ref_norm)
    ok = loss_err <= float(check["loss_rtol"]) and norm_err <= float(check["grad_norm_rtol"])
    print(f"reference check: {int(jax.numpy.sum(masked))} masked of {masked.size}; loss system "
          f"{sys_loss:.6f} reference {ref_loss:.6f} (rel {loss_err:.2e}, tol {check['loss_rtol']}); "
          f"grad norm system {sys_norm:.6f} reference {ref_norm:.6f} (rel {norm_err:.2e}, tol "
          f"{check['grad_norm_rtol']}) -> {'ok' if ok else 'FAILED'}",
          flush=True)
    return ok


def run(ctx) -> dict:
    import jax
    import numpy as np
    import optax

    from pytorch_distributed_training_tpu import comm, models, train
    from pytorch_distributed_training_tpu.comm.mesh import batch_shard_size

    ctx.mark("imports done, backend up")
    cell, config = ctx.cell, ctx.config
    system, step_spec = config["system"], cell["step"]
    samples, micro = int(step_spec["samples"]), int(step_spec["microbatches"])

    mesh = comm.make_mesh(comm.MeshConfig(**cell.get("mesh", {})), devices=ctx.devices)
    policy = train.make_policy(system["precision"]["train"])
    net = models.create_model(system["registry"], dtype=policy.compute_dtype,
                              cfg_overrides=model_overrides(config))
    sample = jax.numpy.zeros((batch_shard_size(mesh), int(step_spec["seq_len"])), jax.numpy.int32)
    # As in kinds/train.py: the state from a fixed key (one cached init
    # program), the seed's weights by the model's own init with the key as
    # an argument.  The optimizer comes after the reference check.
    state = train.create_train_state(
        net, jax.random.PRNGKey(0), sample, optax.sgd(1.0), mesh=mesh, init_kwargs={"train": False},
    )
    seeded = jax.jit(
        lambda key: net.init(key, sample, train=False)["params"],
        out_shardings=jax.tree_util.tree_map(lambda x: x.sharding, state.params),
    )
    with mesh:
        state = state.replace(params=seeded(jax.random.PRNGKey(ctx.seed32)))
    ctx.mark("model and params built")
    take, _, probe = _batch_source(ctx, mesh, samples)
    ctx.mark("input ready")
    step_kw = dict(kind="lm", policy=policy,
                   base_rng=jax.random.PRNGKey((ctx.seed32 + 1) % 2147483629))
    reference_ok = _reference_check(ctx, mesh, net, state, step_kw, probe)
    ctx.mark("reference check done")

    opt = system["optimizer"]
    tx = getattr(optax, opt["name"])(float(opt["learning_rate"]))
    with mesh:
        slots = jax.jit(tx.init, out_shardings=None)(state.params)
    state = state.replace(tx=tx, opt_state=slots)
    jitted = train.make_train_step(num_microbatches=micro, **step_kw)
    seen: list = []              # every step's loss and counters, as device scalars

    def step_fn(s, batch):
        s, metrics = jitted(s, batch)
        seen.append({k: metrics[k] for k in ("loss",) + COUNTERS if k in metrics})
        return s, metrics

    trainer = train.Trainer(state, step_fn, mesh, train.TrainerConfig(progress=False, prefetch=2))
    trainer.run_epoch(take(int(cell.get("warmup_steps", 2))), epoch=0)
    calib = trainer.run_epoch(take(int(cell.get("calibration_steps", 4))), epoch=1)
    step_s = calib["elapsed_s"] * samples / calib["examples"]
    n_steps = max(int(math.floor(ctx.seconds / step_s)), 1)
    first_loss = float(seen[0]["loss"])
    seen.clear()
    if ctx.measuring:
        print(f"warm-up done: {step_s * 1e3:.1f} ms a step, window = {n_steps} steps", flush=True)

    batches = TimedBatches(take(n_steps), ctx.sample_memory)
    ctx.mark("warm-up and calibration done")
    if ctx.trace:
        ctx.prime_profiler()
        span = min(int(cell.get("trace", {}).get("steps", 3)), max(n_steps - 4, 1))
        tail = min(span + 3, n_steps - 1)
        ctx.open_window()
        summary = trainer.run_epoch(itertools.islice(batches, n_steps - tail), epoch=2)
        g0 = trainer.history[-1]["step"] + 2
        trainer.config.profile_dir = ctx.trace_dir()
        trainer.config.profile_steps = (g0, g0 + span)
        traced = trainer.run_epoch(take(tail), epoch=3)
        ctx.collect_trace()
        steps = int((summary["examples"] + traced["examples"]) // samples)
    else:
        ctx.open_window()
        summary = trainer.run_epoch(batches, epoch=2)
        steps = int(summary["examples"] // samples)

    window = {k: np.asarray(v, np.float64) for k, v in jax.device_get(
        {k: [s[k] for s in seen] for k in seen[0]}).items()}
    failed = int(np.sum(~np.isfinite(window["loss"])))
    expect = math.log(float(config["vocab_size"]))
    first_ok = abs(first_loss - expect) / expect <= float(cell["first_loss_rtol"])
    # Counters are sums over the layers, averaged over a step's microbatches.
    positions = 2 * int(step_spec["seq_len"]) * samples // micro
    overrides = system["overrides"]
    counters = {k: float(window[k].sum()) for k in COUNTERS if k in window}
    counters["moe_routed_assignments"] = float(
        len(seen) * int(config["layers"]) * positions * int(config["num_experts_per_tok"]))
    counters["moe_experts_held_per_layer"] = float(overrides["experts_held"][1])

    flops_mod = importlib.import_module(f"benchmark.flops.{system['flops']}")
    # The held experts' FLOPs at the share of the assignments that ran: the
    # layer drops nothing, so its work follows the routing (flops/sdar_moe.py).
    per_sample = flops_mod.train_flops_per_sample(
        config, step_spec,
        held_share=counters["moe_held_assignments"] / counters["moe_routed_assignments"])
    unit, per = flops_mod.units_per_sample(config, step_spec)
    rate = summary["examples"] / summary["elapsed_s"]
    chips = len(ctx.devices)
    print(f"window: {steps} steps, first loss {first_loss:.4f} (ln = {expect:.4f}), "
          f"last loss {window['loss'][-1]:.4f}; counters {counters}", flush=True)
    end_to_end = {}
    if ctx.measuring:
        print(f"window: {summary['elapsed_s']:.3f} s, {rate * per / chips:.1f} {unit}/s/chip, "
              f"input wait {batches.wait_s:.3f} s", flush=True)
        end_to_end["train_mfu"] = 100.0 * rate * per_sample / chips / ctx.peaks["bf16_flops_per_s"]
    return {
        "correct": bool(reference_ok and first_ok and failed == 0 and steps == n_steps),
        "attempted": n_steps,
        "failed": failed + (n_steps - steps),
        "end_to_end": end_to_end,
        "facts": {"window_s": summary["elapsed_s"], "steps": steps,
                  "data_wait_s": batches.wait_s, "counters": counters},
    }
