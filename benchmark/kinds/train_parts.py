"""Training cells of a next-token model with dropless top-k experts whose
loss's parts and step's counters are NAMED BY THE CELL'S FILE
(``loss_parts``: the step's names of the parts beside the next-token cross
entropy, in the reference's order, possibly none; ``counters``: the device
scalars the step returns beside its loss): ``kinds/train_moe.py``'s run —
the program's own mesh, model, state, step, loader and
``Trainer.run_epoch``, the same warm-up, calibration, N = floor(seconds /
step time), two-epoch traced window and ``train_mfu`` formula, the same
per-leaf reference check (a gradient read by its norm and by the norm of its
DIFFERENCE from the reference's) and the same ``facts["counters"]`` — with
those two tuples read from the cell, so that a model whose loss is the cross
entropy alone (``models/nemotron_h``) and one with an MTP and a balance part
run the same code.

The reference's entry returns ``(loss, parts, gradient tree, held
assignments)`` with ``parts[0]`` the next-token cross entropy and ``parts[1
+ i]`` the cell's i-th named part.

The accepted kinds may not be edited by the PR that brought this one; the
``benchmark`` issue that folds the train kinds (PERF.md section 7) folds
them into this file.
"""

from __future__ import annotations

import importlib
import itertools
import math
import time

from ..harness import model_overrides
from .train import TimedBatches, _batch_source
from .train_block_diffusion import LIMITS, readings, relative, told
from .train_moe import DIRECTIONS, direction_readings, leaf_norms, reference_fn


def probe_step(mesh, state, step_kw, placed, parts):
    """``placed`` through the program's own step from ``state`` (not
    consumed): ``(loss, gradient tree ON THE HOST, held assignments, {part:
    value})``."""
    import jax
    import jax.numpy as jnp
    import optax

    from pytorch_distributed_training_tpu import train

    sgd = optax.sgd(1.0)
    params, at = jax.tree_util.tree_map(jnp.copy, (state.params, state.step))   # the step donates its state
    probe_state = state.replace(step=at, params=params, opt_state=sgd.init(params), tx=sgd)
    step = train.make_train_step(num_microbatches=1, **step_kw)
    with mesh:
        new_state, metrics = step(probe_state, placed)
        grads = jax.device_get(jax.jit(lambda old, new: jax.tree_util.tree_map(
            lambda a, b: a - b, old, new))(state.params, new_state.params))
    return (float(metrics["loss"]), grads, float(metrics["moe_held_assignments"]),
            {k: float(metrics[k]) for k in parts})


def all_readings(got, want, norms, parts) -> dict:
    """Every reading of one probe: ``got`` / ``want`` are ``(loss, held
    assignments, the loss's parts)`` of the program (a dict by name) and of
    the reference (its vector), ``norms`` is ``leaf_norms``' three."""
    sys_norms, ref_norms, diff = norms
    return {**readings((got[0], sys_norms, got[1]), (want[0], ref_norms, want[1])),
            **direction_readings(diff, ref_norms),
            **{k: relative(got[2][k], float(want[2][1 + i])) for i, k in enumerate(parts)}}


def limits(check: dict, parts) -> dict:
    """reading → its limit, for every reading that has one."""
    return {**{k: float(check[v]) for k, v in {**LIMITS, **DIRECTIONS}.items()},
            **{k: float(check[k + "_rtol"]) for k in parts}}


def _reference_check(ctx, mesh, state, step_kw, probe, parts):
    import jax

    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch

    check = ctx.cell["reference_check"]
    with mesh:
        placed = shard_batch(probe(int(check["samples_per_device"]) * len(ctx.devices)), mesh)
    loss, grads, held, got_parts = probe_step(mesh, state, step_kw, placed, parts)
    with mesh:
        value, ref_parts, ref_grads, ref_held = reference_fn(ctx.config)(state.params, placed["tokens"])
    norms = leaf_norms(mesh, grads, ref_grads)
    del grads, ref_grads
    read = all_readings((loss, held, got_parts), (float(value), float(ref_held), jax.device_get(ref_parts)),
                        norms, parts)
    over = {k: limit for k, limit in limits(check, parts).items() if not read[k] <= limit}
    print(f"reference check: loss system {loss:.6f} reference {float(value):.6f}, held assignments system "
          f"{held:.0f} reference {float(ref_held):.0f}, {len(norms[1])} leaves; {told(read, check)}; gradient "
          f"difference by leaf: worst outside the routed experts {read['worst_direction']} rel "
          f"{read['grad_direction']:.2e} (tol {check['grad_direction_rtol']}), worst routed "
          f"{read['worst_routed_direction']} rel {read['routed_direction']:.2e} (tol "
          f"{check['routed_direction_rtol']})"
          + "".join(f"; {k} rel {read[k]:.2e} (tol {check[k + '_rtol']})" for k in parts)
          + f" -> {'ok' if not over else 'FAILED: ' + ', '.join(sorted(over))}", flush=True)
    return not over


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
    parts, counted = tuple(cell["loss_parts"]), tuple(cell["counters"])

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
    step_kw = dict(kind="lm", policy=policy)
    reference_ok = _reference_check(ctx, mesh, state, step_kw, probe, parts)
    ctx.mark("reference check done")

    opt = system["optimizer"]
    tx = getattr(optax, opt["name"])(float(opt["learning_rate"]))
    with mesh:
        slots = jax.jit(tx.init, out_shardings=None)(state.params)
    state = state.replace(tx=tx, opt_state=slots)
    jitted = train.make_train_step(num_microbatches=micro, **step_kw)
    seen: list = []              # every step's loss, its parts and counters, as device scalars
    dispatched: list = []        # host clock at every dispatch's return

    def step_fn(s, batch):
        s, metrics = jitted(s, batch)
        seen.append({k: metrics[k] for k in ("loss",) + parts + counted})
        dispatched.append(time.perf_counter())
        return s, metrics

    trainer = train.Trainer(state, step_fn, mesh, train.TrainerConfig(progress=False, prefetch=2))
    trainer.run_epoch(take(int(cell.get("warmup_steps", 2))), epoch=0)
    calib = trainer.run_epoch(take(int(cell.get("calibration_steps", 4))), epoch=1)
    step_s = calib["elapsed_s"] * samples / calib["examples"]
    n_steps = max(int(math.floor(ctx.seconds / step_s)), 1)
    first_loss = float(seen[0]["loss"])
    seen.clear()
    dispatched.clear()
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
    first = cell["first_loss"]
    first_ok = abs(first_loss - float(first["expected"])) / float(first["expected"]) <= float(first["rtol"])
    # Counters are a step's totals over its expert layers and microbatches.
    flops_mod = importlib.import_module(f"benchmark.flops.{system['flops']}")
    seq_len, sequences = int(step_spec["seq_len"]), len(seen) * samples
    counters = {k: float(window[k].sum()) for k in counted}
    counters["moe_routed_assignments"] = float(
        sequences * flops_mod.expert_blocks(config) * seq_len * int(config["num_experts_per_tok"]))
    counters["moe_experts_held_per_layer"] = float(system["overrides"]["experts_held"][1])
    # The held experts' FLOPs at the share of the assignments that ran: the
    # layer drops nothing, so its work follows the routing.
    per_sample = flops_mod.train_flops_per_sample(
        config, step_spec,
        held_share=counters["moe_held_assignments"] / counters["moe_routed_assignments"])
    unit, per = flops_mod.units_per_sample(config, step_spec)
    rate = summary["examples"] / summary["elapsed_s"]
    chips = len(ctx.devices)
    print(f"window: {steps} steps, first loss {first_loss:.4f} (expected {first['expected']}, "
          f"{'ok' if first_ok else 'FAILED'}), last loss {window['loss'][-1]:.4f}"
          + "".join(f", {k} {window[k][-1]:.4e}" for k in parts) + f"; counters {counters}", flush=True)
    end_to_end = {}
    if ctx.measuring:
        print(f"window: {summary['elapsed_s']:.3f} s, {rate * per / chips:.1f} {unit}/s/chip, "
              f"input wait {batches.wait_s:.3f} s; ms between dispatches "
              f"{[round(1e3 * (b - a)) for a, b in zip(dispatched, dispatched[1:])]}", flush=True)
        end_to_end["train_mfu"] = 100.0 * rate * per_sample / chips / ctx.peaks["bf16_flops_per_s"]
    return {
        "correct": bool(reference_ok and first_ok and failed == 0 and steps == n_steps),
        "attempted": n_steps,
        "failed": failed + (n_steps - steps),
        "end_to_end": end_to_end,
        "facts": {"window_s": summary["elapsed_s"], "steps": steps, "microbatches": micro,
                  "data_wait_s": batches.wait_s, "counters": counters},
    }
