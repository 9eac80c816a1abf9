"""Training cells of a next-token model with dropless top-k experts and a
multi-part loss (``models/instella_moe``): ``kinds/train.py``'s window — the
program's own mesh, model, state, step, loader and ``Trainer.run_epoch``, the
same warm-up, calibration, N = floor(seconds / step time), two-epoch traced
window and ``train_mfu`` formula — with the step's counters collected and the
per-leaf reference check of ``kinds/train_block_diffusion.py``.

(a) The reference check.  One probe sequence at the TIMED length goes
through the program's own step (SGD of rate 1: the gradient is old params
minus new) and is held against the reference's loss, its parts (the MTP
module's cross entropy, the weighted balance term), EVERY parameter's
gradient and its own count of the assignments on held experts.  A gradient
is read twice: by its norm, leaf for leaf, as the block-diffusion kind reads
it (the worst leaf decides, the routers' under a limit of their own), and by
the norm of its DIFFERENCE from the reference's, which is what tells a lower
precision apart: the norm of a million-element gradient hardly moves under
unbiased rounding, its direction does (the control, ``reference_check.
reason``).  The difference is held to a limit on the leaves no routed
assignment reaches (attention, dense and shared MLPs, norms, embedding,
head), and read beside a looser one on the routed experts' and routers',
where every top-k choice that flips between bfloat16 and float32 scores
swaps a token's whole contribution.  The program's gradient waits on the
host while the reference runs, and the optimizer's slots are built after
the check: neither fits beside the reference's backward pass.

(b) The step's counters (device scalars, fetched after the window like the
losses) go into ``facts["counters"]`` for the per-layer readers, and the
share of the routed assignments that ran on held experts goes into the FLOPs
count, because a dropless layer's work follows the routing (the counter
behind it is the one the reference check held against the reference's
routing).

A later ``benchmark`` issue folds the three train kinds into one (PERF.md
section 7).
"""

from __future__ import annotations

import importlib
import itertools
import math
import re
import time

from ..harness import model_overrides
from .train import TimedBatches, _batch_source
from .train_block_diffusion import LIMITS, host_norms, readings, relative, told

COUNTERS = ("moe_held_assignments", "moe_load_max")
PARTS = ("mtp_loss", "moe_balance_loss")     # the step's names, in the reference's order after the CE


def reference_fn(config):
    """The reference's jitted entry: ``(params, tokens)`` → loss, its three
    parts, every parameter's gradient, held assignments."""
    import jax

    ref = importlib.import_module(f"benchmark.reference.{config['system']['reference']}")
    return jax.jit(lambda prm, t: ref.loss_and_grads(prm, t, config))


def probe_step(mesh, state, step_kw, placed):
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
            {k: float(metrics[k]) for k in PARTS})


def leaf_norms(mesh, got, want):
    """Two gradient trees (``got`` may wait on the host) → ``({leaf: |got|},
    {leaf: |want|}, {leaf: |got - want|})`` as host numbers."""
    import jax
    import jax.numpy as jnp

    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))
    with mesh:
        three = jax.jit(lambda a, b: tuple(
            jax.tree_util.tree_map(f, a, b) for f in (lambda x, y: norm(x), lambda x, y: norm(y),
                                                      lambda x, y: norm(x - y))))(got, want)
    return tuple(host_norms(t) for t in three)


def routed(leaf: str) -> bool:
    """Whether a routed assignment decides what reaches the leaf: the
    experts' stacks and the routers."""
    return "['moe']" in leaf


def direction_readings(diff: dict, want: dict) -> dict:
    """``|g - g_ref|`` of every leaf over ``|g_ref|`` — a block's leaf over
    that parameter's norm across ALL the blocks, as ``readings`` holds it —
    the worst of the leaves no routed assignment reaches and the worst of
    the others."""
    name = lambda key: re.sub(r"block_\d+", "block", key)
    stacked: dict = {}
    for k, v in want.items():
        stacked[name(k)] = stacked.get(name(k), 0.0) + v * v
    leaves = {k: relative(d, 0.0, math.sqrt(stacked[name(k)])) for k, d in diff.items()}
    worst = lambda keys: max(keys, key=leaves.get)
    dense, sparse = worst([k for k in leaves if not routed(k)]), worst([k for k in leaves if routed(k)])
    return {"grad_direction": leaves[dense], "worst_direction": dense,
            "routed_direction": leaves[sparse], "worst_routed_direction": sparse}


DIRECTIONS = {"grad_direction": "grad_direction_rtol", "routed_direction": "routed_direction_rtol"}


def part_readings(got: dict, want) -> dict:
    """Relative differences of the loss's parts; ``want`` is the reference's
    ``(CE, MTP CE, weighted balance)``."""
    return {k: relative(got[k], float(want[1 + i])) for i, k in enumerate(PARTS)}


def all_readings(got, want, norms) -> dict:
    """Every reading of one probe: ``got`` / ``want`` are ``(loss, held
    assignments, the loss's parts)`` of the program and of the reference
    (``part_readings`` says in what form), ``norms`` is ``leaf_norms``' three."""
    sys_norms, ref_norms, diff = norms
    return {**readings((got[0], sys_norms, got[1]), (want[0], ref_norms, want[1])),
            **direction_readings(diff, ref_norms), **part_readings(got[2], want[2])}


def limits(check: dict) -> dict:
    """reading → its limit, for every reading that has one."""
    return {**{k: float(check[v]) for k, v in {**LIMITS, **DIRECTIONS}.items()},
            **{k: float(check[k + "_rtol"]) for k in PARTS}}


def _reference_check(ctx, mesh, state, step_kw, probe):
    import jax

    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch

    check = ctx.cell["reference_check"]
    with mesh:
        placed = shard_batch(probe(int(check["samples_per_device"]) * len(ctx.devices)), mesh)
    loss, grads, held, got_parts = probe_step(mesh, state, step_kw, placed)
    with mesh:
        value, parts, ref_grads, ref_held = reference_fn(ctx.config)(state.params, placed["tokens"])
    norms = leaf_norms(mesh, grads, ref_grads)
    del grads, ref_grads
    read = all_readings((loss, held, got_parts), (float(value), float(ref_held), jax.device_get(parts)), norms)
    over = {k: limit for k, limit in limits(check).items() if not read[k] <= limit}
    print(f"reference check: loss system {loss:.6f} reference {float(value):.6f}, held assignments system "
          f"{held:.0f} reference {float(ref_held):.0f}, {len(norms[1])} leaves; {told(read, check)}; gradient "
          f"difference by leaf: worst outside the routed experts {read['worst_direction']} rel "
          f"{read['grad_direction']:.2e} (tol {check['grad_direction_rtol']}), worst routed "
          f"{read['worst_routed_direction']} rel {read['routed_direction']:.2e} (tol "
          f"{check['routed_direction_rtol']}); "
          + "; ".join(f"{k} rel {read[k]:.2e} (tol {check[k + '_rtol']})" for k in PARTS)
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
    reference_ok = _reference_check(ctx, mesh, state, step_kw, probe)
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
        seen.append({k: metrics[k] for k in ("loss",) + PARTS + COUNTERS})
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
    counters = {k: float(window[k].sum()) for k in COUNTERS}
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
          f"{'ok' if first_ok else 'FAILED'}), last loss {window['loss'][-1]:.4f} of which MTP cross entropy "
          f"{window['mtp_loss'][-1]:.4f} (before its weight) and balance {window['moe_balance_loss'][-1]:.3e}; "
          f"counters {counters}", flush=True)
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
