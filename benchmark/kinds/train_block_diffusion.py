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
(``fold_in(fold_in(base_rng, step), microbatch 0)``) and held against what
the configuration assumes of them, and the reference's loss, the norm of
EVERY parameter's gradient (the worst leaf decides, the routers' under a
limit of their own) and its own count of the assignments on held experts
are compared with the step's.  The optimizer's
slots are built after the check: both do not fit beside the reference's
gradients.

(b) The step's counters (device scalars, fetched after the window like the
losses) go into ``facts["counters"]`` for the per-layer readers, and two of
them into the verdict and the FLOPs: the window's ``masked_tokens`` has to
be what the noise schedule gives over every sequence of every step (half a
batch left out of the timed program halves it), and the share of the routed
assignments that ran on held experts goes into the FLOPs count, because a
dropless layer's work follows the routing (the counter behind it is the one
the reference check held against the reference's routing).

A later ``benchmark`` issue folds the two kinds into one (PERF.md section 7).
"""

from __future__ import annotations

import importlib
import itertools
import math
import re
import time

from ..harness import model_overrides
from .train import TimedBatches, _batch_source

COUNTERS = ("moe_held_assignments", "moe_load_max", "masked_tokens")


def reference_fn(config):
    """The reference's jitted entry: ``(params, tokens, masked, p)`` → loss,
    per-leaf gradient norms, held assignments."""
    import jax

    ref = importlib.import_module(f"benchmark.reference.{config['system']['reference']}")
    return jax.jit(lambda prm, t, m, q: ref.loss_and_grad_norms(prm, t, m, q, config))


def relative(a: float, b: float, floor: float = 0.0) -> float:
    """|a - b| over the larger of |b| and ``floor``; two zeros agree."""
    scale = max(abs(b), floor)
    return abs(a - b) / scale if scale > 0.0 else (0.0 if a == b else math.inf)


def readings(got, want) -> dict:
    """Relative differences of ``(loss, {leaf: gradient norm}, held
    assignments)`` (host numbers) between two computations of one probe.

    A block's leaf is held to the norm of that parameter's gradient over ALL
    the blocks (what the leaf would be were the blocks stacked): a fresh
    model's routing leaves some block's held experts a handful of tokens, or
    none (a gradient of exactly 0), and a few assignments that flip between
    bfloat16 and float32 scores are then that whole leaf.  The routers'
    leaves are read apart from the rest: their gradient passes through the
    top-k itself, so every flip is in it."""
    name = lambda key: re.sub(r"block_\d+", "block", key)
    stacked: dict = {}
    for k, v in want[1].items():
        stacked[name(k)] = stacked.get(name(k), 0.0) + v * v
    leaves = {k: relative(got[1][k], v, math.sqrt(stacked[name(k)])) for k, v in want[1].items()}
    worst = lambda keys: max(keys, key=leaves.get)
    router, rest = worst([k for k in leaves if "router" in k]), worst([k for k in leaves if "router" not in k])
    whole = lambda norms: math.sqrt(sum(v * v for v in norms.values()))
    return {"loss": relative(got[0], want[0]), "grad_leaf": leaves[rest], "worst_leaf": rest,
            "router_grad": leaves[router], "worst_router": router,
            "grad_norm": relative(whole(got[1]), whole(want[1])), "held_assignments": relative(got[2], want[2])}


LIMITS = {"loss": "loss_rtol", "grad_leaf": "grad_leaf_rtol", "router_grad": "router_grad_rtol",
          "held_assignments": "held_assignments_rtol"}


def within(read: dict, check: dict) -> bool:
    return all(read[k] <= float(check[limit]) for k, limit in LIMITS.items())


def told(read: dict, check: dict) -> str:
    """One line of every reading beside its limit."""
    return (f"loss rel {read['loss']:.2e} (tol {check['loss_rtol']}); gradient norm by leaf: worst of the rest "
            f"{read['worst_leaf']} rel {read['grad_leaf']:.2e} (tol {check['grad_leaf_rtol']}), worst router "
            f"{read['worst_router']} rel {read['router_grad']:.2e} (tol {check['router_grad_rtol']}), whole tree "
            f"rel {read['grad_norm']:.2e}; held assignments rel {read['held_assignments']:.2e} "
            f"(tol {check['held_assignments_rtol']})")


def host_norms(tree) -> dict:
    import jax

    return {jax.tree_util.keystr(path): float(x)
            for path, x in jax.tree_util.tree_leaves_with_path(jax.device_get(tree))}


def _reference_check(ctx, mesh, net, state, step_kw, probe):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_training_tpu import train
    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch
    from pytorch_distributed_training_tpu.train import block_diffusion

    check = ctx.cell["reference_check"]
    batch = probe(int(check["samples_per_device"]) * len(ctx.devices))
    sgd = optax.sgd(1.0)
    params, at = jax.tree_util.tree_map(jnp.copy, (state.params, state.step))   # the step donates its state
    probe_state = state.replace(step=at, params=params, opt_state=sgd.init(params), tx=sgd)
    step = train.make_train_step(num_microbatches=1, **step_kw)
    key = jax.random.fold_in(jax.random.fold_in(step_kw["base_rng"], state.step), 0)
    ref = importlib.import_module(f"benchmark.reference.{ctx.config['system']['reference']}")
    with mesh:
        placed = shard_batch(batch, mesh)
        new_state, metrics = step(probe_state, placed)
        sys_norms = host_norms(jax.jit(lambda old, new: jax.tree_util.tree_map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), old, new))(state.params, new_state.params))
        got = (float(metrics["loss"]), sys_norms, float(metrics["moe_held_assignments"]))
        del new_state, probe_state, params, at
        _, masked, p = jax.jit(
            lambda t, k: block_diffusion.noise(t, k, net.cfg))(placed["tokens"], key)
        value, norms, held = reference_fn(ctx.config)(state.params, placed["tokens"], masked, p)
        want = (float(value), host_norms(norms), float(held))
    masked, p = np.asarray(masked), np.asarray(p)
    noise_ok = ref.noise_is_the_assumed(masked, p)
    read = readings(got, want)
    ok = within(read, check) and noise_ok
    print(f"reference check: {int(masked.sum())} masked of {masked.size} at p {p.round(4).tolist()} "
          f"({'as' if noise_ok else 'NOT as'} assumed); loss system {got[0]:.6f} reference {want[0]:.6f}, "
          f"held assignments system {got[2]:.0f} reference {want[2]:.0f}, {len(want[1])} leaves; "
          f"{told(read, check)} -> {'ok' if ok else 'FAILED'}", flush=True)
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
    # The step's key is a constant of the compiled step, so it is one key
    # for every seed (one cached program each for the probe and the timed
    # step); the seed's noise comes from the step counter the key is folded
    # with, which is an argument: training starts at a step the seed gives.
    with mesh:
        state = state.replace(
            params=seeded(jax.random.PRNGKey(ctx.seed32)),
            step=jax.device_put(jax.numpy.asarray(ctx.seed32 // 2, jax.numpy.int32), state.step.sharding))
    ctx.mark("model and params built")
    take, _, probe = _batch_source(ctx, mesh, samples)
    ctx.mark("input ready")
    step_kw = dict(kind="lm", policy=policy, base_rng=jax.random.PRNGKey(0))
    reference_ok = _reference_check(ctx, mesh, net, state, step_kw, probe)
    ctx.mark("reference check done")

    opt = system["optimizer"]
    tx = getattr(optax, opt["name"])(float(opt["learning_rate"]))
    with mesh:
        slots = jax.jit(tx.init, out_shardings=None)(state.params)
    state = state.replace(tx=tx, opt_state=slots)
    jitted = train.make_train_step(num_microbatches=micro, **step_kw)
    seen: list = []              # every step's loss and counters, as device scalars
    dispatched: list = []        # host clock at every dispatch's return

    def step_fn(s, batch):
        s, metrics = jitted(s, batch)
        seen.append({k: metrics[k] for k in ("loss",) + COUNTERS if k in metrics})
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
    # Counters are a step's totals over its layers and microbatches.
    seq_len, sequences = int(step_spec["seq_len"]), len(seen) * samples
    overrides = system["overrides"]
    counters = {k: float(window[k].sum()) for k in COUNTERS if k in window}
    counters["moe_routed_assignments"] = float(
        sequences * int(config["layers"]) * 2 * seq_len * int(config["num_experts_per_tok"]))
    counters["moe_experts_held_per_layer"] = float(overrides["experts_held"][1])
    # t ~ U(0, 1) a sequence: its masked count has mean L (1 + eps) / 2 and
    # standard deviation L / sqrt(12) (the binomial's own part is small beside).
    noise = cell["masked_tokens"]
    masked_want = sequences * seq_len * (1.0 + float(noise["eps"])) / 2.0
    masked_room = float(noise["sigmas"]) * seq_len * math.sqrt(sequences / 12.0)
    masked_ok = abs(counters["masked_tokens"] - masked_want) <= masked_room

    flops_mod = importlib.import_module(f"benchmark.flops.{system['flops']}")
    # The held experts' FLOPs at the share of the assignments that ran: the
    # layer drops nothing, so its work follows the routing (flops/sdar_moe.py).
    per_sample = flops_mod.train_flops_per_sample(
        config, step_spec,
        held_share=counters["moe_held_assignments"] / counters["moe_routed_assignments"])
    unit, per = flops_mod.units_per_sample(config, step_spec)
    rate = summary["examples"] / summary["elapsed_s"]
    chips = len(ctx.devices)
    print(f"window: {steps} steps, first loss {first_loss:.4f} (expected {first['expected']}, "
          f"{'ok' if first_ok else 'FAILED'}), last loss {window['loss'][-1]:.4f}; masked tokens "
          f"{counters['masked_tokens']:.0f} of {masked_want:.0f} +- {masked_room:.0f} expected over "
          f"{sequences} sequences ({'ok' if masked_ok else 'FAILED'}); counters {counters}", flush=True)
    end_to_end = {}
    if ctx.measuring:
        print(f"window: {summary['elapsed_s']:.3f} s, {rate * per / chips:.1f} {unit}/s/chip, "
              f"input wait {batches.wait_s:.3f} s; ms between dispatches "
              f"{[round(1e3 * (b - a)) for a, b in zip(dispatched, dispatched[1:])]}", flush=True)
        end_to_end["train_mfu"] = 100.0 * rate * per_sample / chips / ctx.peaks["bf16_flops_per_s"]
    return {
        "correct": bool(reference_ok and first_ok and masked_ok and failed == 0 and steps == n_steps),
        "attempted": n_steps,
        "failed": failed + (n_steps - steps),
        "end_to_end": end_to_end,
        "facts": {"window_s": summary["elapsed_s"], "steps": steps, "microbatches": micro,
                  "data_wait_s": batches.wait_s, "counters": counters},
    }
