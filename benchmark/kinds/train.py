"""Training cells: the program's own mesh, model, state, step, loader and
``Trainer.run_epoch`` — the layers ``cli/main.py::run`` calls — timed over a
fixed number of optimizer steps.

The window is a step count, not a deadline: ``run_epoch`` dispatches ahead of
the device and syncs only at its closing loss fetch, so the harness times a
few synced steps first, takes N = floor(seconds / step time) and runs one
epoch of exactly N batches.  Throughput is that epoch's own
``examples / elapsed_s``.
"""

from __future__ import annotations

import importlib
import itertools
import math
import time

from ..harness import model_overrides


class TimedBatches:
    """Wraps the batch iterator the trainer pulls from and times each pull:
    the host time ``run_epoch`` waits on input.  Each pull comes while the
    step dispatched before it runs, so it is also where the chip's memory
    footprint is read (``before_pull``)."""

    def __init__(self, batches, before_pull):
        self._it = iter(batches)
        self._before_pull = before_pull
        self.wait_s = 0.0

    def __iter__(self):
        return self

    def __next__(self):
        self._before_pull()
        t = time.perf_counter()
        try:
            return next(self._it)
        finally:
            self.wait_s += time.perf_counter() - t


def _batch_source(ctx, mesh, samples):
    """``(next_batches(n), normalize, probe_batch(k))`` for the cell's input."""
    import numpy as np

    from pytorch_distributed_training_tpu import data

    spec, step = ctx.cell["input"], ctx.cell["step"]
    if spec["source"] == "synthetic_tokens":
        ds = data.SyntheticTokens(
            n=int(spec["records"]), seq_len=int(step["seq_len"]),
            vocab_size=int(ctx.config["vocab_size"]), seed=ctx.seed32,
        )
        loader = data.DataLoader(ds, data.DataLoaderConfig(
            batch_size=samples, shuffle=True, seed=ctx.seed32,
            num_workers=int(spec.get("num_workers", 0)),
        ))
        stream = iter(loader)

        def take(n):
            return itertools.islice(stream, n)

        def probe(k):
            return {"tokens": np.stack([ds[len(ds) - 1 - i]["tokens"] for i in range(k)])}

        return take, None, probe
    if spec["source"] == "device_cached_images":
        # Records made in bulk from the seed (uint8, as a packed corpus
        # holds them) and uploaded once; batches are assembled on the device.
        rng = np.random.default_rng(ctx.seed)
        n, side = int(spec["records"]), int(step["image_size"])
        images = rng.integers(0, 256, (n, side, side, 3), dtype=np.uint8)
        labels = rng.integers(0, int(ctx.config["num_labels"]), n).astype(np.int32)
        cache = data.DeviceCachedImages(
            (images, labels), mesh=mesh, crop_size=side, train=True, seed=ctx.seed32,
        )
        stream = itertools.chain.from_iterable(
            cache.batches(epoch, samples) for epoch in itertools.count()
        )

        def take(n):
            return itertools.islice(stream, n)

        def probe(k):
            return {"image": images[:k], "label": labels[:k]}

        return take, (cache.mean, cache.std), probe
    raise ValueError(f"unknown input source {spec['source']!r}")


def _reference_check(ctx, mesh, net, state, step_kw, normalize, probe, task):
    """The system's loss and global gradient norm on a seeded probe batch,
    taken through the program's own train step (one SGD step of rate 1, so
    the gradient is old params minus new), against the plain reference in
    float32.  Outside the window."""
    import jax
    import jax.numpy as jnp
    import optax

    from pytorch_distributed_training_tpu import train
    from pytorch_distributed_training_tpu.parallel.sharding import shard_batch

    from ..reference import global_norm

    check = ctx.cell["reference_check"]
    k = int(check["samples_per_device"]) * len(ctx.devices)
    batch = probe(k)
    sgd = optax.sgd(1.0)
    params = jax.tree_util.tree_map(jnp.copy, state.params)   # the step donates its state
    probe_state = train.TrainState(
        step=jnp.zeros((), jnp.int32), params=params, opt_state=sgd.init(params),
        batch_stats=state.batch_stats, apply_fn=net.apply, tx=sgd,
    )
    step = train.make_train_step(num_microbatches=1, **step_kw)
    with mesh:
        placed = shard_batch(batch, mesh)
        new_state, metrics = step(probe_state, placed)
        grads = jax.tree_util.tree_map(lambda a, b: a - b, state.params, new_state.params)
        sys_loss, sys_norm = float(metrics["loss"]), float(global_norm(grads))
        ref = importlib.import_module(f"benchmark.reference.{ctx.config['system']['reference']}")
        static = (ctx.config,) + ((normalize,) if task == "image_classifier" else ())
        ref_loss, ref_norm = (float(x) for x in jax.jit(
            lambda p, b: ref.loss_and_grad_norm(p, b, *static)
        )(state.params, placed))
    loss_err = abs(sys_loss - ref_loss) / abs(ref_loss)
    norm_err = abs(sys_norm - ref_norm) / abs(ref_norm)
    ok = loss_err <= float(check["loss_rtol"]) and norm_err <= float(check["grad_norm_rtol"])
    print(f"reference check: loss system {sys_loss:.6f} reference {ref_loss:.6f} "
          f"(rel {loss_err:.2e}, tol {check['loss_rtol']}); grad norm system {sys_norm:.6f} "
          f"reference {ref_norm:.6f} (rel {norm_err:.2e}, tol {check['grad_norm_rtol']}) "
          f"-> {'ok' if ok else 'FAILED'}", flush=True)
    return ok


def run(ctx) -> dict:
    import jax
    import optax

    from pytorch_distributed_training_tpu import comm, models, train

    ctx.mark("imports done, backend up")
    cell, config = ctx.cell, ctx.config
    system, step_spec = config["system"], cell["step"]
    task = system["task"]
    samples, micro = int(step_spec["samples"]), int(step_spec["microbatches"])

    mesh = comm.make_mesh(comm.MeshConfig(**cell.get("mesh", {})), devices=ctx.devices)
    policy = train.make_policy(system["precision"]["train"])
    model_kw = {"cfg_overrides": model_overrides(config)}
    if task == "image_classifier":
        model_kw["num_classes"] = int(config["num_labels"])
    net = models.create_model(system["registry"], dtype=policy.compute_dtype, **model_kw)

    opt = system["optimizer"]
    tx = getattr(optax, opt["name"])(float(opt["learning_rate"]))
    if task == "lm":
        from pytorch_distributed_training_tpu.comm.mesh import batch_shard_size

        sample = jax.numpy.zeros((batch_shard_size(mesh), int(step_spec["seq_len"])), jax.numpy.int32)
    else:
        side = int(step_spec["image_size"])
        sample = jax.numpy.zeros((1, side, side, 3), policy.compute_dtype)
    # ``create_train_state`` closes over its key, so every new seed would
    # compile an init program of its own (16 s of each run's set-up, my chip
    # runs, PR 23).  The state is built from a fixed key, whose program the
    # cache returns, and the seed's weights are drawn by the model's own init
    # with the key as an argument; AdamW's slots start at zero either way.
    state = train.create_train_state(
        net, jax.random.PRNGKey(0), sample, tx, mesh=mesh, init_kwargs={"train": False},
    )
    seeded = jax.jit(
        lambda key: net.init(key, sample, train=False)["params"],
        out_shardings=jax.tree_util.tree_map(lambda x: x.sharding, state.params),
    )
    with mesh:
        state = state.replace(params=seeded(jax.random.PRNGKey(ctx.seed32)))
    ctx.mark("model and state built")
    take, normalize, probe = _batch_source(ctx, mesh, samples)
    ctx.mark("input ready")
    step_kw = dict(kind=task, policy=policy, input_normalize=normalize,
                   base_rng=jax.random.PRNGKey((ctx.seed32 + 1) % 2147483629))
    reference_ok = _reference_check(ctx, mesh, net, state, step_kw, normalize, probe, task)

    ctx.mark("reference check done")
    jitted = train.make_train_step(num_microbatches=micro, **step_kw)
    losses: list = []            # every step's loss, as device scalars: fetched after the window

    def step_fn(s, batch):
        s, metrics = jitted(s, batch)
        losses.append(metrics["loss"])
        return s, metrics

    prefetch = 0 if cell["input"]["source"].startswith("device_cached") else 2
    trainer = train.Trainer(
        state, step_fn, mesh, train.TrainerConfig(progress=False, prefetch=prefetch),
    )

    # Warm-up: the first epoch compiles (or loads) the step; the second is
    # closed by a loss fetch and gives the step time N is taken from.
    trainer.run_epoch(take(int(cell.get("warmup_steps", 2))), epoch=0)
    calib = trainer.run_epoch(take(int(cell.get("calibration_steps", 4))), epoch=1)
    step_s = calib["elapsed_s"] * samples / calib["examples"]
    n_steps = max(int(math.floor(ctx.seconds / step_s)), 1)
    first_loss = float(losses[0])
    losses.clear()
    if ctx.measuring:
        print(f"warm-up done: {step_s * 1e3:.1f} ms a step, window = {n_steps} steps", flush=True)

    batches = TimedBatches(take(n_steps), ctx.sample_memory)
    ctx.mark("warm-up and calibration done")
    if ctx.trace:
        # The window in two epochs: the first, untraced, gives the host-clock
        # facts; the second holds the capture, through the trainer's own
        # step-window bracket, over steps that start once its queue is full
        # again and stop on a loss fetch.
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

    import numpy as np

    window_losses = np.asarray(jax.device_get(losses), np.float64)
    failed = int(np.sum(~np.isfinite(window_losses)))
    expect = math.log(float(config["vocab_size"] if task == "lm" else config["num_labels"]))
    first_ok = abs(first_loss - expect) / expect <= float(cell["first_loss_rtol"])

    flops_mod = importlib.import_module(f"benchmark.flops.{system['flops']}")
    per_sample = flops_mod.train_flops_per_sample(config, step_spec)
    unit, per = flops_mod.units_per_sample(config, step_spec)
    rate = summary["examples"] / summary["elapsed_s"]
    chips = len(ctx.devices)
    print(f"window: {steps} steps, first loss {first_loss:.4f} (ln = {expect:.4f}), "
          f"last loss {window_losses[-1]:.4f}", flush=True)
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
        "facts": {
            "window_s": summary["elapsed_s"], "steps": steps,
            # a device-resident source's pull blocks on the device's own queue,
            # which is not input starvation: no input wait is reported for it
            **({"data_wait_s": batches.wait_s} if prefetch else {}),
        },
    }
