"""Serving cells: the program's ``ServingEngine`` (paged) under its
``ContinuousScheduler``, fed an open loop on the real clock — every latency
is counted from the time the request was due, not from when it was sent.

``mode: steady`` offers the cell's fixed rate (below the knee) for the window
and lets ``scheduler.run`` finish every request; the tails are judged.
``mode: saturated`` offers a rate above the knee, opens the window once a
ramp has filled the slots, drives the same public ``submit`` / ``tick`` /
``idle`` as ``run`` does and stops at the deadline; the tokens emitted inside
the window are judged, and requests still queued or decoding at the end are
neither failures nor latency samples.
"""

from __future__ import annotations

import importlib
import time

from .. import traffic as traffic_lib
from ..harness import model_overrides
from ..stats import median, percentile


class DrainLimit(Exception):
    """The steady cell's requests did not finish within the drain limit."""


def observed_scheduler(engine, **kw):
    """The program's scheduler with three observation hooks and no other
    change: how late each request was submitted, a callback before each tick
    (the traced window's start and stop, the drain limit), and — only while
    ``emitted`` is a dict — the tokens each request emitted."""
    from pytorch_distributed_training_tpu.serve import ContinuousScheduler

    class ObservedScheduler(ContinuousScheduler):
        submit_lag: list
        before_tick = None
        emitted = None

        def submit(self, request, **kwargs):
            self.submit_lag.append(self.clock() - request.arrival_time)
            return super().submit(request, **kwargs)

        def tick(self):
            if self.before_tick is not None:
                self.before_tick()
            events = super().tick()
            if self.emitted is not None:
                for ev in events:
                    if ev.kind == "token":
                        self.emitted.setdefault(ev.request_id, []).append(ev.token)
            return events

    sched = ObservedScheduler(engine, **kw)
    sched.submit_lag = []
    return sched


def build(ctx):
    """Model, bf16 params made on the device from the seed in one jitted
    call, and the engine with its two compiled programs."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tpu import models, train
    from pytorch_distributed_training_tpu.serve import ServingEngine

    config, spec = ctx.config, ctx.cell["engine"]
    policy = train.make_policy(config["system"]["precision"]["serve"])
    net = models.create_model(
        config["system"]["registry"], dtype=policy.compute_dtype,
        cfg_overrides=model_overrides(config),
    )

    @jax.jit
    def init(key):
        params = net.init(key, jnp.zeros((1, 8), jnp.int32), train=False)["params"]
        return jax.tree_util.tree_map(lambda x: x.astype(policy.compute_dtype), params)

    params = init(jax.random.PRNGKey(ctx.seed32))
    engine = ServingEngine(
        net, params, num_slots=int(spec["num_slots"]), max_len=int(spec["max_len"]),
        prefill_chunk=int(spec["prefill_chunk"]), temperature=0.0, seed=ctx.seed32,
        paged=bool(spec["paged"]), block_size=int(spec["block_size"]),
        num_blocks=spec.get("num_blocks"), kv_dtype=spec["kv_dtype"],
    )
    return net, params, engine


def warm_and_check(ctx, params, engine) -> bool:
    """Run the cell's warm-up requests (long and short, so both programs and
    every chunk position run once) to completion, then hold every token the
    engine emitted against one teacher-forced forward of the plain reference
    over prompt + generated tokens: the reference's logit of the emitted token
    must lie within epsilon of the reference's top logit at that position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tpu.serve import Request

    warm, check = ctx.cell["warmup"], ctx.cell["reference_check"]
    rng = np.random.default_rng(ctx.seed)
    vocab = int(ctx.config["vocab_size"])
    prompts = [rng.integers(0, vocab, int(n), dtype=np.int32) for n in warm["prompt_len"]]
    budgets = [int(b) for b in warm["output_len"]]
    sched = observed_scheduler(engine, max_queue=len(prompts))
    sched.emitted = {}
    now = sched.clock()
    done = sched.run([Request(i, p, b, now) for i, (p, b) in enumerate(zip(prompts, budgets))])
    budgets_ok = len(done) == len(prompts) and all(
        r["generated"] == budgets[r["id"]] and len(sched.emitted[r["id"]]) == budgets[r["id"]]
        for r in done
    )

    ref = importlib.import_module(f"benchmark.reference.{ctx.config['system']['reference']}")
    pad_to = int(ctx.config["n_positions"])
    deficit = jax.jit(lambda p, t: ref.greedy_deficit(p, t, ctx.config))
    worst = 0.0
    for i, prompt in enumerate(prompts):
        seq = np.concatenate([prompt, np.asarray(sched.emitted.get(i, []), np.int32)])
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, : seq.size] = seq
        d = np.asarray(deficit(params, jnp.asarray(padded)))
        # position p-1 predicts token p: the emitted tokens sit at prompt.size..seq.size-1
        worst = max(worst, float(d[prompt.size - 1: seq.size - 1].max()))
    ok = budgets_ok and worst <= float(check["epsilon"])
    print(f"reference check: {len(prompts)} warm-up requests, {sum(budgets)} emitted tokens, "
          f"largest gap under the reference's top logit {worst:.4f} (epsilon {check['epsilon']}), "
          f"budgets {'met' if budgets_ok else 'NOT met'} -> {'ok' if ok else 'FAILED'}", flush=True)
    return ok


def make_requests(ctx, traffic, seconds, clock):
    """The mix's requests for ``seconds``, due from a t0 just ahead of now."""
    from pytorch_distributed_training_tpu.serve import Request

    gen = traffic_lib.generate(
        traffic, seed=ctx.seed, seconds=seconds, vocab_size=int(ctx.config["vocab_size"]),
    )
    t0 = clock() + 0.05
    return t0, [Request(i, gen.prompts[i], int(gen.budgets[i]), float(t0 + gen.due_s[i]))
                for i in range(len(gen))]


def tracer(ctx, sched, start_at, stop_at):
    """A ``before_tick`` hook that captures ``[start_at, stop_at)`` on the
    scheduler's clock; returns ``finish()`` to close a capture left open."""
    state = {"cm": None, "done": False}

    def hook():
        now = sched.clock()
        if state["cm"] is None and not state["done"] and now >= start_at:
            state["cm"] = ctx.capture()
            state["cm"].__enter__()
        elif state["cm"] is not None and now >= stop_at:
            finish()

    def finish():
        if state["cm"] is not None:
            cm, state["cm"], state["done"] = state["cm"], None, True
            cm.__exit__(None, None, None)

    return hook, finish


def chain(*hooks):
    hooks = [h for h in hooks if h is not None]
    return (lambda: [h() for h in hooks]) if hooks else None


def latency_samples(records, worst_s):
    """TTFT and TPOT of every record; a request without a first token counts
    as the worst for TTFT."""
    ttft = [(r["first_token"] - r["arrival"]) if r["first_token"] is not None else worst_s
            for r in records]
    tpot = [(r["finish"] - r["first_token"]) / (r["generated"] - 1) for r in records
            if r["finish"] is not None and r["first_token"] is not None and r["generated"] > 1]
    return ttft, tpot


def run_steady(ctx, engine, traffic, seconds, *, trace=False):
    """Offer ``traffic`` for ``seconds`` and let every request finish (or hit
    the drain limit).  Returns the records in arrival order, how late each
    submit was, the scheduler and the clock time the window opened."""
    drain_limit = float(ctx.cell.get("drain_limit_s", 30.0))
    sched = observed_scheduler(engine, max_queue=traffic_lib.n_requests(traffic, seconds) + 1)
    t0, requests = make_requests(ctx, traffic, seconds, sched.clock)
    deadline = t0 + seconds + drain_limit

    def limit():
        if sched.clock() > deadline:
            raise DrainLimit

    hook = finish = None
    if trace:
        span = float(ctx.cell["trace"]["seconds"])
        start = t0 + max((seconds - span) * 0.6, 0.0)
        hook, finish = tracer(ctx, sched, start, start + span)
    sched.before_tick = chain(limit, ctx.sample_memory, hook)
    ctx.open_window()
    try:
        sched.run(requests)
    except DrainLimit:
        print(f"drain limit: requests unfinished {drain_limit:.0f} s after the last was due", flush=True)
    finally:
        if finish is not None:
            finish()
    return [sched.records[r.id] for r in requests if r.id in sched.records], sched, t0


def run_saturated(ctx, engine, traffic, seconds, *, trace=False):
    """Offer ``traffic`` (above the knee) for a ramp and then ``seconds``;
    the window opens after the ramp and closes at the deadline, with no
    drain.  Returns records, scheduler, tokens emitted inside the window, the
    window's length and the engine's counters as the window opened."""
    ramp = float(ctx.cell["ramp_s"])
    total = ramp + seconds
    sched = observed_scheduler(engine, max_queue=traffic_lib.n_requests(traffic, total) + 1)
    t0, pending = make_requests(ctx, traffic, total, sched.clock)
    t_open, t_end = t0 + ramp, t0 + total
    hook = None
    if trace:
        span = float(ctx.cell["trace"]["seconds"])
        start = t_open + max((seconds - span) * 0.6, 0.0)
        hook, finish = tracer(ctx, sched, start, start + span)
    sched.before_tick = chain(ctx.sample_memory, hook)

    def emitted():
        return sum(r["generated"] for r in sched.records.values())

    i, opened = 0, None
    while True:
        now = sched.clock()
        if opened is None and now >= t_open:
            ctx.open_window()
            opened = (now, emitted(), engine.stats())
        if now >= t_end:
            break
        while i < len(pending) and pending[i].arrival_time <= now:
            sched.submit(pending[i])
            i += 1
        if not sched.idle:
            sched.tick()
        elif i < len(pending):
            time.sleep(max(min(pending[i].arrival_time, t_end) - now, 0.0))
    closed = (sched.clock(), emitted())
    if trace:
        finish()
    records = [sched.records[r.id] for r in pending[:i]]
    return records, sched, closed[1] - opened[1], closed[0] - opened[0], opened[2]


def run(ctx) -> dict:
    cell, mode = ctx.cell, ctx.cell["mode"]
    net, params, engine = build(ctx)
    programs = engine.mosaic_custom_calls
    print("serving programs: mosaic_custom_calls " +
          " ".join(f"{k}={v}" for k, v in programs.items()), flush=True)
    kernels_ok = all(v >= 1 for v in programs.values()) if ctx.measuring else True
    reference_ok = warm_and_check(ctx, params, engine)
    engine.reset()
    if ctx.trace:
        ctx.prime_profiler()

    chips = len(ctx.devices)
    end_to_end, facts = {}, {"num_slots": int(cell["engine"]["num_slots"])}
    at_open = {}             # the engine's counters as the window opened (after a ramp)
    if mode == "steady":
        records, sched, t0 = run_steady(ctx, engine, cell["traffic"], ctx.seconds, trace=ctx.trace)
        finished = [r for r in records if r["finish_reason"] == "length"]
        attempted = len(records) + sched.rejected
        failed = attempted - len(finished)
        budgets_ok = all(r["generated"] == r["max_new_tokens"] for r in finished)
        worst = ctx.seconds + float(cell.get("drain_limit_s", 30.0))
        ttft, tpot = latency_samples(records, worst)
        tokens = sum(r["generated"] for r in records)
        elapsed = max(r["finish"] for r in finished) - t0 if finished else float("nan")
        if ctx.measuring:
            end_to_end = {"serve_ttft_p95_ms": 1e3 * percentile(ttft, 95.0),
                          "serve_tpot_p95_ms": 1e3 * percentile(tpot, 95.0)}
            print(f"window: {attempted} requests due in {ctx.seconds:.1f} s, all finished after "
                  f"{elapsed:.2f} s, {tokens / elapsed / chips:.1f} tokens/s/chip completed; "
                  f"TTFT p50 {1e3 * median(ttft):.1f} p95 {end_to_end['serve_ttft_p95_ms']:.1f} ms; "
                  f"TPOT p50 {1e3 * median(tpot):.2f} p95 {end_to_end['serve_tpot_p95_ms']:.2f} ms",
                  flush=True)
    elif mode == "saturated":
        records, sched, tokens, window_s, at_open = run_saturated(
            ctx, engine, cell["traffic"], ctx.seconds, trace=ctx.trace)
        admitted = [r for r in records if r["admitted"] is not None]
        finished = [r for r in admitted if r["finish"] is not None]
        attempted = len(admitted)
        failed = sum(1 for r in finished if r["finish_reason"] != "length") + sched.rejected
        budgets_ok = all(r["generated"] == r["max_new_tokens"] for r in finished
                         if r["finish_reason"] == "length")
        if ctx.measuring:
            end_to_end = {"serve_tok_s_chip": tokens / window_s / chips}
            ttft, tpot = latency_samples(finished, float("nan"))
            print(f"window: {window_s:.2f} s after a {cell['ramp_s']} s ramp, {tokens} tokens emitted, "
                  f"{end_to_end['serve_tok_s_chip']:.1f} tokens/s/chip; {len(records)} submitted, "
                  f"{attempted} admitted, {len(finished)} finished, {len(sched.queue)} still queued; "
                  f"unjudged: TTFT p50 {1e3 * median(ttft):.0f} p95 {1e3 * percentile(ttft, 95.0):.0f} ms, "
                  f"TPOT p50 {1e3 * median(tpot):.2f} p95 {1e3 * percentile(tpot, 95.0):.2f} ms",
                  flush=True)
    else:
        raise ValueError(f"unknown serve mode {mode!r}")

    lag = sched.submit_lag
    if ctx.measuring and lag:
        print(f"generator: submit - due, median {1e3 * median(lag):.2f} ms, "
              f"max {1e3 * max(lag):.2f} ms over {len(lag)} requests", flush=True)
    stats = engine.stats()
    print(f"counts: attempted {attempted} failed {failed} decode_ticks {stats['decode_ticks']} "
          f"decode_slot_ticks {stats['decode_slot_ticks']} prefill_tokens "
          f"{stats['prefill_tokens_computed']}/{stats['prefill_tokens_offered']}", flush=True)
    ticks = {k: stats[k] - at_open.get(k, 0) for k in ("decode_ticks", "decode_slot_ticks")}
    facts.update(records=records, engine_stats=ticks)
    return {
        "correct": bool(reference_ok and kernels_ok and budgets_ok and failed == 0),
        "attempted": attempted, "failed": failed,
        "end_to_end": end_to_end, "facts": facts,
    }
