"""Serving core (serve/) on the CPU tier-1 harness.

Three contracts pinned here (ISSUE: serving engine acceptance):

1. KV-pool slot bookkeeping: allocate/release/advance invariants and the
   ragged-mask contract — stale bytes from an evicted tenant are never
   reachable, so a re-allocated slot behaves exactly like a fresh cache.
2. Scheduler behavior under a scripted arrival trace: FIFO admission into
   freed slots, bounded-queue backpressure, complete SLO records.
3. Engine greedy decode is TOKEN-EXACT against the static path
   (models/generate.py) on ragged prompts — chunked batched prefill +
   per-slot positions produce the identical greedy chain the one-token-
   per-tick scan produces.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.models import gpt2_124m
from pytorch_distributed_training_tpu.models.generate import generate
from pytorch_distributed_training_tpu.serve import (
    ContinuousScheduler, KVCachePool, Request, ServingEngine, VirtualClock,
    finalize_record, summarize_records,
)

SHRINK = dict(num_layers=2, hidden_dim=32, num_heads=2, vocab_size=61,
              max_seq_len=32)


@pytest.fixture(scope="module")
def model_and_params():
    m = gpt2_124m(cfg_overrides=SHRINK)
    params = m.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32), train=False
    )["params"]
    return m, params


@pytest.fixture(scope="module")
def engine(model_and_params):
    m, params = model_and_params
    return ServingEngine(
        m, params, num_slots=3, max_len=32, prefill_chunk=4, temperature=0.0
    )


def _requests(n=5, seed=7, lo=3, hi=9, budgets=(6, 4, 8, 5, 7)):
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, 61, (int(rng.integers(lo, hi + 1)),)).astype(np.int32)
        for _ in range(n)
    ]
    return prompts, list(budgets)[:n]


# --------------------------------------------------------------------- #
# KV pool invariants
# --------------------------------------------------------------------- #


def test_kv_pool_alloc_release_invariants(model_and_params):
    m, _ = model_and_params
    pool = KVCachePool(m.clone(decode=True), num_slots=3, max_len=16)
    assert pool.free_slots() == [0, 1, 2]
    assert pool.sentinel == 16
    a, b = pool.allocate(), pool.allocate()
    assert (a, b) == (0, 1) and pool.num_active == 2
    pool.advance(a, 5)
    assert pool.lengths[a] == 5 and pool.lengths[b] == 0
    mask = pool.valid_mask()
    assert mask[a].sum() == 5 and mask[a, :5].all() and not mask[a, 5:].any()
    assert not mask[b].any()
    pool.release(a)
    assert pool.free_slots() == [0, 2] and pool.lengths[a] == 0
    # lowest-free reuse; the new tenant starts at length 0
    assert pool.allocate() == a and pool.lengths[a] == 0
    with pytest.raises(ValueError, match="not allocated"):
        pool.release(2)
    with pytest.raises(ValueError, match="overflow"):
        pool.advance(b, 17)
    third = pool.allocate()
    assert third == 2 and pool.allocate() is None  # full pool
    with pytest.raises(ValueError, match="outside"):
        KVCachePool(m.clone(decode=True), num_slots=1, max_len=64)


def test_slot_mode_chunked_prefill_matches_full_forward(model_and_params):
    """The layers-level ragged-mask contract: per-row-position chunked
    decode over a shared cache reproduces the full causal forward for each
    row at ITS OWN offsets, with the other row parked at the sentinel."""
    m, params = model_and_params
    dec = m.clone(decode=True)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 61)
    full = m.apply({"params": params}, tokens, train=False)
    cache = dec.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 16), jnp.int32), train=False
    )["cache"]
    sentinel = 16
    # row 0 prefills 0..6 in one chunk while row 1 idles, then row 1
    # prefills 0..4 while row 0 idles — interleaved loading, one cache.
    out0, upd = dec.apply(
        {"params": params, "cache": cache}, tokens[:, :7], train=False,
        mutable=["cache"], positions=jnp.array([0, sentinel], jnp.int32),
    )
    out1, upd = dec.apply(
        {"params": params, "cache": upd["cache"]}, tokens[:, :5],
        train=False, mutable=["cache"],
        positions=jnp.array([sentinel, 0], jnp.int32),
    )
    # ragged single-token decode at each row's own next position
    nxt = jnp.stack([tokens[0, 7], tokens[1, 5]])[:, None]
    out, _ = dec.apply(
        {"params": params, "cache": upd["cache"]}, nxt, train=False,
        mutable=["cache"], positions=jnp.array([7, 5], jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(out0[0]), np.asarray(full[0, :7]), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(out1[1]), np.asarray(full[1, :5]), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(out[0, 0]), np.asarray(full[0, 7]), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(out[1, 0]), np.asarray(full[1, 5]), rtol=1e-4, atol=1e-4
    )


def test_slot_mode_rejects_non_decode(model_and_params):
    m, params = model_and_params
    with pytest.raises(ValueError, match="decode-mode"):
        m.apply(
            {"params": params}, jnp.zeros((1, 4), jnp.int32), train=False,
            positions=jnp.zeros((1,), jnp.int32),
        )


# --------------------------------------------------------------------- #
# engine vs generate(): greedy token-exactness on ragged prompts
# --------------------------------------------------------------------- #


def test_engine_greedy_matches_generate_on_ragged_prompts(
    model_and_params, engine
):
    """5 mixed-length requests through 3 slots (forcing slot reuse over
    evicted tenants' stale bytes): every streamed sequence equals the
    static scan decoder's greedy continuation of its own prompt."""
    m, params = model_and_params
    prompts, budgets = _requests()
    streamed = {i: [] for i in range(len(prompts))}
    engine.reset()
    engine.stream_cb = lambda rid, tok: streamed[rid].append(tok)
    try:
        sched = ContinuousScheduler(engine, clock=VirtualClock())
        recs = sched.run(
            [Request(i, p, b) for i, (p, b) in enumerate(zip(prompts, budgets))],
            sleep=lambda dt: None,
        )
    finally:
        engine.stream_cb = None
    assert len(recs) == len(prompts)
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        ref = generate(
            m, params, jnp.asarray(p)[None], max_new_tokens=b,
            rng=jax.random.PRNGKey(0), temperature=0.0,
        )
        np.testing.assert_array_equal(
            np.asarray(ref)[0, p.size:], np.asarray(streamed[i]), f"req {i}"
        )
    # pool fully drained: eviction released every slot
    assert engine.pool.num_active == 0
    assert not engine.pool.valid_mask().any()


def test_engine_eos_retirement(model_and_params):
    """EOS retirement: pick the token the greedy chain emits at step 3 as
    EOS — the engine must stream exactly through that token, finish with
    reason 'eos', and free the slot."""
    m, params = model_and_params
    prompt = np.asarray([5, 9, 2, 44], np.int32)
    ref = np.asarray(generate(
        m, params, jnp.asarray(prompt)[None], max_new_tokens=8,
        rng=jax.random.PRNGKey(0), temperature=0.0,
    ))[0, prompt.size:]
    eos = int(ref[2])
    cut = int(np.argmax(ref == eos)) + 1  # first occurrence, inclusive
    eng = ServingEngine(
        m, params, num_slots=1, max_len=32, prefill_chunk=4,
        temperature=0.0, eos_token_id=eos,
    )
    eng.start("r", prompt, 8)
    events = []
    while eng.busy:
        events.extend(eng.step())
    finishes = [e for e in events if e.kind == "finish"]
    toks = [e.token for e in events if e.kind == "token"]
    assert finishes[0].reason == "eos"
    np.testing.assert_array_equal(np.asarray(toks), ref[:cut])
    assert eng.pool.num_active == 0


def test_engine_budget_and_validation(model_and_params, engine):
    m, params = model_and_params
    engine.reset()
    with pytest.raises(ValueError, match="exceeds"):
        engine.start("big", np.zeros(30, np.int32), 8)
    with pytest.raises(ValueError, match="max_new"):
        engine.start("zero", np.zeros(4, np.int32), 0)
    with pytest.raises(ValueError, match="empty"):
        engine.start("empty", np.zeros(0, np.int32), 4)
    engine.start("ok", np.asarray([1, 2, 3], np.int32), 2)
    events = []
    while engine.busy:
        events.extend(engine.step())
    assert [e.kind for e in events] == ["token", "token", "finish"]
    assert events[-1].reason == "length"


# --------------------------------------------------------------------- #
# scheduler: scripted arrival trace
# --------------------------------------------------------------------- #


def test_scheduler_scripted_trace_admission_and_backpressure(
    model_and_params, engine
):
    m, params = model_and_params
    engine.reset()
    clock = VirtualClock()
    sched = ContinuousScheduler(engine, max_queue=2, clock=clock)
    prompts, budgets = _requests()
    reqs = [
        Request(i, p, b, arrival_time=0.0)
        for i, (p, b) in enumerate(zip(prompts, budgets))
    ]
    # 3 slots; queue of 2: five submissions fit only after the first tick
    # drains the queue into slots.
    assert sched.submit(reqs[0]) and sched.submit(reqs[1])
    sched.tick()  # both admitted (slots free), queue empty again
    assert sched.submit(reqs[2]) and sched.submit(reqs[3])
    assert not sched.submit(reqs[4])  # backpressure: queue full
    assert sched.rejected == 1
    # oversize requests are an error, not a silent truncation
    with pytest.raises(ValueError, match="exceeds"):
        sched.submit(Request(99, np.zeros(30, np.int32), 8))
    while not sched.idle:
        clock.advance(0.01)
        sched.tick()
    recs = sched.completed
    assert sorted(r["id"] for r in recs) == [0, 1, 2, 3]
    # FIFO: request 2 was queued before 3, so it is admitted no later
    by_id = {r["id"]: r for r in recs}
    assert by_id[2]["admitted"] <= by_id[3]["admitted"]
    for r in recs:
        assert r["generated"] == r["max_new_tokens"]  # no EOS configured
        assert r["admitted"] >= r["arrival"]
        assert r["first_token"] >= r["admitted"]
        assert r["finish"] >= r["first_token"]
        assert r["ttft"] == r["first_token"] - r["arrival"]
    assert max(sched.queue_depth_samples) >= 1
    summary = summarize_records(
        recs, elapsed=clock() or None,
        queue_depth_samples=sched.queue_depth_samples,
        rejected=sched.rejected,
    )
    assert summary["completed"] == 4 and summary["rejected"] == 1
    assert summary["generated_tokens"] == sum(
        r["generated"] for r in recs
    )


def test_serve_ttl_inflight_cancellation(model_and_params):
    """--serve-ttl's in-flight half: a request past its deadline MID-DECODE
    is retired at the next tick with finish reason 'cancelled', freeing its
    slot for the queue head the same tick; cancelled requests (and their
    partial tokens) are excluded from goodput like shed ones."""
    m, params = model_and_params
    eng = ServingEngine(
        m, params, num_slots=1, max_len=32, prefill_chunk=8, temperature=0.0
    )
    clock = VirtualClock()
    sched = ContinuousScheduler(eng, clock=clock)
    # r0 has a long budget but a 1 s deadline; r1 waits behind it.
    sched.submit(Request(0, np.asarray([3, 1, 4], np.int32), 20,
                         arrival_time=0.0, deadline=1.0))
    sched.submit(Request(1, np.asarray([2, 7], np.int32), 2,
                         arrival_time=0.0))
    sched.tick()                      # r0 admitted, prefill + first token
    assert eng.live_requests() == [0]
    clock.advance(0.01)
    sched.tick()                      # still within deadline: decodes on
    assert sched.records[0]["generated"] >= 1
    clock.advance(2.0)                # now past the deadline, mid-decode
    sched.tick()
    rec0 = next(r for r in sched.completed if r["id"] == 0)
    assert rec0["finish_reason"] == "cancelled"
    assert sched.cancelled == 1
    assert 0 < rec0["generated"] < 20   # retired early, not run to budget
    # The freed slot admitted r1 on the SAME tick (cancel before admit).
    assert sched.records[1]["admitted"] == rec0["finish"]
    while not sched.idle:
        clock.advance(0.01)
        sched.tick()
    rec1 = next(r for r in sched.completed if r["id"] == 1)
    assert rec1["finish_reason"] == "length"
    summary = summarize_records(sched.completed, elapsed=clock())
    assert summary["completed"] == 1 and summary["cancelled"] == 1
    assert summary["finish_reasons"] == {"cancelled": 1, "length": 1}
    # Goodput counts only what a live caller received: r1's tokens.
    assert summary["generated_tokens"] == rec1["generated"]
    assert eng.pool.num_active == 0


def test_serve_ttl_cancellation_frees_paged_blocks(model_and_params):
    """Paged engine: cancellation releases the retired request's
    block-table blocks back to the global pool, not just its slot."""
    m, params = model_and_params
    eng = ServingEngine(
        m, params, num_slots=2, max_len=32, prefill_chunk=8,
        temperature=0.0, paged=True, block_size=4,
    )
    clock = VirtualClock()
    sched = ContinuousScheduler(eng, clock=clock)
    sched.submit(Request(0, np.asarray([3, 1, 4, 9, 2], np.int32), 16,
                         arrival_time=0.0, deadline=0.5))
    sched.tick()
    clock.advance(0.01)
    sched.tick()
    assert eng.stats()["blocks_in_use"] > 0
    clock.advance(1.0)
    sched.tick()
    rec = next(r for r in sched.completed if r["id"] == 0)
    assert rec["finish_reason"] == "cancelled"
    assert eng.pool.num_active == 0
    assert eng.stats()["blocks_in_use"] == 0


def test_cli_serve_smoke(tmp_path):
    """--serve end to end through the CLI: fresh-init warning path, a short
    trace, the SLO summary line, and per-request JSONL records."""
    from click.testing import CliRunner

    from pytorch_distributed_training_tpu.cli.main import main as cli_main

    jsonl = str(tmp_path / "req.jsonl")
    runner = CliRunner()
    result = runner.invoke(
        cli_main,
        [
            "--use-cpu", "--serve", "--model", "gpt2",
            "--model-overrides",
            "num_layers=2,hidden_dim=32,num_heads=2,vocab_size=61,"
            "max_seq_len=32",
            "--serve-requests", "4", "--serve-slots", "2",
            "--serve-max-new", "6", "--serve-prefill-chunk", "4",
            "--metrics-jsonl", jsonl,
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "serving started" in result.output
    assert "serving finished" in result.output
    assert "goodput_tok_per_s=" in result.output
    assert "FRESH-INIT" in result.output
    import json

    with open(jsonl) as f:
        rows = [json.loads(line) for line in f]
    assert len(rows) == 4
    assert all(r["finish_reason"] == "length" for r in rows)

    # non-LM models must be refused
    result = runner.invoke(
        cli_main, ["--use-cpu", "--serve", "--model", "resnet18"],
    )
    assert result.exit_code != 0
    assert "requires a transformer LM" in result.output


def test_restore_params_from_fresh_manager(model_and_params, tmp_path):
    """The serving restore path: params-only restore must work from a
    manager that did NOT perform the save (a fresh serving process) —
    the bare restore(step) form only works in the saving process."""
    import optax

    from pytorch_distributed_training_tpu.checkpoint import CheckpointManager
    from pytorch_distributed_training_tpu.train import create_train_state

    m, _ = model_and_params
    state = create_train_state(
        m, jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32),
        optax.adamw(1e-3), init_kwargs={"train": False},
    )
    CheckpointManager(str(tmp_path)).save(state, wait=True)
    restored = CheckpointManager(str(tmp_path)).restore_params()
    a = jax.tree_util.tree_leaves(state.params)
    b = jax.tree_util.tree_leaves(restored)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert CheckpointManager(str(tmp_path / "empty")).restore_params() is None


def test_request_logger_roundtrip_recomputes_percentiles(tmp_path):
    """Per-request JSONL is the raw material of serving percentiles:
    records read back from disk must finalize to the same ttft/tpot."""
    from pytorch_distributed_training_tpu.utils.metrics import RequestLogger

    path = str(tmp_path / "req.jsonl")
    logger = RequestLogger(path)
    recs = []
    for i in range(3):
        rec = {
            "id": i, "prompt_len": 4 + i, "max_new_tokens": 8,
            "arrival": 1.0 * i, "admitted": 1.0 * i + 0.1,
            "first_token": 1.0 * i + 0.5, "finish": 1.0 * i + 2.5,
            "finish_reason": "length", "generated": 5,
        }
        finalize_record(rec)
        logger.log(rec)
        recs.append(rec)
    back = logger.read()
    assert len(back) == 3
    for orig, rt in zip(recs, back):
        redone = finalize_record({
            k: v for k, v in rt.items() if k not in ("ttft", "tpot")
        })
        assert redone["ttft"] == pytest.approx(orig["ttft"])
        assert redone["tpot"] == pytest.approx(orig["tpot"])
    s1 = summarize_records(recs)
    s2 = summarize_records([finalize_record(dict(r)) for r in back])
    assert s1["ttft_p50_s"] == s2["ttft_p50_s"]
    assert s1["tpot_p99_s"] == s2["tpot_p99_s"]
