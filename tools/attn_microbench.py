"""Stable attention micro-bench: flash (Pallas) vs low-memory XLA.

VERDICT r3 weak #7: the old B=4 micro-bench (bench_attention.py) times
single calls, where per-call dispatch dominates, so kernel claims had to
rest on minutes-long full-model A/Bs.  This harness fixes that the same way
bench.py does: N chained executions per timing draw (the donated carry
serializes them; one scalar fetch closes the async window), median of R
draws, dispatch warmup first.  Spread lands at the ~1% level, good enough
to catch a kernel regression cheaply.

Times three programs per (shape, path): forward, forward+backward (grads
wrt q/k/v), and bwd-only (difference).  Run:
  python tools/attn_microbench.py [--seq 512] [--save]
writes ATTN_MICRO.json rows for seq in {256, 512, 1024, 2048} by default.
"""

import json
import os
import sys
import time
from statistics import median

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

B, H, D = 8, 12, 64  # GPT-2 microbatch-8 shape
# Two chain lengths per measurement: the per-iteration time is the slope
# (t_long - t_short) / (LONG - SHORT), which cancels the fixed per-call
# cost (dispatch + the closing scalar fetch).
SHORT, LONG = 16, 144
ROUNDS = 5


def _paths():
    from pytorch_distributed_training_tpu.ops import pallas_attention
    from pytorch_distributed_training_tpu.ops.attention import _xla_attention

    def flash(q, k, v):
        return pallas_attention.flash_attention(q, k, v, causal=True)

    def xla_lowp(q, k, v):
        return _xla_attention(q, k, v, causal=True)

    return {"flash": flash, "xla_lowp": xla_lowp}


def _slope(make_chain, q, k, v):
    """Per-iteration seconds via the two-length slope, plus a spread
    estimate from the long-chain draws."""
    short = jax.jit(make_chain(SHORT))
    long_ = jax.jit(make_chain(LONG))
    float(short(q, k, v))  # compile + warm
    float(long_(q, k, v))
    ts, tl = [], []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        s = float(short(q, k, v))
        ts.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        s2 = float(long_(q, k, v))
        tl.append(time.perf_counter() - t0)
        assert np.isfinite(s) and np.isfinite(s2)
    per_iter = (median(tl) - median(ts)) / (LONG - SHORT)
    spread = (max(tl) - min(tl)) / median(tl)
    return per_iter, spread


def _time_fn(fn, q, k, v):
    def make_chain(n):
        def chain(q, k, v):
            def body(carry, _):
                out = fn(carry, k, v)
                return out.astype(carry.dtype), ()

            final, _ = jax.lax.scan(body, q, None, length=n)
            return jnp.sum(final.astype(jnp.float32))

        return chain

    return _slope(make_chain, q, k, v)


def _time_grad(fn, q, k, v):
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 1, 2))

    def make_chain(n):
        def chain(q, k, v):
            def body(carry, _):
                dq, dk, dv = grad(carry, k, v)
                mix = (dq + dk + dv).astype(carry.dtype)
                return carry + mix * jnp.asarray(1e-9, carry.dtype), ()

            final, _ = jax.lax.scan(body, q, None, length=n)
            return jnp.sum(final.astype(jnp.float32))

        return chain

    return _slope(make_chain, q, k, v)


def main():
    seqs = [256, 512, 1024, 2048]
    if "--seq" in sys.argv[1:]:
        seqs = [int(sys.argv[sys.argv.index("--seq") + 1])]
    rows = []
    for seq in seqs:
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((B, seq, H, D)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((B, seq, H, D)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((B, seq, H, D)), jnp.bfloat16)
        row = {"batch": B, "seq": seq, "heads": H, "head_dim": D,
               "chain_lengths": [SHORT, LONG], "rounds": ROUNDS}
        for name, fn in _paths().items():
            fwd_s, fwd_spread = _time_fn(fn, q, k, v)
            both_s, both_spread = _time_grad(fn, q, k, v)
            row[name] = {
                "fwd_us": round(fwd_s * 1e6, 1),
                "fwd_spread": round(fwd_spread, 4),
                "fwd_bwd_us": round(both_s * 1e6, 1),
                "fwd_bwd_spread": round(both_spread, 4),
                "bwd_only_us": round((both_s - fwd_s) * 1e6, 1),
            }
        row["flash_over_xla_fwd"] = round(
            row["flash"]["fwd_us"] / row["xla_lowp"]["fwd_us"], 3
        )
        row["flash_over_xla_fwd_bwd"] = round(
            row["flash"]["fwd_bwd_us"] / row["xla_lowp"]["fwd_bwd_us"], 3
        )
        rows.append(row)
        print(json.dumps(row))
    if "--save" in sys.argv[1:]:
        out = {
            "metric": "attention_microbench_flash_vs_xla",
            "protocol": (
                f"two-length slope ({SHORT} vs {LONG} chained executions) "
                f"over median-of-{ROUNDS} draws, dispatch-warmed — cancels "
                "the fixed per-call cost"
            ),
            "rows": rows,
        }
        with open(os.path.join(_REPO_ROOT, "ATTN_MICRO.json"), "w") as f:
            json.dump(out, f, indent=1)
        print("wrote ATTN_MICRO.json")


if __name__ == "__main__":
    main()
