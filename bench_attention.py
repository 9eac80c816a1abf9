"""Attention micro-benchmark: Pallas flash kernel vs XLA full attention.

Substantiates the kernel's perf claim with recorded numbers (VERDICT r1
item 3): fwd+bwd wall time at L in {197, 1024, 2048}, bf16, on the current
backend.  Prints one JSON line per config:

  {"metric": "flash_attention_speedup", "L": ..., "flash_ms": ...,
   "xla_ms": ..., "speedup": ...}

Run on TPU hardware for the recorded numbers; CPU runs exercise the same
code through the Pallas interpreter but are not meaningful timings.
"""

from __future__ import annotations

import json
import sys
import time


def main():
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tpu.ops import flash_attention
    from pytorch_distributed_training_tpu.ops.attention import _xla_attention

    on_tpu = jax.default_backend() == "tpu"
    B, H, D = (4, 12, 64) if on_tpu else (1, 2, 64)
    # L=197 is ViT-B/16 at 224px (non-causal, its real attention); the LM
    # lengths run causal.
    configs = [(197, False), (1024, True), (2048, True)] if on_tpu else [(197, False)]
    steps = 20 if on_tpu else 2

    results = []
    for L, causal in configs:
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, L, H, D), jnp.bfloat16)
        k = jax.random.normal(kk, (B, L, H, D), jnp.bfloat16)
        v = jax.random.normal(kv, (B, L, H, D), jnp.bfloat16)

        def timed(fn):
            loss = jax.jit(
                jax.value_and_grad(
                    lambda q, k, v: jnp.sum(
                        fn(q, k, v).astype(jnp.float32) ** 2
                    )
                , argnums=(0, 1, 2))
            )
            (l0, g) = loss(q, k, v)
            jax.block_until_ready(l0)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(steps):
                    l, g = loss(q, k, v)
                float(l)
                best = min(best, (time.perf_counter() - t0) / steps)
            return best * 1e3

        flash_ms = timed(
            lambda q, k, v: flash_attention(q, k, v, causal=causal)
        )
        xla_ms = timed(lambda q, k, v: _xla_attention(q, k, v, causal=causal))
        results.append({
            "metric": "flash_attention_fwd_bwd",
            "L": L, "B": B, "H": H, "D": D, "dtype": "bf16", "causal": causal,
            "flash_ms": round(flash_ms, 3),
            "xla_ms": round(xla_ms, 3),
            "speedup": round(xla_ms / flash_ms, 3),
            "backend": jax.default_backend(),
        })
        print(json.dumps(results[-1]), flush=True)
    if "--save" in sys.argv[1:]:
        with open("ATTN_BENCH.json", "w") as f:
            json.dump({
                "rows": results,
                "note": (
                    "B=4 micro-bench: per-call dispatch dominates at ms "
                    "scale, so these rows are indicative only. The "
                    "flash-vs-XLA dispatch threshold is set by full-model "
                    "A/Bs, not by this file."
                ),
            }, f, indent=1)
    return results


if __name__ == "__main__":
    from pytorch_distributed_training_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    main()
