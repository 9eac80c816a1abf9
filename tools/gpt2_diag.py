"""GPT-2 124M step diagnosis: compiled cost analysis + roofline placement.

The ViT and ResNet headlines carry committed roofline evidence
(VIT_ROOFLINE (deleted: not measured on the current machine), RESNET_ROOFLINE
(deleted: not measured on the current machine)); this closes the set for the
GPT-2 flagship.  Reports the accumulation microbatch's own XLA FLOP and
bytes-accessed counts (cost analysis counts a while-loop body ONCE, so
multiply by accum for per-step totals), roofline bounds from the public
v5e peaks, and the measured full-step time from the chained-donated-step
protocol bench.py uses.  One JSON line; --save writes GPT2_ROOFLINE.json.

Usage: python tools/gpt2_diag.py [--batch 128] [--accum 16] [--save]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_training_tpu.models import gpt2_124m
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_policy, make_train_step,
    )

    batch = 128
    accum = 16
    if "--batch" in sys.argv[1:]:
        batch = int(sys.argv[sys.argv.index("--batch") + 1])
    if "--accum" in sys.argv[1:]:
        accum = int(sys.argv[sys.argv.index("--accum") + 1])
    seq = 1024

    model = gpt2_124m(dtype=jnp.bfloat16)
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32),
        optax.adamw(3e-4), init_kwargs={"train": False},
    )
    rng = np.random.default_rng(0)
    b = {"tokens": jnp.asarray(
        rng.integers(0, 50257, (batch, seq)), jnp.int32
    )}
    step_fn = make_train_step(
        kind="lm", policy=make_policy("bf16"), num_microbatches=accum,
        base_rng=jax.random.PRNGKey(1),
    )
    compiled = step_fn.lower(state, b).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    # XLA counts the accumulation while-loop body once; scale to a step.
    flops_ub = float(cost.get("flops", 0.0))
    bytes_ub = float(cost.get("bytes accessed", 0.0))
    flops_step = flops_ub * accum
    bytes_step = bytes_ub * accum

    # Measured step time (chained donated steps, one scalar fetch).
    st, m = step_fn(state, b)
    float(m["loss"])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(8):
            st, m = step_fn(st, b)
        float(m["loss"])
        best = min(best, (time.perf_counter() - t0) / 8)

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    model_flops = 6 * n_params * batch * seq
    from pytorch_distributed_training_tpu.obs.cost import require_peaks

    peak_flops, peak_hbm = require_peaks()
    out = {
        "metric": "gpt2_124m_step_diagnosis",
        "batch": batch,
        "seq": seq,
        "accum": accum,
        "compiled_flops_per_step": flops_step,
        "compiled_bytes_accessed_per_step": bytes_step,
        "model_flops_6NT_per_step": model_flops,
        "roofline_ms_flops": round(flops_step / peak_flops * 1e3, 1),
        "roofline_ms_bytes": round(bytes_step / peak_hbm * 1e3, 1),
        "measured_ms_full_step": round(best * 1e3, 1),
        "tokens_per_sec": round(batch * seq / best, 1),
        "mfu_vs_v5e_bf16_peak": round(
            model_flops / best / peak_flops, 4
        ),
    }
    print(json.dumps(out))
    if "--save" in sys.argv[1:]:
        with open("GPT2_ROOFLINE.json", "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
