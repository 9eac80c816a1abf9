"""ViT-B/16 step diagnosis: compiled cost analysis + component timings.

VERDICT r2 item 3 asks either >= 0.5 MFU or a committed roofline analysis
showing what the remaining gap is.  This tool produces the evidence: the
compiled step's own FLOP and bytes-accessed counts (XLA cost analysis),
roofline bounds from the public v5e peaks, and wall-times of stripped
variants (forward-only, forward+backward, full step; flash vs XLA
attention) that localize where the time goes.  One JSON line; --save
writes VIT_ROOFLINE.json.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def timed(fn, *args, rounds=3, inner=8):
    out = fn(*args)
    jax_block(out)
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax_block(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def jax_block(x):
    import jax

    jax.tree_util.tree_map(
        lambda l: l.block_until_ready() if hasattr(l, "block_until_ready") else l,
        x,
    )


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_training_tpu.models import vit_b16
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_policy, make_train_step,
    )

    batch = 128
    if "--batch" in sys.argv[1:]:
        batch = int(sys.argv[sys.argv.index("--batch") + 1])
    model = vit_b16(num_classes=1000, dtype=jnp.bfloat16)
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3), jnp.bfloat16),
        optax.adamw(1e-3), init_kwargs={"train": False},
    )
    rng = np.random.default_rng(0)
    images = jnp.asarray(
        rng.standard_normal((batch, 224, 224, 3), np.float32), jnp.bfloat16
    )
    labels = jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32)
    b = {"image": images, "label": labels}

    step_fn = make_train_step(kind="image_classifier", policy=make_policy("bf16"))
    lowered = step_fn.lower(state, b)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    flops = cost.get("flops", 0.0)
    bytes_acc = cost.get("bytes accessed", 0.0)

    params = state.params
    variables = {"params": params}

    fwd = jax.jit(
        lambda v, x: model.apply(v, x, train=False)
    )
    loss_fn = lambda p, x, y: jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(
            model.apply({"params": p}, x, train=False).astype(jnp.float32), y
        )
    )
    fwdbwd = jax.jit(jax.grad(loss_fn))

    t_fwd = timed(fwd, variables, images)
    t_fwdbwd = timed(fwdbwd, params, images, labels)

    def t_step():
        # Reuse the already-jitted step_fn (its compile is cached) rather
        # than paying a second full XLA compile.
        st = state
        stp = step_fn
        st, m = stp(st, b)
        float(m["loss"])
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(8):
                st, m = stp(st, b)
            float(m["loss"])
            best = min(best, (time.perf_counter() - t0) / 8)
        return best

    t_full = t_step()

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    model_flops_step = 6 * n_params * 197 * batch
    from pytorch_distributed_training_tpu.obs.cost import require_peaks

    peak_flops, peak_hbm = require_peaks()
    out = {
        "metric": "vit_b16_step_diagnosis",
        "batch": batch,
        "compiled_flops_per_step": flops,
        "compiled_bytes_accessed_per_step": bytes_acc,
        "roofline_ms_flops": round(flops / peak_flops * 1e3, 2),
        "roofline_ms_bytes": round(bytes_acc / peak_hbm * 1e3, 2),
        "model_flops_6NT_per_step": model_flops_step,
        "measured_ms_forward": round(t_fwd * 1e3, 2),
        "measured_ms_fwd_bwd": round(t_fwdbwd * 1e3, 2),
        "measured_ms_full_step": round(t_full * 1e3, 2),
        "imgs_per_sec_full_step": round(batch / t_full, 1),
    }
    print(json.dumps(out))
    if "--save" in sys.argv[1:]:
        with open("VIT_ROOFLINE.json", "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
