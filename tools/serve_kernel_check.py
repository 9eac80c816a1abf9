"""Compile every attention kernel the serving and training paths can
dispatch to, on the backend this process gets, at GPT-2 124M width
(12 heads x 64, block 16, L 1024, bf16), and compare each with plain
``jax.numpy`` on the same inputs.

The interpret-mode tests pin the kernels' arithmetic; only a compiled
run says whether Mosaic accepts them.  One JSON line per case:
``{"case", "ok", "kernels", "max_err" | "error"}`` — ``kernels`` counts the
Mosaic calls of the case's compiled program by the role name the
``pallas_call`` carries (``ops.pallas_attention.KERNEL_NAMES``; empty under
the interpreter) — and the full report (with whole error texts) goes to
``--out``.  Exit code 1 if any case failed.

    python tools/serve_kernel_check.py --out chiprun_out/kernels.json
    JAX_PLATFORMS=cpu python tools/serve_kernel_check.py --tiny   # interpreter
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cases(tiny: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tpu.comm.compress import (
        dequantize_kv, quantize_kv,
    )
    from pytorch_distributed_training_tpu.obs.cost import mosaic_kernels
    from pytorch_distributed_training_tpu.ops import pallas_attention as pa
    from pytorch_distributed_training_tpu.ops.attention import _xla_attention

    def compiled_run(fn, *args):
        """``fn(*args)`` through one compile, and what Mosaic kernels it
        holds, by name."""
        compiled = jax.jit(fn).lower(*args).compile()
        return compiled(*args), mosaic_kernels(compiled.as_text())

    if tiny:
        b, h, dh, bs, nb, seq = 3, 2, 8, 4, 32, 128
        dtype = jnp.float32
    else:
        b, h, dh, bs, nb, seq = 8, 12, 64, 16, 64, 1024
        dtype = jnp.bfloat16
    n_blocks = b * nb
    max_len = nb * bs
    rng = np.random.default_rng(0)

    def rand(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    kb, vb = rand(n_blocks, h, bs, dh), rand(n_blocks, h, bs, dh)
    table = jnp.asarray(
        rng.permutation(n_blocks).reshape(b, nb), jnp.int32
    )
    # Row prefixes: start of a block, mid-block, a block boundary, the end
    # of the table span, and whatever else fills the batch.
    starts = np.resize(
        np.array([0, bs + 1, 2 * bs - 1, max_len // 2, 5, 3 * bs]), b
    ).astype(np.int32)

    def through_table(blocks):
        g = jnp.transpose(blocks[table], (0, 2, 1, 3, 4))
        return g.reshape(b, h, max_len, g.shape[-1])

    def reference(q, kk, vv, index):
        """q (B, C, H, Dh) over contiguous (B, H, L, Dh) f32 K/V; query j
        of row b sees keys 0..index[b]+j."""
        q, kk, vv = (x.astype(jnp.float32) for x in (q, kk, vv))
        s = jnp.einsum("bchd,bhkd->bhck", q, kk) * (dh ** -0.5)
        cols = index[:, None] + jnp.arange(q.shape[1])[None, :]
        mask = (
            jnp.arange(kk.shape[2])[None, None, None, :]
            <= cols[:, None, :, None]
        )
        s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
        return jnp.einsum("bhck,bhkd->bchd", jax.nn.softmax(s, axis=-1), vv)

    def paged(fn, c, quant):
        def run():
            index = jnp.asarray(np.minimum(starts, max_len - c), jnp.int32)
            if index.shape[0] > 3:
                index = index.at[3].set(max_len - c)  # last block is live
            q = rand(b, c, h, dh)
            kw = {}
            k_ref, v_ref = kb, vb
            k_in, v_in = kb, vb
            if quant:
                k_in, ks = quantize_kv(kb, quant)
                v_in, vs = quantize_kv(vb, quant)
                kw = dict(k_scale=ks, v_scale=vs, quant=quant)
                k_ref = dequantize_kv(k_in, ks, quant)
                v_ref = dequantize_kv(v_in, vs, quant)
            q_in = q[:, 0] if fn is pa.paged_decode_attention else q
            out, kernels = compiled_run(
                lambda *a: fn(*a, **kw), q_in, k_in, v_in, table, index
            )
            if out.ndim == 3:
                out = out[:, None]
            ref = reference(
                q, through_table(k_ref), through_table(v_ref), index
            )
            return out, ref, kernels
        return run

    def contiguous(fn, c):
        def run():
            index = jnp.asarray(np.minimum(starts, max_len - c), jnp.int32)
            q = rand(b, c, h, dh)
            kk, vv = rand(b, h, max_len, dh), rand(b, h, max_len, dh)
            q_in = q[:, 0] if fn is pa.decode_attention else q
            out, kernels = compiled_run(fn, q_in, kk, vv, index)
            if out.ndim == 3:
                out = out[:, None]
            return out, reference(q, kk, vv, index), kernels
        return run

    def flash(grad):
        def run():
            q, k, v = (rand(b, seq, h, dh) for _ in range(3))

            def loss(fn):
                return lambda q, k, v: jnp.sum(
                    fn(q, k, v, causal=True).astype(jnp.float32) ** 2
                )

            if grad:
                out, kernels = compiled_run(
                    jax.grad(loss(pa.flash_attention), (0, 1, 2)), q, k, v
                )
                ref = jax.jit(jax.grad(loss(_xla_attention), (0, 1, 2)))(
                    *(x.astype(jnp.float32) for x in (q, k, v))
                )
                return jnp.concatenate(out), jnp.concatenate(ref), kernels
            out, kernels = compiled_run(
                lambda q, k, v: pa.flash_attention(q, k, v, causal=True),
                q, k, v,
            )
            ref = _xla_attention(
                *(x.astype(jnp.float32) for x in (q, k, v)), causal=True
            )
            return out, ref, kernels
        return run

    spec_c = 5  # --serve-spec-k 4 verifies k+1 positions per slot
    cases = [
        ("flash_fwd", flash(False)),
        ("flash_bwd", flash(True)),
        ("decode", contiguous(pa.decode_attention, 1)),
        ("decode_multi_c5", contiguous(pa.decode_attention_multi, spec_c)),
    ]
    for quant in (None, "int8", "int4"):
        tag = quant or "bf16"
        cases += [
            (f"paged_decode_{tag}", paged(pa.paged_decode_attention, 1, quant)),
            (f"paged_verify_c5_{tag}",
             paged(pa.paged_decode_attention_multi, spec_c, quant)),
            (f"paged_prefill_c16_{tag}",
             paged(pa.paged_prefill_attention, 16, quant)),
        ]
    cases.append((
        f"paged_prefill_c{pa.MAX_FUSED_PREFILL_CHUNK}_bf16",
        paged(pa.paged_prefill_attention, pa.MAX_FUSED_PREFILL_CHUNK, None),
    ))
    return cases


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="small f32 shapes (the CPU interpreter's size)")
    ap.add_argument("--only", default=None,
                    help="comma-separated substrings of case names to run")
    ap.add_argument("--out", default=None, help="write the full report here")
    args = ap.parse_args()

    import jax
    import numpy as np

    dev = jax.devices()[0]
    report = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "jax": jax.__version__, "cases": [],
    }
    print(json.dumps(report["device"]))
    # Largest error over the largest reference magnitude: bf16 keeps 8
    # significant bits, and the kernels round p to bf16 before p @ v.
    tol = 1e-5 if args.tiny else 2e-2
    wanted = args.only.split(",") if args.only else None
    for name, run in _cases(args.tiny):
        if wanted and not any(w in name for w in wanted):
            continue
        entry = {"case": name}
        try:
            out, ref, entry["kernels"] = run()
            out = np.asarray(out, np.float32)
            ref = np.asarray(ref, np.float32)
            err = float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))
            entry.update(
                ok=bool(np.isfinite(out).all() and err <= tol), max_err=err
            )
        except Exception as e:  # report every case; one failure must not hide the rest
            entry.update(ok=False, error=f"{type(e).__name__}: {e}"[:1500],
                         traceback=traceback.format_exc())
        report["cases"].append(entry)
        print(json.dumps({k: v for k, v in entry.items() if k != "traceback"}),
              flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0 if all(c["ok"] for c in report["cases"]) else 1


if __name__ == "__main__":
    sys.exit(main())
