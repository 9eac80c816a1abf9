"""Share of its roofline one flash-attention kernel reaches, by the name the
program gave the kernel (``pl.pallas_call(..., name=...)``: the trace calls it
``%<name>.<n>``): the least time the chip could take for the calls (per call
the larger of FLOPs / peak and bytes / peak bandwidth, from
``benchmark/flops/flash_attention.py`` and the call's own shapes) over the
device time the trace gives every kernel the pattern matches.

A backward pass that is split over two kernels (``flash_bwd_dq`` and
``flash_bwd_dkv``) still needs the backward's operations and bytes once: the
least time is counted for the calls ``counted`` matches (the fused kernel and
one half of a split one), the device time for all of them.

Results lead with ``bf16[batch, len, heads*dim]`` (the native layout) or
``bf16[batch, heads, len, dim]`` (the transposed multi-tile kernels).
"""

import re

from ..flops.flash_attention import ops_bytes

_FIRST = re.compile(r"= \(?(bf16|f32)\[([\d,]+)\]")
_ITEMSIZE = {"bf16": 2, "f32": 4}


def read(facts, pattern, counted, heads_key, causal, backward):
    peaks, heads = facts["peaks"], int(facts["config"][heads_key])
    named, once = re.compile(pattern), re.compile(counted)
    least = spent = 0.0
    for name, seconds in facts["trace"]["custom_calls"]:
        if not name.endswith(" tpu_custom_call") or not named.search(name):
            continue
        spent += seconds
        m = _FIRST.search(name)
        if not m or not once.search(name):
            continue
        dims = [int(d) for d in m.group(2).split(",")]
        if len(dims) == 3:
            batch, length, head_dim = dims[0], dims[1], dims[2] // heads
        elif len(dims) == 4:
            batch, length, head_dim = dims[0], dims[2], dims[3]
        else:
            continue
        ops, nbytes = ops_bytes(
            batch=batch, heads=heads, q_len=length, kv_len=length, head_dim=head_dim,
            causal=bool(causal), itemsize=_ITEMSIZE[m.group(1)], backward=bool(backward),
        )
        least += max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent if spent > 0 and least > 0 else None
