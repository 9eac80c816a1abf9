"""Share of its roofline the flash-attention kernels reach, over every call
in the traced window: the least time the chip could take for the calls (per
call the larger of FLOPs / peak and bytes / peak bandwidth, from
``benchmark/flops/flash_attention.py`` and the call's own shapes) over the
device time the trace gives them.

The trace names both kernels ``%attn.<n>`` (a Mosaic custom call); they are
told apart by what they return: the forward ``(o, log-sum-exp)``, the backward
``(dq, dk, dv)``.  Both results lead with a ``bf16[batch, len, heads*dim]``.
"""

import re

from ..flops.flash_attention import ops_bytes

_FIRST = re.compile(r"= \(?(bf16|f32)\[(\d+),(\d+),(\d+)\]")
_ITEMSIZE = {"bf16": 2, "f32": 4}


def read(facts, heads_key, causal):
    peaks, heads = facts["peaks"], int(facts["config"][heads_key])
    least = spent = 0.0
    for name, seconds in facts["trace"]["custom_calls"]:
        m = _FIRST.search(name)
        if not name.endswith(" tpu_custom_call") or not m:
            continue
        dtype, batch, length, width = m.group(1), *(int(g) for g in m.groups()[1:])
        ops, nbytes = ops_bytes(
            batch=batch, heads=heads, q_len=length, kv_len=length, head_dim=width // heads,
            causal=bool(causal), itemsize=_ITEMSIZE[dtype],
            backward=name.count("[") >= 3 and name.count("f32[") == 0,
        )
        least += max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
        spent += seconds
    return 100.0 * least / spent if spent > 0 else None
