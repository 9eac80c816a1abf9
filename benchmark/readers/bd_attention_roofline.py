"""Share of its roofline a block-diffusion attention kernel reaches, by the
name the program gave it (``%flash_bd_fwd.<n>``, ``%flash_bd_bwd.<n>``): the least time the chip could take for the calls
(per call the larger of FLOPs / peak and bytes / peak bandwidth, from
``benchmark/flops/block_diffusion_attention.py``: live pairs of the mask
only, K/V at their own head count) over the device time the trace gives
every kernel the pattern matches.

``kernel_roofline.py`` cannot read these: it knows one head count and a mask
that is causal or absent.  As there, a backward split over several kernels
would need its operations once: the least time is counted for the calls
``counted`` matches, the device time for all the pattern matches.  The result
the program's kernels lead with is ``bf16[batch, heads, 2L, dim]`` (the
forward's o, the backward's dq); the block length is the configuration's.  A trace
with no such kernel (the parent's, another cell's) gives nothing to read.
"""

import re

from ..flops.block_diffusion_attention import ops_bytes

_FIRST = re.compile(r"= \(?(bf16|f32)\[(\d+),(\d+),(\d+),(\d+)\]")
_ITEMSIZE = {"bf16": 2, "f32": 4}


def read(facts, pattern, counted, kv_heads_key, backward):
    peaks, config = facts["peaks"], facts["config"]
    kv_heads = int(config[kv_heads_key])
    block = int(config["system"]["overrides"]["block_length"])
    named, once = re.compile(pattern), re.compile(counted)
    least = spent = 0.0
    for name, seconds in facts["trace"]["custom_calls"]:
        if not name.endswith(" tpu_custom_call") or not named.search(name):
            continue
        spent += seconds
        m = _FIRST.search(name)
        if not m or not once.search(name):
            continue
        batch, heads, positions, head_dim = (int(g) for g in m.groups()[1:])
        ops, nbytes = ops_bytes(
            batch=batch, heads=heads, kv_heads=kv_heads, seq_len=positions // 2, block=block,
            head_dim=head_dim, itemsize=_ITEMSIZE[m.group(1)], backward=bool(backward),
        )
        least += max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent if spent > 0 and least > 0 else None
