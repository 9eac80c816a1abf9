"""Share of their roofline the experts' grouped matrix products reach: the
least time the chip could take for the products the MODEL needs of the rows
that were live (``benchmark/flops/ragged_dot.py``) over the device time the
trace gives every call the pattern matches (``%ragged-dot*``: XLA's own
Mosaic grouped matmul behind ``lax.ragged_dot``, the forward products, their
data gradients and their weight gradients alike).

A call's name carries its static shapes, not the rows that were live, so the
rows come from the program's counter: ``moe_held_assignments``, a step's
total over layers and microbatches, as its mean over the window's steps,
shared evenly over the ``layers * microbatches`` places a step has (the
least time of a call is convex in its rows, so the even share is a lower
bound).  ``products`` is the model's count a live row and place: a gated
expert is three matrices, each met forward, by its data gradient and by its
weight gradient; what a program recomputes beside is time without least
time, as in ``train_mfu``.  A trace with no such call or a kind with no
counters (the parent's, another cell's) gives nothing to read.
"""

import re

from ..flops.ragged_dot import ops_bytes


def read(facts, pattern, module_prefix, products, width_key, inner_key, layers_key, groups_counter):
    counters, peaks, config = facts.get("counters") or {}, facts["peaks"], facts["config"]
    named = re.compile(pattern)
    spent = sum(s for name, s in facts["trace"]["custom_calls"] if named.search(name))
    steps = sum(1 for name, _, _ in facts["trace"]["modules"] if name.startswith(module_prefix))
    if spent <= 0 or not steps or "moe_held_assignments" not in counters or not facts.get("steps") \
            or not facts.get("microbatches"):
        return None
    places = int(config[layers_key]) * int(facts["microbatches"])
    ops, nbytes = ops_bytes(
        rows=counters["moe_held_assignments"] / facts["steps"] / places,
        groups=int(counters[groups_counter]), d_in=int(config[width_key]), d_out=int(config[inner_key]),
        itemsize=2,
    )
    least = steps * places * products * max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
