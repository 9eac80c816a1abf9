"""Modeled DP scaling efficiency from AOT-compiled multi-chip programs.

The BASELINE north star asks for >= 90% scaling efficiency from v5e-8 to
v5e-64.  Multi-chip hardware is not reachable from this environment, so
this tool does the honest next-best thing: AOT-compile the exact DP
ResNet-50 train step for real v5e topologies (8 = 2x4, 16 = 2x8, 64 = 8x8) via
``jax.experimental.topologies``, read the *actual* collective traffic XLA
emitted (every all-reduce operand, classified gradient-bucket vs sync-BN
stat as in check_overlap.py), and combine it with the *measured*
single-chip step time (rounds 1-5, another machine) under a documented
ring model:

    T_comm(n)  = 2 * S * (n-1)/n / BW_ici      (bidirectional ring
                 all-reduce of S bytes over the ICI torus; BW_ici is the
                 per-direction ring bandwidth, default 45 GB/s per the
                 public v5e spec of 1600 Gbps total ICI per chip across
                 4 links)
    eff(n)     = T_step / (T_step + T_comm_exposed)

``T_comm_exposed`` conservatively assumes ZERO comm/compute overlap
(OVERLAP.json shows XLA schedules the first gradient bucket with ~14% of
compute still pending, so the true exposure is lower).  Per-chip batch is
held fixed (weak scaling, the DDP regime the reference runs).

Output: one JSON line per topology plus a summary, saved to SCALING.json
with --save.  Every number derived from a compiled program is labeled
``from_hlo``; every modeled number is labeled ``modeled`` — nothing here
claims to be a hardware measurement.
"""

from __future__ import annotations

import json
import math
import os
import re
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

ICI_RING_BW_GBPS = 45.0  # per-direction ring bandwidth, GB/s (public v5e spec)
# Per-host DCN egress bandwidth, GB/s.  Public v5e pod spec: ~200 Gbps of
# data-center network per 8-chip host (the "How to Scale Your Model" DCN
# figure); the conservative planning number used for the cross-slice term.
DCN_HOST_BW_GBPS = 25.0


_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u8": 1, "f64": 8}


def _collective_lines(entry: str, op: str):
    """Yield ``(is_start, shapes)`` for each ``op`` line in the entry
    computation, where ``shapes`` is the LHS's [(dtype, dims-string)].
    Done ops are never matched; the one HLO-parsing loop shared by every
    census here."""
    op_re = re.compile(rf" ({op}-start|{op})(?:\.\d+)?\(")
    for ln in entry.splitlines():
        mo = op_re.search(ln)
        if not mo:
            continue
        shapes = re.findall(
            r"(f32|bf16|f16|s32|u8|f64)\[([0-9,]*)\]", ln[:mo.start()]
        )
        if shapes:
            yield mo.group(1).endswith("-start"), shapes


def _shape_bytes(dt: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _op_operand_bytes(entry: str, op: str, *, start_rule: str) -> tuple[int, int]:
    """(bytes, count) for ``op``.  A ``-start`` op's LHS tuple holds inputs
    AND outputs, handled per ``start_rule``:

    - "halve":   input and output shapes match (all-reduce, all-to-all) —
                 sum everything and divide by two (even tuples only).
    - "outputs": shapes differ (all-gather: each input is 1/N of its
                 output) — count only the second half of the tuple, i.e.
                 output bytes, matching the sync form's LHS.
    """
    total = count = 0
    for is_start, shapes in _collective_lines(entry, op):
        count += 1
        if is_start and len(shapes) % 2 == 0:
            if start_rule == "outputs":
                shapes = shapes[len(shapes) // 2:]
                total += sum(_shape_bytes(dt, d) for dt, d in shapes)
                continue
            total += sum(_shape_bytes(dt, d) for dt, d in shapes) // 2
            continue
        total += sum(_shape_bytes(dt, d) for dt, d in shapes)
    return total, count


def collective_bytes(hlo_text: str) -> dict:
    """Sum all-reduce operand bytes in the entry computation, split into
    gradient buckets (any rank>=2 operand) vs 1-D stat reduces.

    Handles both the synchronous ``all-reduce`` form XLA:TPU currently
    schedules and the async ``all-reduce-start`` form the latency-hiding
    scheduler may emit.  A start op's LHS tuple holds input *and* output
    buffers for the same logical operands, so its summed bytes are halved
    (even-element tuples only); done ops are not counted at all.
    """
    from check_overlap import entry_computation

    entry = entry_computation(hlo_text)
    grad = stat = count = 0
    for is_start, shapes in _collective_lines(entry, "all-reduce"):
        count += 1
        halve = is_start and len(shapes) % 2 == 0
        is_grad = any("," in dims and dims for _, dims in shapes)
        op_bytes = sum(_shape_bytes(dt, d) for dt, d in shapes)
        if halve:
            op_bytes //= 2
        if is_grad:
            grad += op_bytes
        else:
            stat += op_bytes
    if count == 0:
        # A DP step with zero all-reduces is impossible; treat silence as a
        # parsing failure rather than fabricating 100% efficiency.
        raise RuntimeError(
            "no all-reduce ops found in the entry computation — the HLO "
            "collective form is not one this parser understands"
        )
    return {"grad_bytes": grad, "stat_bytes": stat, "allreduce_count": count}


def alltoall_bytes(hlo_text: str) -> dict:
    """Sum all-to-all operand bytes in the entry computation.

    The GShard dispatch/combine einsums of an expert-sharded MoE lower to
    all-to-alls over the ``expert`` axis — this census is the AOT evidence
    of that traffic (VERDICT r3 item 7).  Handles the sync ``all-to-all``
    and async ``all-to-all-start`` forms with the same tuple-halving rule
    as ``collective_bytes``.
    """
    from check_overlap import entry_computation

    entry = entry_computation(hlo_text)
    a2a, a2a_n = _op_operand_bytes(entry, "all-to-all", start_rule="halve")
    ag, ag_n = _op_operand_bytes(entry, "all-gather", start_rule="outputs")
    return {
        "alltoall_bytes": a2a, "alltoall_count": a2a_n,
        "allgather_bytes": ag, "allgather_count": ag_n,
        "allgather_bytes_note": "output bytes (what lands on each shard)",
    }


def compile_moe_ep_step(topology: str = "v5e:2x4", batch: int = 16,
                        seq: int = 1024) -> str:
    """AOT-compile the gpt2_moe train step with experts sharded over the
    ``expert`` axis of a real 8-chip topology; returns scheduled HLO."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.models import create_model
    from pytorch_distributed_training_tpu.parallel.sharding import (
        batch_sharding, infer_params_sharding, tp_rules_for,
    )
    from pytorch_distributed_training_tpu.train import (
        TrainState, make_policy, make_train_step,
    )

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=topology
    )
    mesh = make_mesh(
        MeshConfig(data=2, expert=4), devices=list(topo.devices)
    )
    model = create_model("gpt2_moe", dtype=jnp.bfloat16)
    tx = optax.adamw(1e-3)

    def build_state():
        variables = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32),
            train=False,
        )
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=variables["params"],
            opt_state=tx.init(variables["params"]),
            batch_stats=variables.get("batch_stats", {}),
            apply_fn=model.apply,
            tx=tx,
        )

    shapes = jax.eval_shape(build_state)
    # tp_rules_for("gpt2") carries the expert-parallel MoE rules (w_up/
    # w_down leading axis over `expert`); with tensor=1 the TP entries
    # degenerate to replication, so this is a pure data x expert placement.
    shardings = infer_params_sharding(shapes, mesh, tp_rules_for("gpt2"))
    shardings = shardings.replace(step=NamedSharding(mesh, P()))

    def abstract(s, sh):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    state = jax.tree_util.tree_map(abstract, shapes, shardings)
    tokens = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32, sharding=batch_sharding(mesh, ndim=2)
    )
    step_fn = make_train_step(kind="lm", policy=make_policy("bf16"))
    with mesh:
        return step_fn.lower(state, {"tokens": tokens}).compile().as_text()


def moe_ep_census() -> dict:
    """Compile the expert-sharded MoE step and print its all-to-all
    traffic."""
    hlo = compile_moe_ep_step()
    row = {
        "topology": "v5e:2x4 (data=2 x expert=4)",
        "model": "gpt2_moe (8 experts, top-1, seq 1024, batch 16, bf16)",
        **alltoall_bytes(hlo),
        **{k: v for k, v in collective_bytes(hlo).items()},
        "note": (
            "AOT census: with tokens constrained over (data,fsdp,expert) "
            "(models/moe._constrain_for_ep) the t<->e resharding lowers "
            "to one all-to-all per MoE block over the expert axis "
            "(expert activations); the all-gather bytes are dominated by "
            "the GShard (T,E,C) one-hot dispatch/combine tensors, and "
            "all-reduce bytes are the data-axis grad sync"
        ),
    }
    print(json.dumps(row))
    return row


def compile_for(topology: str, num_slices: int = 1):
    from check_overlap import compile_dp_step_for_topology

    # The per-chip batch (128) held fixed per chip: weak scaling,
    # the DDP regime the reference runs.
    return compile_dp_step_for_topology(
        topology, per_chip_batch=128, image_dtype="bfloat16",
        num_slices=num_slices,
    )


def hierarchical_op_census(hlo_text: str) -> dict:
    """Count the collective forms the multi-slice (MegaScale) compile lowers
    to.  The single-slice DP step is all-reduce-only; the 2-slice program
    instead shows reduce-scatter/all-gather plus send/recv — the
    hierarchical intra-slice/cross-DCN decomposition, recorded here as
    direct evidence that the hybrid mesh changes the lowering."""
    from check_overlap import entry_computation

    text = entry_computation(hlo_text)
    census = {}
    for op in ("all-reduce", "reduce-scatter", "all-gather", "send", "recv",
               "collective-permute"):
        census[op.replace("-", "_") + "_count"] = len(
            re.findall(rf" {op}(?:-start)?\(", text)
        )
    return census


def multislice_row(
    step_ms: float,
    s_total: int,
    num_slices: int = 2,
    slice_topology: str = "v5e:2x4",
) -> dict:
    """The BASELINE config-5 shape: ``num_slices`` hosts x 8 chips joined by
    DCN.  Compiles the DP step over a REAL multi-slice (MegaScale) topology
    descriptor — ``make_hybrid_mesh`` puts ``data`` across slices — then
    models the hierarchical all-reduce XLA demonstrably lowers for it
    (see ``from_hlo.op_census``: reduce-scatter/all-gather/send/recv
    replace the single-slice program's plain all-reduces):

      intra-slice (ICI):  reduce-scatter + all-gather of S bytes over the
                          k-chip ring           t = 2*S*(k-1)/k / BW_ici
      inter-slice (DCN):  all-reduce of the per-chip shards; aggregate
                          bytes crossing each host NIC
                          t = 2*S*(m-1)/m / BW_dcn_host

    ``s_total`` is the gradient payload measured from the single-slice
    compile (the same grads cross DCN, just pre-reduced per slice).  Both
    terms assume zero comm/compute overlap (conservative, as in the
    single-slice rows).
    """
    # "v5e:2x4" -> 8 chips per slice (product of the grid dims).
    dims = slice_topology.split(":", 1)[1]
    chips_per_slice = math.prod(int(d) for d in dims.split("x"))
    n = num_slices * chips_per_slice
    hlo = compile_for(slice_topology, num_slices=num_slices)
    census = hierarchical_op_census(hlo)
    t_ici_ms = (
        2 * s_total * (chips_per_slice - 1) / chips_per_slice
        / (ICI_RING_BW_GBPS * 1e9) * 1e3
    )
    t_dcn_ms = (
        2 * s_total * (num_slices - 1) / num_slices
        / (DCN_HOST_BW_GBPS * 1e9) * 1e3
    )
    eff = step_ms / (step_ms + t_ici_ms + t_dcn_ms)

    # DCN-bandwidth sensitivity (VERDICT r3 weak #8): the headline row
    # pins DCN at the public per-host figure with zero overlap; one
    # assumption flip shouldn't live outside the artifact.  Each entry
    # re-derives efficiency at a DCN bandwidth multiplier, plus one row
    # granting overlap on the DCN leg only (the dcn_2x8 OVERLAP.json legs
    # show 112/113 buckets interleaved there, so zero-overlap is the
    # conservative bound, not the expectation).
    def eff_at(dcn_scale: float, overlap_dcn: bool = False) -> float:
        t_dcn = t_dcn_ms / dcn_scale
        if overlap_dcn:
            t_dcn = max(t_dcn - step_ms * 0.5, 0.0)  # half the step can hide it
        return round(step_ms / (step_ms + t_ici_ms + t_dcn), 4)

    sensitivity = {
        "dcn_bw_x0.5": eff_at(0.5),
        "dcn_bw_x1": eff_at(1.0),
        "dcn_bw_x2": eff_at(2.0),
        "dcn_bw_x1_with_overlap": eff_at(1.0, overlap_dcn=True),
        "note": (
            "efficiency vs the DCN-bandwidth assumption (halved / nominal "
            "/ doubled per-host NIC) and with the measured interleaving "
            "allowed to hide DCN traffic under up to half the step "
            "(OVERLAP.json dcn_2x8: 112/113 grad buckets interleaved, "
            "99.75% of compute after the first bucket)"
        ),
    }
    return {
        "chips": n,
        "topology": f"{num_slices}x {slice_topology} (multi-slice over DCN)",
        "from_hlo": {"grad_bytes_single_slice": s_total, "op_census": census},
        "modeled": {
            "t_step_ms_measured_1chip": step_ms,
            "t_comm_ms_ici_intra_slice": round(t_ici_ms, 3),
            "t_comm_ms_dcn_inter_slice": round(t_dcn_ms, 3),
            "scaling_efficiency": round(eff, 4),
            "ici_ring_bw_gbps": ICI_RING_BW_GBPS,
            "dcn_host_bw_gbps": DCN_HOST_BW_GBPS,
            "sensitivity": sensitivity,
        },
        "note": (
            "BASELINE config 5 (multi-node 2x8): DP step AOT-compiled over a "
            "2-slice MegaScale topology with data spanning DCN "
            "(make_hybrid_mesh); hierarchical-allreduce cost model, zero "
            "overlap assumed"
        ),
    }


def main():
    step_ms = 49.0  # single-chip step at batch 128 (rounds 1-5, another machine)
    args = sys.argv[1:]
    if "--moe-ep" in args:
        moe_ep_census()
        return
    if "--step-ms" in args:
        i = args.index("--step-ms")
        try:
            step_ms = float(args[i + 1])
        except (IndexError, ValueError):
            sys.exit("usage: scaling_analysis.py [--step-ms <milliseconds>] [--save]")

    only_multislice = "--only-multislice" in args
    results = []
    if only_multislice:
        # Reuse the committed single-slice rows (the 64-chip AOT compile
        # takes ~10-15 min); compile and model only the DCN row.  The step
        # time comes from the saved rows unless --step-ms overrides it, so
        # the reused efficiencies and the new row share one step time.
        with open("SCALING.json") as f:
            results = [
                r for r in json.load(f)["per_topology"]
                if "multi-slice" not in r["topology"]
            ]
        if "--step-ms" not in args:
            step_ms = results[0]["modeled"]["t_step_ms_measured_1chip"]
        else:
            # Re-derive the reused rows' efficiencies from their stored
            # comm times so every row in the saved artifact shares the
            # overridden step time.
            for r in results:
                m = r["modeled"]
                m["t_step_ms_measured_1chip"] = step_ms
                m["scaling_efficiency"] = round(
                    step_ms / (step_ms + m["t_comm_ms_ring_no_overlap"]), 4
                )
    else:
        # 8 = v5e-8 (north-star hardware), 16 = 2x8 single-slice, 64 =
        # v5e-64 (the scaling-efficiency target).
        for n, topology in ((8, "v5e:2x4"), (16, "v5e:2x8"), (64, "v5e:8x8")):
            hlo = compile_for(topology)
            traffic = collective_bytes(hlo)
            s_total = traffic["grad_bytes"] + traffic["stat_bytes"]
            t_comm_ms = 2 * s_total * (n - 1) / n / (ICI_RING_BW_GBPS * 1e9) * 1e3
            eff = step_ms / (step_ms + t_comm_ms)
            row = {
                "chips": n,
                "topology": topology,
                "from_hlo": traffic,
                "modeled": {
                    "t_step_ms_measured_1chip": step_ms,
                    "t_comm_ms_ring_no_overlap": round(t_comm_ms, 3),
                    "scaling_efficiency": round(eff, 4),
                    "ici_ring_bw_gbps": ICI_RING_BW_GBPS,
                },
            }
            results.append(row)
            print(json.dumps(row))

    # BASELINE config 5: the multi-node 2x8 shape — 2 slices x 8 chips
    # joined by DCN, the reference's torchrun multi-node contract
    # (src/main.py:38-41) in TPU form.  Gradient payload from the 8-chip
    # single-slice row (same grads, pre-reduced per slice before DCN).
    row8 = next(r for r in results if r["chips"] == 8)
    s_total = row8["from_hlo"]["grad_bytes"] + row8["from_hlo"]["stat_bytes"]
    ms_row = multislice_row(step_ms, s_total)
    results.append(ms_row)
    print(json.dumps(ms_row))
    by_chips = {r["chips"]: r for r in results if "multi-slice" not in r["topology"]}
    summary = {
        "metric": "modeled_dp_scaling_efficiency_8_to_64",
        "value": round(
            by_chips[64]["modeled"]["scaling_efficiency"]
            / by_chips[8]["modeled"]["scaling_efficiency"],
            4,
        ),
        "multislice_2x8_efficiency": ms_row["modeled"]["scaling_efficiency"],
        "note": (
            "AOT-compiled collective traffic + measured 1-chip step under a "
            "no-overlap ring model; NOT a hardware measurement"
        ),
    }
    print(json.dumps(summary))
    if "--save" in sys.argv[1:]:
        with open("SCALING.json", "w") as f:
            json.dump({"per_topology": results, "summary": summary}, f, indent=1)


if __name__ == "__main__":
    main()
