"""Compiled-cost accounting: FLOPs, bytes, MFU, and collective traffic.

Everything here reads the artifact XLA already produced — the compiled
executable's ``cost_analysis()`` / ``memory_analysis()`` and its HLO text —
so the numbers are the *program's*, not a hand model.  Two consumers:

- the CLI's ``--metrics-dir`` probe emits one ``compiled_cost`` event per
  run (train step FLOPs, bytes accessed, memory footprint, collective
  census), and ``tools/telemetry_report.py`` divides those FLOPs by the
  measured median step time for MFU;
- the analytic DCN byte model (``comm.hierarchical.dcn_bytes_per_sync``)
  becomes per-step counters on every step event, which the tests assert
  against directly — the ROADMAP "validate the byte model" item as an
  automated check instead of a chip-session TODO.

The collective census is a lightweight HLO text parse (the same shape-list
idiom as ``tools/scaling_analysis.py``, kept dependency-free here): per
collective kind, operand bytes and op count, with a per-dtype breakdown so
a compressed DCN hop is visible as int8 all-gather payload.
"""

from __future__ import annotations

import collections
import functools
import re
from typing import Any

# The one table of device peaks, keyed by device_kind substrings (what
# jax.devices()[0].device_kind actually reports — v5e shows up as
# "TPU v5 lite").  Source: Google Cloud documentation, "TPU v5e" — 197
# TFLOP/s bf16 and 819 GB/s of HBM per chip.  A device that is not here has
# no peak: ``peak_flops_for`` says None, ``require_peaks`` raises.
_V5E = ("v5 lite", "v5e", "v5litepod")
PEAK_FLOPS = ((_V5E, 197e12),)
PEAK_HBM_BYTES_PER_S = ((_V5E, 819e9),)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def compiled_cost(compiled: Any) -> dict[str, float]:
    """{"flops", "bytes_accessed"} from ``compiled.cost_analysis()``
    (which returns a dict, or a 1-list of dicts on older jax)."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }


def memory_stats(compiled: Any) -> dict[str, int] | None:
    """Per-program memory analysis (argument/output/temp/generated code
    bytes); None when the backend doesn't expose it (CPU)."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None
    out = {}
    for key in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ):
        val = getattr(mem, key, None)
        if val is not None:
            out[key] = int(val)
    return out or None


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_census(hlo_text: str) -> dict[str, dict[str, Any]]:
    """Per-collective-kind operand bytes/count from compiled HLO text.

    Counts the sync form and the async ``-start`` form (whose LHS tuple
    holds input AND output buffers — halved for the even-tuple case, as in
    tools/scaling_analysis.py); ``-done`` ops are never counted.  Bytes are
    broken down per dtype so compressed payloads (bf16/int8 DCN hops) are
    attributable.
    """
    dtype_re = "|".join(_DTYPE_BYTES)
    census: dict[str, dict[str, Any]] = {}
    for op in _COLLECTIVE_OPS:
        op_re = re.compile(rf" ({op}-start|{op})(?:\.\d+)?\(")
        total = count = 0
        by_dtype: dict[str, int] = {}
        for ln in hlo_text.splitlines():
            mo = op_re.search(ln)
            if not mo:
                continue
            shapes = re.findall(
                rf"({dtype_re})\[([0-9,]*)\]", ln[: mo.start()]
            )
            if not shapes:
                continue
            count += 1
            halve = mo.group(1).endswith("-start") and len(shapes) % 2 == 0
            if halve:
                shapes = shapes[: len(shapes) // 2]
            for dt, dims in shapes:
                b = _shape_bytes(dt, dims)
                total += b
                by_dtype[dt] = by_dtype.get(dt, 0) + b
        if count:
            census[op] = {"bytes": total, "count": count, "by_dtype": by_dtype}
    return census


_MOSAIC_CALL = re.compile(
    r'^\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = .*custom_call_target="tpu_custom_call"',
    re.M,
)


def mosaic_kernels(hlo_text: str) -> dict[str, int]:
    """Pallas TPU (Mosaic) kernels in a compiled program's text, counted by
    the name their instruction carries — the ``name`` the ``pallas_call``
    was given (``ops.pallas_attention.KERNEL_NAMES``: ``flash_fwd``,
    ``paged_decode_attn``, ...).  Kernel choice is a predicate on the
    backend at trace time; this is what was actually lowered — nothing on
    a TPU program that should carry a fused kernel means the dispatch
    routed around it (and always nothing off-TPU, where the kernels run
    interpreted as plain HLO)."""
    counts: dict[str, int] = {}
    for name in _MOSAIC_CALL.findall(hlo_text):
        counts[name] = counts.get(name, 0) + 1
    return counts


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"(?:^|[\s)\]}])([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


@functools.lru_cache(maxsize=None)
def _scoped() -> re.Pattern:
    """An ``obs.trace.PHASES`` name standing as a scope in a path: after the
    start, a ``/`` or a ``(``, before a ``/``, a ``)`` or the end.  (Made on
    first use: this module is also loaded by file path, with no package and
    no JAX — ``chip_smoke.py``.)"""
    from .trace import PHASES

    names = "|".join(re.escape(p) for p in sorted(PHASES, key=len, reverse=True))
    return re.compile(f"(?:^|(?<=[/(]))({names})(?=[/)]|$)")


# The work a fusion is put down to, whatever its root is.
_HEAVY = ("dot", "convolution", "custom-call")
# Instructions that gather or run other work: they take no scope from operands.
_STRUCTURAL = ("tuple", "while", "conditional", "call")


def innermost_scope(op_name: str) -> str | None:
    """The ``obs.trace.PHASES`` name that ends rightmost in an instruction's
    ``op_name`` — ``jit(step)/grad_accum/microbatch/transpose(jvp(moe/experts))/…/checkpoint/dot_general``
    is ``moe/experts`` — through ``jvp(…)``, ``transpose(…)``, ``checkpoint``,
    ``shard_map`` and ``pjit`` wrappers; of several paths joined by ``;``
    the first; None where the path holds no such name."""
    found = _scoped().findall(op_name.split(";", 1)[0])
    return found[-1] if found else None


def scopes_named(text: str) -> set[str]:
    """Every ``obs.trace.PHASES`` name that stands as a scope anywhere in a
    program's text, lowered (with its locations) or compiled."""
    return set(_scoped().findall(text))


def scope_table(hlo_text: str) -> dict[str, str | None]:
    """``{instruction name: innermost scope}`` of a compiled program's text
    (:func:`innermost_scope` of its ``op_name``), for every instruction that
    can appear as a device event: those of the entry, of loop bodies and
    conditions, of branches and of called computations — not the insides of
    a fused computation.  Names are as the device trace has them, without
    the ``%``.

    A fusion is put down to its heaviest work, not its root: where its fused
    computation holds a ``dot``, a ``convolution`` or a custom call under a
    scope, that instruction's scope, else the root's.  (A weight-gradient
    product is fused with the float32 add that accumulates it, and the add's
    ``op_name`` ends in ``grad_accum/microbatch``.)

    What the compiler made has no path of the program's: a fusion whose root
    is the compiler's cast (the end of GPT-2's cross-entropy backward, 4.9 %
    of its step) takes the scope most of its fused instructions carry, and
    any other instruction with no scope of its own — a relayout ``copy``, a
    ``reshape``, XLA's grouped matmul, whose rewrite names it
    ``ragged-dot-none`` and nothing else — the scope most of its operands
    have (the text lists operands before their users, so this runs along a
    chain of such instructions; a ``tuple``, a loop, a branch or a call takes
    none)."""
    insides: dict[str, list] = {}      # computation -> [(name, opcode, scope, is_root, callee, operands)]
    inside = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            head = _COMPUTATION.match(line)
            if head:
                inside = insides.setdefault(head.group(1), [])
            continue
        if inside is None:
            continue
        rest = line[m.end():]
        opcode = _OPCODE.search(rest)
        operands = _OPERAND.findall(rest[opcode.end():rest.find(")", opcode.end())]) if opcode else []
        opcode = opcode.group(1) if opcode else ""
        op_name = _OP_NAME.search(rest)
        callee = _FUSED.search(rest) if opcode == "fusion" else None
        inside.append((
            m.group(2), opcode, innermost_scope(op_name.group(1)) if op_name else None,
            bool(m.group(1)), callee.group(1) if callee else None, operands,
        ))
    fused = {callee for rows in insides.values() for *_, callee, _ in rows if callee}

    def most(scopes):
        return collections.Counter(scopes).most_common(1)[0][0] if scopes else None

    table: dict[str, str | None] = {}
    for computation, rows in insides.items():
        if computation in fused:
            continue
        for name, opcode, scope, _, callee, operands in rows:
            if callee:
                body = insides.get(callee, ())
                heavy = [s for _, op, s, *_ in body if op in _HEAVY and s]
                root = [s for _, _, s, is_root, *_ in body if is_root and s]
                scope = (heavy or root or [scope])[0] or most([s for _, _, s, *_ in body if s])
            if scope is None and opcode not in _STRUCTURAL:
                scope = most([table[o] for o in operands if table.get(o)])
            table[name] = scope
    return table


def scope_census(hlo_text: str) -> dict[str, int]:
    """Instructions by scope in a compiled program's text (:func:`scope_table`;
    ``none`` holds those under no scope): what a trace's events, laid against
    the table, can land in."""
    census: dict[str, int] = {}
    for scope in scope_table(hlo_text).values():
        census[scope or "none"] = census.get(scope or "none", 0) + 1
    return census


def mosaic_custom_calls(hlo_text: str) -> int:
    """How many Mosaic kernels the program's text holds, whatever their
    names (see :func:`mosaic_kernels`)."""
    return hlo_text.count('custom_call_target="tpu_custom_call"')


def _lookup(table, device_kind: str | None) -> float | None:
    if not device_kind:
        try:
            import jax

            device_kind = getattr(jax.devices()[0], "device_kind", "")
        except Exception:
            return None
    kind = device_kind.lower()
    for patterns, peak in table:
        if any(p in kind for p in patterns):
            return peak
    return None


def peak_flops_for(device_kind: str | None = None) -> float | None:
    """Peak FLOP/s for MFU accounting, None when unknown (CPU — callers
    pass an explicit override or report raw FLOP/s instead)."""
    return _lookup(PEAK_FLOPS, device_kind)


def require_peaks(device_kind: str | None = None) -> tuple[float, float]:
    """(bf16 FLOP/s, HBM bytes/s) of the device (default: the first one
    JAX reports).  Raises on a kind the tables do not hold: an MFU or a
    roofline against a guessed peak is a wrong number, not a rough one."""
    if not device_kind:
        import jax

        device_kind = jax.devices()[0].device_kind
    flops = _lookup(PEAK_FLOPS, device_kind)
    hbm = _lookup(PEAK_HBM_BYTES_PER_S, device_kind)
    if flops is None or hbm is None:
        raise ValueError(
            f"no peak FLOP/s / HBM bandwidth on record for device kind "
            f"{device_kind!r} (obs/cost.py PEAK_FLOPS, PEAK_HBM_BYTES_PER_S)"
        )
    return flops, hbm


def mfu(flops_per_step: float, step_time_s: float,
        peak_flops: float | None) -> float | None:
    """Model FLOPs utilization from *compiled* FLOPs (not a 6NT estimate):
    achieved FLOP/s over the hardware peak."""
    if not peak_flops or step_time_s <= 0:
        return None
    return flops_per_step / step_time_s / peak_flops


def step_cost_report(
    compiled: Any, *, peak_flops: float | None = None,
    with_census: bool = True,
) -> dict[str, Any]:
    """The ``compiled_cost`` event payload for one compiled train step."""
    report: dict[str, Any] = dict(compiled_cost(compiled))
    mem = memory_stats(compiled)
    if mem:
        report["memory"] = mem
    if with_census:
        try:
            text = compiled.as_text()
            report["collectives"] = collective_census(text)
            report["mosaic_custom_calls"] = mosaic_custom_calls(text)
            report["mosaic_kernels"] = mosaic_kernels(text)
        except Exception:
            pass
    report["peak_flops"] = (
        peak_flops if peak_flops is not None else peak_flops_for()
    )
    return report


# ---------------------------------------------------------------------- #
# analytic HBM byte model (graftcheck pass 3's memory audit)
# ---------------------------------------------------------------------- #
#
# The audit (analysis/reshard_audit.py) pins ``compiled.memory_analysis()``
# — whose argument/alias/temp sizes are PER-DEVICE — against the model
# built from these primitives.  The split of exact vs estimated:
#
# - argument and alias bytes are EXACT functions of the program's declared
#   layout (each leaf's global bytes over its PartitionSpec's shard
#   factor), so the audit pins them with equality — this is what catches
#   the silent classes: opt slots compiled replicated under zero1, a
#   donation that stopped aliasing, a KV pool at the wrong layout/tp;
# - the temp (activation working set) is XLA's to choose, so the model
#   carries a coarse ESTIMATE and the audit pins only the peak TOTAL
#   within a relative tolerance — wide enough to absorb fusion choices,
#   tight enough that a doubled pool or un-aliased state blows through.


def spec_shard_factor(spec: Any, mesh: Any) -> int:
    """Number of distinct shards a PartitionSpec tiles an array into over
    ``mesh`` — the divisor from global bytes to per-device bytes."""
    factor = 1
    for entry in spec:
        if entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        for ax in axes:
            factor *= mesh.shape.get(ax, 1)
    return factor


def _leaf_bytes(leaf: Any) -> int:
    import numpy as np

    shape = tuple(getattr(leaf, "shape", ()))
    n = 1
    for d in shape:
        n *= int(d)
    return n * np.dtype(leaf.dtype).itemsize


def tree_bytes_per_device(
    tree: Any, *, mesh: Any = None, rules: Any = None,
    shardings: Any = None,
) -> int:
    """Per-device bytes of a pytree of arrays / ShapeDtypeStructs.

    Layout intent comes from ``rules`` (a ``ShardingRules`` applied per
    path, the analytic route) or an explicit matching ``shardings`` tree
    of NamedShardings; with neither, every leaf counts full (replicated).
    This is the model-side mirror of ``memory_analysis()``'s per-device
    argument accounting.
    """
    import jax

    if rules is not None and mesh is not None:
        from ..parallel.sharding import infer_params_sharding

        shardings = infer_params_sharding(tree, mesh, rules)
    if shardings is None:
        return sum(
            _leaf_bytes(l) for l in jax.tree_util.tree_leaves(tree)
        )
    total = 0
    for leaf, sh in zip(
        jax.tree_util.tree_leaves(tree),
        jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: hasattr(x, "spec")
        ),
    ):
        factor = spec_shard_factor(sh.spec, sh.mesh) if hasattr(
            sh, "spec"
        ) else 1
        total += _leaf_bytes(leaf) // factor
    return total


def kv_heads_shard(num_heads: int, tp: int) -> int:
    """Shard factor ``kv_cache_sharding`` achieves on the heads axis:
    ``tp`` when it divides the head count, else 1 (indivisible heads
    fall back to replication).  The ONE owner of that divisibility rule
    on the model side — ``kv_pool_model_bytes`` and the serving engine's
    ``memory_model`` both call it, so the rule cannot drift apart."""
    return tp if tp > 1 and num_heads % tp == 0 else 1


def kv_position_bytes(
    head_dim: int, *, itemsize: int = 4, dtype: str | None = None,
) -> int:
    """Bytes ONE (position, head) cache entry costs under a KV storage
    dtype (``--serve-kv-dtype``) — the ONE owner of the quantized
    per-position byte rule, shared by the pool model, the per-block
    model, and the engine's memory model so the dtype axis cannot drift
    between them.

    ``dtype=None`` prices native storage at ``itemsize`` (4 on the f32
    CPU proxy, 2 on a bf16 TPU pool); ``"bf16"`` pins 2 explicitly;
    ``"int8"`` / ``"int4"`` add the per-position-per-head bf16 scale the
    quantized pool stores alongside the payload
    (comm.compress.quantize_kv)."""
    if dtype is None:
        return head_dim * itemsize
    if dtype == "bf16":
        return head_dim * 2
    if dtype == "int8":
        return head_dim + 2
    if dtype == "int4":
        return head_dim // 2 + 2
    raise ValueError(f"unknown kv dtype {dtype!r} (bf16|int8|int4)")


def kv_pool_model_bytes(
    *, num_layers: int, num_heads: int, head_dim: int, max_len: int,
    num_slots: int = 0, paged: bool = False, num_blocks: int = 0,
    block_size: int = 0, itemsize: int = 4, tp: int = 1,
    index_bytes: int = 0, dtype: str | None = None,
) -> int:
    """Analytic per-device bytes of a KV-cache pool.

    Contiguous: ``L x 2(K,V) x (num_slots, H, max_len, Dh)``; paged:
    ``L x 2 x (num_blocks, H, block_size, Dh)``.  ``dtype`` prices the
    quantized paged storage (``kv_position_bytes`` — int8/int4 payload
    plus per-position bf16 scales).  K/V shard on the heads axis over
    ``tp`` (parallel/sharding.kv_cache_sharding) when divisible;
    ``index_bytes`` covers the replicated non-K/V leaves (flax cache
    indices and any host-fed control state)."""
    pos = kv_position_bytes(head_dim, itemsize=itemsize, dtype=dtype)
    if paged:
        kv = num_layers * 2 * num_blocks * num_heads * block_size * pos
    else:
        kv = num_layers * 2 * num_slots * num_heads * max_len * pos
    return kv // kv_heads_shard(num_heads, tp) + index_bytes


def kv_block_model_bytes(
    *, num_layers: int, num_heads: int, head_dim: int, block_size: int,
    itemsize: int = 4, dtype: str | None = None,
) -> int:
    """Bytes of ONE physical KV block across every layer's K and V —
    ``L x 2 x (H, block_size, Dh)`` at ``kv_position_bytes`` per entry
    (the dtype axis: a quantized pool's blocks shrink by the same
    factor everywhere the block travels — HBM, host-tier spills,
    sibling fetches).  The unit of the tiered-KV-store accounting: a
    host-tier spill/restore moves exactly this many bytes per block,
    and ``serve/kv_store.py``'s byte ledger is pinned EQUAL to
    ``stored_blocks x this`` (tests/test_serve_disagg.py,
    tests/test_serve_quant.py) so the host side of the cache-hierarchy
    capacity story stays as audited as the pass-3 HBM side."""
    return num_layers * 2 * num_heads * block_size * kv_position_bytes(
        head_dim, itemsize=itemsize, dtype=dtype
    )


def serve_activation_estimate(
    *, num_slots: int, width: int, hidden: int, num_heads: int,
    vocab: int, mask_len: int, cache_bytes: int = 0, itemsize: int = 4,
    head_dim: int = 0, kv_quant: bool = False,
) -> int:
    """Coarse working-set estimate for one serving forward of ``width``
    positions per slot: the qkv/mlp intermediates, attention scores over
    the cache window, and the logits row — per LAYER, which is also the
    peak (XLA reuses the buffers layer to layer) — plus one copy of the
    pool (``cache_bytes``): XLA writes the new K/V rows by scattering into
    a position-major relayout of every layer's K and V and relaying it
    back (the contiguous decode program's buffer assignment shows it; the
    paged and the interpreted kernels' programs hold one pool-sized copy
    too).  A QUANTIZED pool additionally materializes the dequantized f32
    K and V read windows (``(S, H, mask_len, Dh)`` each) on the XLA gather
    path — the fused kernels dequantize per block tile in VMEM instead,
    which is the point of in-kernel dequantization.  Calibrated against
    CPU XLA's ``temp_size_in_bytes`` on the audit micro models; the audit
    consumes it only inside the peak-total tolerance."""
    per_pos = 3 * hidden + 4 * hidden + vocab + num_heads * mask_len
    est = num_slots * width * per_pos * itemsize + cache_bytes
    if kv_quant:
        est += 2 * num_slots * num_heads * mask_len * head_dim * 4
    return est


def train_activation_estimate(
    *, param_bytes_per_device: int, batch_rows_per_device: int,
    seq_len: int, vocab: int, itemsize: int = 4,
) -> int:
    """Coarse fwd+bwd working-set estimate for one train step: the
    gradient tree plus the logits row, counted twice (forward value +
    backward cotangent) — the two terms that dominate at every scale.
    Consumed only inside the memory audit's peak-total tolerance."""
    logits = batch_rows_per_device * seq_len * vocab * itemsize
    return 2 * (param_bytes_per_device + logits)


def memory_totals(mem: dict[str, int]) -> int:
    """Peak-footprint scalar from a ``memory_stats()`` dict: live
    arguments + non-aliased outputs + XLA temp scratch.  (Donated buffers
    appear in both arguments and outputs but alias_size removes the
    double count.)"""
    return (
        mem.get("argument_size_in_bytes", 0)
        + mem.get("output_size_in_bytes", 0)
        - mem.get("alias_size_in_bytes", 0)
        + mem.get("temp_size_in_bytes", 0)
    )


def dcn_step_counters(
    *,
    grad_sync: Any | None = None,
    mesh: Any | None = None,
    params: Any | None = None,
    mode: str = "flat",
    n_slices: int | None = None,
    num_microbatches: int = 1,
) -> dict[str, float]:
    """Per-step counters for the analytic DCN byte model, one sync spelled
    the way the configured ``--grad-sync`` mode moves it.

    With a ``GradSync`` engine, the counters come straight off the engine
    (its padded bucket layout and overlap contract).  For the flat GSPMD
    path there is no engine — the model is evaluated on the raw parameter
    count over the mesh's detected (or overridden) slice split, so a flat
    run's counters stay comparable to a hier run's.
    """
    if grad_sync is not None:
        per_sync = grad_sync.dcn_bytes_per_sync()
        syncs = grad_sync.syncs_per_step(num_microbatches)
        return {
            "dcn_bytes": float(per_sync * syncs),
            # Per-fabric split: the within-slice (ICI) bytes of the same
            # sync — RS + AG phases plus the multi-path stripe rotations
            # (``comm.striping.ici_bytes_per_sync``), so the telemetry
            # can price each fabric's share of the sync wall separately.
            "ici_bytes": float(grad_sync.ici_bytes_per_sync() * syncs),
            "dcn_syncs": float(syncs),
        }
    if mesh is None or params is None:
        raise ValueError("flat-mode counters need mesh and params")
    import jax

    from ..comm.hierarchical import dcn_bytes_per_sync
    from ..comm.mesh import AXIS_DATA, dcn_axis_name, ici_axis_name, \
        split_slice_mesh

    smesh = split_slice_mesh(mesh, axis=AXIS_DATA, n_slices=n_slices)
    slices = smesh.shape[dcn_axis_name(AXIS_DATA)]
    ici = smesh.shape[ici_axis_name(AXIS_DATA)]
    n_elems = sum(
        x.size for x in jax.tree_util.tree_leaves(params)
    )
    # One sync per optimizer step regardless of accumulation (the
    # engine-less path has no per-microbatch overlap to multiply by).
    # No ici_bytes entry: the flat GSPMD psum's within-slice staging is
    # XLA's lowering choice, not a modeled transfer.
    return {
        "dcn_bytes": float(dcn_bytes_per_sync(n_elems, slices, ici, mode)),
        "dcn_syncs": 1.0,
    }


# Within-slice fabric constants for the sync wall model, the ICI-side
# counterparts of ``comm.compress.DCN_LATENCY_S``/``DCN_BYTES_PER_S``:
# per-link ICI bandwidth is ~2 orders over DCN and its launch latency ~2
# orders under, which is exactly why the serialized RS → AR → AG walk
# leaves the expensive fabric idle most of the wall.
ICI_LATENCY_S = 1e-6
ICI_BYTES_PER_S = 100e9


def grad_sync_wall_model(
    *,
    ici_bytes: float,
    dcn_bytes: float,
    n_buckets: int,
    n_slices: int,
    ici_size: int,
    stripe: int = 1,
    phase_overlap: bool = False,
) -> dict[str, float]:
    """Overlap-aware analytic wall for ONE sync, per fabric.

    Per-bucket fabric occupancies, from the per-fabric byte models
    (``ici_bytes`` = ``comm.striping.ici_bytes_per_sync``, ``dcn_bytes``
    = ``comm.hierarchical.dcn_bytes_per_sync``, both fabric totals for
    the whole sync):

    * **ICI**: the RS and AG rings run their links concurrently — one
      launch each plus the bucket's share of the fabric bytes over the
      ``S x L`` concurrently-active links.
    * **DCN**: one launch plus the bucket's per-rail payload over the
      crossing edge(s).  Serialized transport puts rail *r*'s payload on
      edge *r* alone; multi-path striping spreads it over ``stripe``
      edges concurrently (FlexLink, arXiv:2510.15882), dividing the
      per-payload serialization ``stripe``-fold.

    The schedule then prices as a two-resource pipeline over the bucket
    walk: serialized phases cost the SUM of the fabrics every bucket,
    ``nb·(u+v)``; the phase-pipelined wavefront (--grad-sync-overlap)
    costs the MAX of the fabric totals plus one fill/drain bubble (the
    smaller fabric's single-bucket time), ``nb·max(u,v) + min(u,v)``.
    ``wall_s`` is the configured schedule's wall; both are always
    reported so the telemetry can show the sum-vs-max gap.
    """
    nb = max(int(n_buckets), 1)
    k = max(int(stripe), 1)
    links = max(n_slices * ici_size, 1)
    u = 2 * ICI_LATENCY_S + (ici_bytes / nb) / (links * ICI_BYTES_PER_S)
    from ..comm.compress import DCN_BYTES_PER_S, DCN_LATENCY_S

    rail_bytes = (dcn_bytes / nb) / max(ici_size, 1)
    v = DCN_LATENCY_S + rail_bytes / (k * DCN_BYTES_PER_S)
    wall_serial = nb * (u + v)
    wall_overlap = nb * max(u, v) + min(u, v)
    return {
        "ici_per_bucket_s": u,
        "dcn_per_bucket_s": v,
        "wall_serial_s": wall_serial,
        "wall_overlap_s": wall_overlap,
        "bubble_s": min(u, v),
        "wall_s": wall_overlap if phase_overlap else wall_serial,
        "overlap_ratio": wall_serial / wall_overlap,
    }


def pp_step_counters(
    *,
    schedule: str,
    num_stages: int,
    num_microbatches: int,
    microbatch_rows: int,
    seq_len: int,
    hidden: int,
    act_itemsize: int = 4,
    mode: str = "none",
    num_chunks: int = 1,
    n_slices: int | None = None,
) -> dict[str, float]:
    """Per-step counters for the pipeline stage-boundary byte model
    (``comm.compress.pp_boundary_bytes_per_step``), the ``--pp-compress``
    face of the DCN accounting spine.

    ``pp_boundary_bytes`` counts EVERY ppermute payload byte the step's
    tick loops move (both directions, wrap edge included) — pinned against
    the model in tests/test_obs.py.  ``pp_dcn_bytes`` is the share on
    edges that cross an ICI-slice boundary: with stages laid out
    contiguously per slice, ``n_slices`` of the ring's ``num_stages``
    edges cross (0 on single-slice/CPU device sets — detected when not
    given).
    """
    from ..comm.compress import pp_boundary_bytes_per_step
    from ..comm.mesh import num_slices as _num_slices

    total = pp_boundary_bytes_per_step(
        schedule=schedule, num_stages=num_stages,
        num_microbatches=num_microbatches, microbatch_rows=microbatch_rows,
        seq_len=seq_len, hidden=hidden, act_itemsize=act_itemsize,
        mode=mode, num_chunks=num_chunks,
    )
    if n_slices is None:
        n_slices = _num_slices()
    crossing = min(n_slices, num_stages) if n_slices > 1 else 0
    return {
        "pp_boundary_bytes": float(total),
        "pp_dcn_bytes": float(total * crossing // num_stages),
    }
