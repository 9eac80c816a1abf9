"""graftcheck pass 3 (shardcheck): sharding-flow lint, resharding census,
HBM memory audit.

Contract (ISSUE 10): the sharding AST rules and the coverage check have
firing + negative fixtures; the expected-inventory census admits every
live program's collectives and catches a deliberately-broken TP layout
(dropped row-split rule → GSPMD all-gather); the memory audit pins
``memory_analysis()`` to the analytic byte model with EQUALITY on the
argument/alias components and tolerance on the peak total — for the
train step under every --grad-sync mode, the zero1 leg, and all serving
programs (both pools, tp=1/tp=2), all read from the session-scoped
lowering cache.  Pass 2's audit of the same programs (donation, host
callbacks, the DCN crossing census, the recompile guard) reads that cache
here too: under ``--dist loadfile`` one file is one worker, and one worker
builds the cache once.
"""

import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorch_distributed_training_tpu.analysis import (
    KNOWN_AXES,
    PROGRAM_REGISTRY,
    check_rules_axes,
    check_tree_coverage,
    lint_source,
    memory_record,
    validate_memory_records,
)
from pytorch_distributed_training_tpu.analysis.hlo_audit import (
    GRAD_SYNC_MODES,
    audit_serving_engine,
    audit_train_program,
    parse_collectives,
    tp_allreduce_model,
)
from pytorch_distributed_training_tpu.analysis.reshard_audit import (
    DEFAULT_HBM_TOL,
    _exp,
    audit_program_memory,
    audit_program_reshard,
    match_inventory,
    memory_model_for,
)
from pytorch_distributed_training_tpu.obs.cost import (
    kv_pool_model_bytes,
    memory_totals,
    spec_shard_factor,
    tree_bytes_per_device,
)
from pytorch_distributed_training_tpu.parallel.sharding import (
    ShardingRules,
    serve_tp_mesh,
    serve_tp_rules,
    tp_rules_for,
)

jnp = jax.numpy


def _lint(snippet: str, **kw):
    return lint_source(textwrap.dedent(snippet), "fixture.py", **kw)


def _rules_of(findings):
    return [f.rule for f in findings]


# --------------------------------------------------------------------- #
# pass 3a: sharding AST rules (ride the pass-1 lint runner)
# --------------------------------------------------------------------- #


def test_shard_axis_unknown_fires_on_typo():
    findings = _lint("""
        from jax.sharding import PartitionSpec as P

        SPEC_A = P("tenosr", None)
        SPEC_B = P(None, ("data", "fsbp"))
    """)
    assert _rules_of(findings) == ["shard-axis-unknown"] * 2


def test_shard_axis_unknown_passes_known_axes():
    findings = _lint("""
        from jax.sharding import PartitionSpec as P

        SPEC_A = P("data", ("fsdp", "tensor"))
        SPEC_B = P("data_dcn", "data_ici")
        SPEC_C = P(None, axis)          # variables: not literals
        OTHER = range("nope")           # not a PartitionSpec call
    """)
    assert findings == []


def test_known_axes_mirrors_comm_mesh():
    """KNOWN_AXES is a literal (so the lint path stays jax-free) — pin it
    to the real comm.mesh derivation so the two can't drift."""
    from pytorch_distributed_training_tpu.comm.mesh import (
        MESH_AXES, dcn_axis_name, ici_axis_name,
    )

    derived = frozenset(MESH_AXES) | {
        name
        for axis in MESH_AXES
        for name in (dcn_axis_name(axis), ici_axis_name(axis))
    }
    assert KNOWN_AXES == derived


def test_shard_axis_unknown_disable_hatch():
    findings = _lint("""
        from jax.sharding import PartitionSpec as P

        # graftcheck: disable=shard-axis-unknown — exotic test mesh
        SPEC = P("rows")
    """)
    assert findings == []


def test_donate_no_out_shardings_fires_and_negative():
    findings = _lint("""
        import jax

        bad = jax.jit(f, donate_argnums=(0,), in_shardings=(s,))
        good = jax.jit(
            f, donate_argnums=(0,), in_shardings=(s,), out_shardings=(s,)
        )
        plain = jax.jit(f, donate_argnums=(0,))   # no shardings: fine
    """)
    assert _rules_of(findings) == ["donate-no-out-shardings"]


# --------------------------------------------------------------------- #
# pass 3a: classify() + coverage check
# --------------------------------------------------------------------- #


def test_explicit_empty_rule_is_terminal(devices8):
    """Regression (the spec_for fall-through fix): an explicit P() rule
    means acknowledged replication — it must NOT fall through to a
    fallback that would silently re-shard the leaf."""
    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(data=-1, fsdp=2), devices=devices8)
    rules = ShardingRules(
        rules=((r"table", P()),), fallback="fsdp", min_fsdp_size=1,
    )
    spec, reason = rules.classify("table", (1024, 64), mesh)
    assert spec == P() and reason == "rule-replicate"
    # The same leaf WITHOUT the rule does get fsdp-sharded.
    spec, reason = ShardingRules(
        rules=(), fallback="fsdp", min_fsdp_size=1,
    ).classify("table", (1024, 64), mesh)
    assert spec != P() and reason == "fallback"


def test_classify_reasons(devices8):
    mesh = serve_tp_mesh(2, devices=devices8)
    rules = tp_rules_for("gpt2")
    spec, reason = rules.classify("h/attn/qkv/kernel", (32, 96), mesh)
    assert reason == "rule" and "tensor" in str(spec)
    # Odd vocab: the wte rule matches but the shape refuses the split,
    # and the fsdp fallback is trivial on a TP-only mesh.
    _, reason = rules.classify("wte", (61, 32), mesh)
    assert reason == "rule-dropped"
    # No rule matches and nothing can shard: fall-through replication.
    _, reason = rules.classify("wpe", (48, 32), mesh)
    assert reason == "fallback-replicate"
    # serve_tp_rules makes that replication explicit.
    _, reason = serve_tp_rules().classify("wpe", (48, 32), mesh)
    assert reason == "rule-replicate"
    # A matched-but-dropped rule under a replicate fallback is still the
    # acknowledged indivisible case, not accidental fall-through.
    _, reason = ShardingRules(
        rules=((r"wte", P("tensor", None)),), fallback="replicate",
    ).classify("wte", (61, 32), mesh)
    assert reason == "rule-dropped"


def test_serve_tp_rules_placement_identical_to_tp_rules(devices8):
    """The explicit-replication ruleset must not MOVE anything: on the
    serving submesh every gpt2_124m leaf gets the same spec under
    serve_tp_rules as under tp_rules_for (the engine's pre-PR-10
    layout) — intent became explicit, placement did not change."""
    from pytorch_distributed_training_tpu.models import gpt2_124m

    mesh = serve_tp_mesh(2, devices=devices8)
    model = gpt2_124m()
    params = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
            train=False,
        )
    )["params"]
    old, new = tp_rules_for("gpt2"), serve_tp_rules()

    def check(path, leaf):
        p = "/".join(str(getattr(k, "key", k)) for k in path)
        assert old.spec_for(p, leaf.shape, mesh) == \
            new.spec_for(p, leaf.shape, mesh), p
        return leaf

    jax.tree_util.tree_map_with_path(check, params)


def test_coverage_check_fires_and_acknowledges(devices8):
    mesh = serve_tp_mesh(2, devices=devices8)
    big = {"table": jax.ShapeDtypeStruct((1024, 1024), jnp.float32)}
    rules = ShardingRules(rules=(), fallback="fsdp")
    findings, report = check_tree_coverage(
        big, mesh, rules, where="fixture"
    )
    assert _rules_of(findings) == ["shard-coverage"]
    assert "table" in findings[0].message
    assert report["leaves_by_reason"] == {"fallback-replicate": 1}
    # An explicit P() rule acknowledges the replication: clean.
    acked = ShardingRules(rules=((r"table", P()),), fallback="fsdp")
    findings, report = check_tree_coverage(
        big, mesh, acked, where="fixture"
    )
    assert findings == []
    assert report["leaves_by_reason"] == {"rule-replicate": 1}
    # Small leaves replicate for free — below the byte floor, no finding.
    small = {"bias": jax.ShapeDtypeStruct((64,), jnp.float32)}
    findings, _ = check_tree_coverage(
        small, mesh, rules, where="fixture"
    )
    assert findings == []
    # Replication-intent rulesets (DDP) are exempt wholesale.
    ddp = ShardingRules(rules=(), fallback="replicate")
    findings, _ = check_tree_coverage(big, mesh, ddp, where="fixture")
    assert findings == []


def test_check_rules_axes_flags_stale_constant():
    rules = ShardingRules(rules=((r"w", P("tensro", None)),))
    findings = check_rules_axes(rules, where="fixture")
    assert _rules_of(findings) == ["shard-axis-unknown"]
    assert check_rules_axes(serve_tp_rules(), where="live") == []


def test_shardflow_audit_live_tree_clean(devices8):
    """THE pass-3a gate: the real layouts — serve_tp_rules over
    gpt2_124m at tp=2, zero1 slots on the 2-slice mesh, the EF
    residual — all covered (sharded or explicitly replicated)."""
    from pytorch_distributed_training_tpu.analysis.shardflow import (
        run_shardflow_audit,
    )

    findings, report = run_shardflow_audit(tp=2)
    assert findings == [], [f.format() for f in findings]
    serve = report["serve/tp2-params"]["leaves_by_reason"]
    # wpe is the one explicit replication; wte the one acknowledged
    # indivisible drop; kernels/biases shard by rule.
    assert serve["rule-replicate"] == 1
    assert serve["rule-dropped"] == 1
    assert serve["rule"] > 50
    assert report["train/ef-residual"]["shard_factor"] == 8


# --------------------------------------------------------------------- #
# pass 3b: resharding census — synthetic-HLO fixtures
# --------------------------------------------------------------------- #

_TP_HLO_CLEAN = "\n".join([
    "HloModule fixture, entry_computation_layout={()->()}",
    '  %ar = f32[256]{0} all-reduce(f32[256]{0} %x), '
    'replica_groups={{0,1}}, op_name="jit(f)/proj/dot_general"',
])

_TP_HLO_RESHARD = _TP_HLO_CLEAN + "\n" + (
    '  %ag = f32[4096]{0} all-gather(f32[2048]{0} %w), '
    'replica_groups={{0,1}}, dimensions={0}, '
    'op_name="jit(f)/w2/reshape"'
)

_TP_EXPECTED = [
    _exp("all-reduce", "f32", 1, scope="dot_general", max_bytes=1024,
         reason="megatron row-parallel partial sum"),
]


def test_census_expected_collective_does_not_fire():
    findings, report = match_inventory(
        parse_collectives(_TP_HLO_CLEAN), _TP_EXPECTED, "fixture"
    )
    assert findings == []
    assert report["expected"][0]["found"] == 1


def test_census_unexpected_all_gather_fires():
    findings, _ = match_inventory(
        parse_collectives(_TP_HLO_RESHARD), _TP_EXPECTED, "fixture"
    )
    assert _rules_of(findings) == ["unexpected-reshard"]
    assert "all-gather" in findings[0].message


def test_census_missing_expected_fires():
    expected = [_exp("all-reduce", "f32", 2, scope="dot_general",
                     reason="two blocks expected")]
    findings, _ = match_inventory(
        parse_collectives(_TP_HLO_CLEAN), expected, "fixture"
    )
    assert _rules_of(findings) == ["missing-collective"]


def test_census_max_bytes_guard_rejects_param_sized_gather():
    """A param gather cannot hide in an activation-sized expected entry:
    the 16 KB gather exceeds the 4 KB bound and fires even though op and
    dtype match."""
    expected = [
        _exp("all-reduce", "f32", 1, scope="dot_general"),
        _exp("all-gather", "f32", (0, 1), max_bytes=4096,
             reason="activation gather allowance"),
    ]
    findings, _ = match_inventory(
        parse_collectives(_TP_HLO_RESHARD), expected, "fixture"
    )
    assert _rules_of(findings) == ["unexpected-reshard"]


_COMBINED_HLO = "\n".join([
    "HloModule fixture, entry_computation_layout={()->()}",
    '  %ar = (f32[16]{0}, f32[16,16]{1,0}, f32[]) all-reduce(%a, %b, %c), '
    'replica_groups={{0,1,2,3}}, op_name="jit(f)/grad/psum"',
])


@pytest.mark.parametrize("want, rules", [(3, []), (4, ["missing-collective"])],
                         ids=["counts-3", "missing-one-fires"])
def test_census_counts_each_operand_of_a_combined_all_reduce(want, rules):
    """XLA's combiner folds three psums into one tuple all-reduce: the
    census counts the three it carries, and a fourth that is not there
    is still missing."""
    expected = [_exp("all-reduce", "f32", want, scope="grad")]
    findings, report = match_inventory(
        parse_collectives(_COMBINED_HLO), expected, "fixture"
    )
    assert _rules_of(findings) == rules
    assert report["expected"][0]["found"] == 3
    assert [c["bytes"] for c in report["collectives"]] == [64, 1024, 4]


def test_census_overcount_fires():
    expected = [_exp("all-reduce", "f32", (1, 1), scope="dot_general")]
    doubled = _TP_HLO_CLEAN + "\n" + _TP_HLO_CLEAN.splitlines()[1]
    findings, _ = match_inventory(
        parse_collectives(doubled), expected, "fixture"
    )
    assert _rules_of(findings) == ["unexpected-reshard"]
    assert "exceeds" in findings[0].message


# --------------------------------------------------------------------- #
# pass 3b: the deliberately-broken compiled fixture
# --------------------------------------------------------------------- #


def _compile_tp_up_projection(devices, *, drop_consumer_rule: bool):
    """The dropped-``tp_rules_for``-entry failure mode in miniature: a
    column-split up-projection whose consumer keeps (or loses) the
    sharded layout.  With the consumer's rule intact the activation
    stays head-sharded end to end — ZERO collectives.  Drop it and the
    program boundary demands a replicated activation, so GSPMD re-forms
    the sharded tensor with an all-gather: the silent resharding class
    the census exists to catch.  (A dropped rule on a matmul's OWN
    operand is absorbed by the partitioner — it slices the replicated
    side and all-reduces the partials, same wire cost as megatron — so
    the boundary form is the minimal genuinely-observable break.)"""
    mesh = serve_tp_mesh(2, devices=devices)
    rep = NamedSharding(mesh, P())
    col = NamedSharding(mesh, P(None, "tensor"))
    x = jax.device_put(jnp.ones((4, 16)), rep)
    w1 = jax.device_put(jnp.ones((16, 32)), col)
    fn = jax.jit(
        lambda x, w1: jnp.tanh(x @ w1),
        out_shardings=rep if drop_consumer_rule else col,
    )
    return fn.lower(x, w1).compile()


def test_broken_tp_rules_caught_by_census(devices8):
    # The intact layout matches the tp-sharded expectation: no
    # collectives at all (the single-program analogue of the tp=1 pin).
    clean = _compile_tp_up_projection(devices8, drop_consumer_rule=False)
    findings, _ = match_inventory(
        parse_collectives(clean.as_text()), [], "tp-up"
    )
    assert findings == [], [f.message for f in findings]
    broken = _compile_tp_up_projection(devices8, drop_consumer_rule=True)
    lines = parse_collectives(broken.as_text())
    assert [l.op for l in lines] == ["all-gather"]
    findings, _ = match_inventory(lines, [], "tp-up")
    assert _rules_of(findings) == ["unexpected-reshard"]
    assert "all-gather" in findings[0].message


# --------------------------------------------------------------------- #
# pass 3b/3c over the REAL programs (session-scoped lowering cache)
# --------------------------------------------------------------------- #

ALL_PROGRAMS = [
    "train/step-flat", "train/step-hier", "train/step-hier-bf16",
    "train/step-hier-int8", "train/step-hier-int4",
    "train/step-hier-topk", "train/step-zero1",
    # Striped+overlapped variants (comm/striping.py): each codec's step
    # under multi-path DCN striping + the phase-pipelined bucket schedule
    # — same crossing bytes (pass 2), per-bucket × per-lane inventory
    # (pass 3).
    "train/step-hier-striped", "train/step-hier-bf16-striped",
    "train/step-hier-int8-striped", "train/step-hier-int4-striped",
    "train/step-hier-topk-striped",
    # Elastic (shrunk-world) variants (resilience/elastic.py): each
    # codec's step at the 4-device single-slice survivor mesh a shrink
    # resizes to — same census + HBM pins, so a resize cannot land on
    # an unaudited layout.
    "train/step-flat-elastic", "train/step-hier-elastic",
    "train/step-hier-bf16-elastic", "train/step-hier-int8-elastic",
    "train/step-hier-int4-elastic", "train/step-hier-topk-elastic",
    "serve/contig/prefill", "serve/contig/decode", "serve/contig/verify",
    "serve/paged/prefill", "serve/paged/decode", "serve/paged/verify",
    # Quantized paged pools (--serve-kv-dtype): int8 with the full
    # program set, int4 pinning the nibble-packed layout; plus the
    # fused chunked-prefill variant (Pallas kernels inside the lowered
    # programs, interpret mode on the CPU mesh).
    "serve/paged-int8/prefill", "serve/paged-int8/decode",
    "serve/paged-int8/verify",
    "serve/paged-int4/prefill", "serve/paged-int4/decode",
    "serve/paged-fusedpf/prefill", "serve/paged-fusedpf/decode",
    "serve/tp2/prefill", "serve/tp2/decode", "serve/tp2/verify",
    "serve/tp2-paged/prefill", "serve/tp2-paged/decode",
    "serve/tp2-paged/verify",
    # Disaggregated role engines (serve/disagg.py): one shared-substrate
    # tier, each role compiling ONLY its own programs.
    "serve/role-prefill/prefill",
    "serve/role-decode/decode", "serve/role-decode/verify",
]


def test_audit_cache_covers_the_matrix(audit_programs):
    assert sorted(audit_programs) == sorted(ALL_PROGRAMS)


@pytest.mark.parametrize("name", ALL_PROGRAMS)
def test_reshard_census_live_clean(audit_programs, name):
    """Zero unexpected-reshard on the live tree: every collective of
    every audited program matches the expected-inventory model, and
    every expected collective is present."""
    findings, report = audit_program_reshard(audit_programs[name])
    assert findings == [], [f.message for f in findings]
    # Every parsed collective was matched to an expected entry.
    assert all(
        c["expected"] is not None for c in report["collectives"]
    ), report["collectives"]


@pytest.mark.parametrize(
    "name",
    [p for p in ALL_PROGRAMS if p.startswith(("serve/contig",
                                              "serve/paged"))],
)
def test_tp1_serving_programs_carry_no_collectives(audit_programs, name):
    """The strongest census pin: a single-device serving replica has no
    business communicating at all."""
    assert parse_collectives(audit_programs[name].hlo_text) == []


def test_zero1_weight_update_sharding_materializes(audit_programs):
    """Regression pin for the zero1 drift fix: the compiled step carries
    the weight-update all-gathers (params re-formed replicated from the
    data-sharded update, arXiv:2004.13336), and donation aliases the
    WHOLE state — before ``state_shardings`` pinned the output layout,
    GSPMD returned some slots at a different sharding (no all-gather for
    them, broken aliasing, a re-layout every step)."""
    from pytorch_distributed_training_tpu.analysis.hlo_audit import (
        parse_alias_entries,
    )

    prog = audit_programs["train/step-zero1"]
    ags = [
        l for l in parse_collectives(prog.hlo_text)
        if l.op == "all-gather"
    ]
    assert len(ags) >= 10, "weight-update all-gathers missing"
    # Donation covers the WHOLE TrainState (50 leaves) — pre-fix the
    # drifted slots fell out of the alias set (36 covered).
    state = prog.context["state"]
    n_leaves = len(jax.tree_util.tree_leaves(state))
    assert len(parse_alias_entries(prog.hlo_text)) == n_leaves
    findings, report = audit_program_memory(prog)
    assert findings == [], [f.message for f in findings]
    model = report["model"]
    # The sharded slots are visible as per-device argument bytes: adam's
    # mu+nu would cost 2x params replicated; data-sharded they cost
    # 2x/8 ≈ params/4 per device.
    assert model["opt_state"] < model["params"] // 2


@pytest.mark.parametrize("name", ALL_PROGRAMS)
def test_memory_audit_live_pins(audit_programs, name):
    """The HBM pin for every live program: argument and donation-alias
    bytes EQUAL the analytic model; the peak total sits within the
    tolerance band."""
    findings, report = audit_program_memory(audit_programs[name])
    assert findings == [], [f.message for f in findings]
    measured, model = report["measured"], report["model"]
    assert measured["argument_size_in_bytes"] == model["arguments"]
    if measured["alias_size_in_bytes"]:
        assert measured["alias_size_in_bytes"] == model["aliased"]
        assert memory_totals(measured) == report["measured_total"]
    else:
        # Persistent-cache-deserialized executables zero the alias stat;
        # the audit must have fallen back to the header-proven model
        # bytes rather than failing the pin.
        assert report["alias_stats"] == "unavailable-deserialized"
    assert report["total_rel_err"] <= DEFAULT_HBM_TOL


class _FakeMem:
    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


class _FakeCompiled:
    def __init__(self, **kw):
        self._mem = _FakeMem(**kw)

    def memory_analysis(self):
        return self._mem


def _fake_prog(model, **measured):
    """An AuditProgram around stubbed memory stats, so the finding logic
    is exercised independent of the compilation cache's alias quirk."""
    from pytorch_distributed_training_tpu.analysis.hlo_audit import (
        AuditProgram,
    )

    prog = AuditProgram(
        name="fixture/prog", kind="train", compiled=_FakeCompiled(
            **measured
        ),
        hlo_text="HloModule fixture", signature="", context={},
    )
    return prog, model


def test_memory_audit_fires_on_model_mismatch(monkeypatch):
    """Firing fixture: measured stats drifted from the model (arguments
    off by a page, donation half-unaliased, peak 2x) produce the three
    finding kinds."""
    import pytorch_distributed_training_tpu.analysis.reshard_audit as ra

    model = {"arguments": 1000, "aliased": 400, "total": 1200}
    prog, model = _fake_prog(
        model,
        argument_size_in_bytes=1096, output_size_in_bytes=500,
        temp_size_in_bytes=2000, alias_size_in_bytes=200,
        generated_code_size_in_bytes=0,
    )
    monkeypatch.setattr(ra, "memory_model_for", lambda p: model)
    findings, report = ra.audit_program_memory(prog)
    assert sorted(_rules_of(findings)) == [
        "hbm-alias", "hbm-arguments", "hbm-peak",
    ]
    assert report["measured_total"] == 1096 + 500 - 200 + 2000


def test_memory_audit_deserialized_alias_fallback(monkeypatch):
    """A cache-deserialized executable zeroes alias_size; with the HLO
    header proving the aliasing, the audit substitutes the model bytes
    (no false hbm-alias) — but an EMPTY header (donation genuinely
    gone) still fails the pin."""
    import pytorch_distributed_training_tpu.analysis.reshard_audit as ra

    model = {"arguments": 1000, "aliased": 400, "total": 1400}
    measured = dict(
        argument_size_in_bytes=1000, output_size_in_bytes=420,
        temp_size_in_bytes=400, alias_size_in_bytes=0,
        generated_code_size_in_bytes=0,
    )
    prog, model = _fake_prog(model, **measured)
    prog.hlo_text = (
        "HloModule f, input_output_alias={ {0}: (0, {}, may-alias) }, x"
    )
    monkeypatch.setattr(ra, "memory_model_for", lambda p: model)
    findings, report = ra.audit_program_memory(prog)
    assert findings == [], [f.message for f in findings]
    assert report["alias_stats"] == "unavailable-deserialized"
    assert report["measured_total"] == 1000 + 420 - 400 + 400
    # No header entries: the zero alias is a REAL donation failure.
    prog2, model2 = _fake_prog(dict(model), **measured)
    monkeypatch.setattr(ra, "memory_model_for", lambda p: model2)
    findings, _ = ra.audit_program_memory(prog2)
    assert "hbm-alias" in _rules_of(findings)
    # PARTIAL failure: the donated tree has two leaves but the header
    # kept only one entry (the zero1 drift class) — the fallback must
    # refuse, not substitute the full model bytes.
    prog3, model3 = _fake_prog(dict(model), **measured)
    prog3.hlo_text = prog.hlo_text
    prog3.context = {"state": {"a": object(), "b": object()}}
    monkeypatch.setattr(ra, "memory_model_for", lambda p: model3)
    findings, report3 = ra.audit_program_memory(prog3)
    assert "hbm-alias" in _rules_of(findings)
    assert "alias_stats" not in report3


def test_memory_audit_tolerance_leg(audit_programs):
    """tol=0 makes the peak pin fire on the (nonzero) estimate error —
    the tolerance leg is live, not vacuous."""
    prog = audit_programs["serve/paged/prefill"]
    findings, _ = audit_program_memory(prog, tol=0.0)
    assert "hbm-peak" in _rules_of(findings)


# --------------------------------------------------------------------- #
# pass 3c: byte-model unit math
# --------------------------------------------------------------------- #


def test_kv_pool_model_bytes_layouts():
    # Contiguous: L*2*(S,H,max_len,Dh) f32.
    contig = kv_pool_model_bytes(
        num_layers=2, num_heads=2, head_dim=16, max_len=48, num_slots=2,
    )
    assert contig == 2 * 2 * 2 * 2 * 48 * 16 * 4
    # Paged: L*2*(num_blocks,H,block,Dh); same bytes when the pool is
    # sized to the contiguous equivalent (12 blocks x 8 = 2 slots x 48).
    paged = kv_pool_model_bytes(
        num_layers=2, num_heads=2, head_dim=16, max_len=48,
        paged=True, num_blocks=12, block_size=8,
    )
    assert paged == contig
    # TP shards the heads axis when divisible; indivisible replicates.
    assert kv_pool_model_bytes(
        num_layers=2, num_heads=2, head_dim=16, max_len=48, num_slots=2,
        tp=2,
    ) == contig // 2
    assert kv_pool_model_bytes(
        num_layers=2, num_heads=3, head_dim=16, max_len=48, num_slots=2,
        tp=2, index_bytes=12,
    ) == 2 * 2 * 2 * 3 * 48 * 16 * 4 + 12


def test_spec_shard_factor_and_tree_bytes(devices8):
    mesh = serve_tp_mesh(2, devices=devices8)
    assert spec_shard_factor(P(), mesh) == 1
    assert spec_shard_factor(P(None, "tensor"), mesh) == 2
    assert spec_shard_factor(P(("data", "tensor")), mesh) == 2
    tree = {
        "w": jax.ShapeDtypeStruct((16, 32), jnp.float32),
        "b": jax.ShapeDtypeStruct((32,), jnp.float32),
    }
    shardings = {
        "w": NamedSharding(mesh, P(None, "tensor")),
        "b": NamedSharding(mesh, P()),
    }
    assert tree_bytes_per_device(tree) == 16 * 32 * 4 + 32 * 4
    assert tree_bytes_per_device(tree, shardings=shardings) == \
        16 * 32 * 4 // 2 + 32 * 4


def test_serve_memory_model_components(audit_programs):
    """The engine's model decomposes the way the config says: paged and
    contiguous pools cost the same bytes at the audit sizing, TP halves
    the sharded components, and the closed-form pool bytes agree with
    the tree-derived ones (the drift check)."""
    contig = audit_programs["serve/contig/decode"]
    tp2 = audit_programs["serve/tp2/decode"]
    m1 = memory_model_for(contig)
    m2 = memory_model_for(tp2)
    assert m1["kv_cache"] == m1["kv_cache_model"]
    assert m2["kv_cache"] == m2["kv_cache_model"]
    assert m2["kv_cache"] < m1["kv_cache"]  # heads-sharded
    assert m2["params"] < m1["params"]      # TP-sharded kernels
    assert m1["aliased"] == m1["kv_cache"]  # the donated buffer is the pool


# --------------------------------------------------------------------- #
# memory-record schema + runner legs
# --------------------------------------------------------------------- #


def test_memory_record_schema_roundtrip():
    rec = memory_record(
        "serve/contig/decode",
        {"argument_size_in_bytes": 10, "alias_size_in_bytes": 4},
        {"arguments": 10, "aliased": 4, "total": 12},
    )
    validate_memory_records([rec])
    with pytest.raises(ValueError):
        validate_memory_records([dict(rec, findings_schema=1)])
    with pytest.raises(ValueError):
        validate_memory_records([dict(rec, measured="nope")])
    # The audit's corrected peak/rel_err ride as optional typed fields
    # (they carry the deserialized-alias fallback a reader recomputing
    # from the raw measured stats would miss).
    rec2 = memory_record(
        "serve/contig/decode",
        {"argument_size_in_bytes": 10},
        {"arguments": 10, "total": 12},
        measured_total=11, total_rel_err=0.0833,
    )
    assert rec2["measured_total"] == 11
    validate_memory_records([rec2])
    with pytest.raises(ValueError):
        validate_memory_records([dict(rec2, measured_total="11")])
    with pytest.raises(ValueError):
        validate_memory_records([dict(rec2, total_rel_err="big")])


# --------------------------------------------------------------------- #
# pass 2: the compiled-artifact audit over the real programs (the same
# session-scoped lowering cache: its readers share one worker's build)
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("mode", GRAD_SYNC_MODES)
def test_train_step_audit_clean(audit_programs, mode):
    """Donation covers every TrainState leaf, no host callbacks, and the
    DCN crossing census equals the analytic byte model (crossing >= the
    best-case bound for flat) — for every --grad-sync mode.  Reads the
    session-scoped lowering cache (conftest.audit_programs), the same
    artifacts pass 3's census/memory tests pin."""
    findings, report = audit_train_program(
        audit_programs[f"train/step-{mode}"]
    )
    assert findings == [], [f.message for f in findings]
    assert report["alias_entries"] == report["donated_leaves"]
    if mode != "flat":
        assert sum(report["dcn_crossing"].values()) == report["dcn_model"]
    # The compressed wire is visibly compressed: nothing f32 crosses DCN
    # except int8's per-bucket scales.
    if mode in ("hier-bf16", "hier-int4", "hier-topk"):
        assert "f32" not in report["dcn_crossing"], report["dcn_crossing"]


def test_bf16_wire_stays_narrow(audit_programs):
    """Regression pin for the wire-widening find: the hier-bf16 DCN hop
    crosses as u16 (bitcast bf16), NOT as f32 — XLA's convert motion
    would otherwise legally widen the payload and double the compressed
    hop's bytes."""
    _, report = audit_train_program(
        audit_programs["train/step-hier-bf16"]
    )
    crossing = report["dcn_crossing"]
    assert set(crossing) == {"u16"}
    assert crossing["u16"] == report["dcn_model"]


@pytest.fixture(scope="module")
def audit_engines(audit_programs):
    # The engines behind the cached serving programs — one per pool
    # layout/TP label, shared with pass 3's tests via the session cache.
    return {
        prog.context["label"]: prog.context["engine"]
        for prog in audit_programs.values()
        if prog.kind == "serve"
    }


@pytest.mark.parametrize("label", ["contig", "paged"])
def test_serving_programs_audit_clean(audit_engines, label):
    """All three AOT serving programs, both pool layouts: donation
    materialized for every cache leaf, zero host callbacks."""
    engine = audit_engines[label]
    findings, report = audit_serving_engine(engine, label)
    assert findings == [], [f.message for f in findings]
    assert set(report) == {"prefill", "decode", "verify"}
    n_cache = len(jax.tree_util.tree_leaves(engine.pool.cache))
    for entry in report.values():
        assert entry["alias_entries"] == n_cache
        assert entry["custom_calls"] == []
        assert entry["signature"]


@pytest.mark.parametrize("label", ["tp2", "tp2-paged"])
def test_serving_programs_audit_tp(audit_engines, label):
    """The TP satellite: on the simulated 8-device mesh, donation
    aliasing holds under NamedShardings and the head-sharded collective
    census matches the megatron model for all three programs."""
    engine = audit_engines[label]
    findings, report = audit_serving_engine(engine, label)
    assert findings == [], [f.message for f in findings]
    cfg = engine._decoder.cfg
    widths = {"prefill": engine.prefill_chunk, "decode": 1,
              "verify": engine.spec_k + 1}
    for name, entry in report.items():
        expect = tp_allreduce_model(
            num_layers=cfg.num_layers, num_slots=engine.num_slots,
            width=widths[name], hidden=cfg.hidden_dim,
        )
        assert entry["tp_allreduce_model"] == expect
        got = entry["collectives"]["all-reduce"]["by_dtype"]["f32"]
        assert got == expect, (name, got, expect)


def test_recompile_guard_full_scheduler_trace(audit_engines):
    """The recompile-count regression: a full ContinuousScheduler trace
    — admission, speculative decode, mid-decode cancellation, reset, and
    a second wave after the reset — compiles each AOT engine program
    exactly once (at construction), pinned via the signature registry."""
    from pytorch_distributed_training_tpu.serve import (
        ContinuousScheduler, Request, VirtualClock,
    )

    engine = audit_engines["paged"]
    engine.reset()

    # Deterministic drafting (the drafter is an injectable attribute):
    # every decode tick proposes a repeat of the last token, so the
    # VERIFY program is exercised on every tick regardless of what the
    # untrained model happens to emit.
    class _ScriptedDrafter:
        index = None

        def observe_prompt(self, prompt):
            pass

        def draft(self, history, k):
            return np.full((min(2, max(k, 0)),), history[-1], np.int32)

    real_drafter = engine.drafter
    engine.drafter = _ScriptedDrafter()
    base = PROGRAM_REGISTRY.snapshot()
    sigs = dict(engine.program_signatures)
    assert set(sigs) == {"prefill", "decode", "verify"}
    # Construction recorded each signature exactly once.
    for name, sig in sigs.items():
        assert PROGRAM_REGISTRY.counts(f"serve/{name}")[
            (f"serve/{name}", sig)
        ] == 1, (name, sig)

    clock = VirtualClock()
    sched = ContinuousScheduler(engine, clock=clock)
    rng = np.random.default_rng(5)
    pat = rng.integers(0, 61, (3,)).astype(np.int32)
    reqs = [
        Request(0, np.tile(pat, 5)[:12], 10),          # draftable tail
        # Admitted into the second slot at t=0, budget far beyond its
        # deadline: expires MID-DECODE (cancelled, not shed).
        Request(2, rng.integers(0, 61, 5).astype(np.int32), 30,
                deadline=0.5),
        Request(1, rng.integers(0, 61, 7).astype(np.int32), 8),
        Request(3, np.tile(pat, 4)[:9], 6),
    ]
    for r in reqs:
        assert sched.submit(r)
    for _ in range(100):
        if sched.idle:
            break
        sched.tick()
        clock.advance(0.2)
    assert sched.idle
    reasons = {r["id"]: r["finish_reason"] for r in sched.completed}
    assert reasons[2] == "cancelled"
    assert engine.spec_drafted_tokens > 0  # the verify program ran
    engine.reset()
    # Second wave on the SAME engine after reset.
    sched2 = ContinuousScheduler(engine, clock=VirtualClock())
    assert sched2.submit(Request(10, np.tile(pat, 5)[:12], 8))
    for _ in range(50):
        if sched2.idle:
            break
        sched2.tick()
    assert sched2.idle
    engine.drafter = real_drafter
    # The whole trace compiled NOTHING new.
    assert PROGRAM_REGISTRY.compiles_since(base) == {}
    assert engine.program_signatures == sigs


def test_build_audit_programs_filter(devices8):
    """--programs narrows the matrix BEFORE any lowering: a no-match
    filter builds nothing (and in particular constructs no engine)."""
    from pytorch_distributed_training_tpu.analysis.hlo_audit import (
        _selected, build_audit_programs,
    )

    assert build_audit_programs(programs=["no-such-program"]) == {}
    assert _selected("serve/contig/decode", ["serve/contig"])
    assert _selected("train/step-flat", None)
    assert not _selected("train/step-flat", ["serve"])


def test_graftcheck_runner_programs_filter(devices8, tmp_path, capsys):
    """Runner smoke for the pass-3 legs: --reshard --memory scoped to
    one cheap program exits clean, reports per-pass wall time, and
    emits schema-valid memory records through the obs spine."""
    from tools.graftcheck import main

    rc = main([
        "--reshard", "--memory", "--programs", "train/step-flat",
        "--metrics-dir", str(tmp_path / "m"), "--json",
    ])
    assert rc == 0
    import json as _json

    out = _json.loads(capsys.readouterr().out)
    assert list(out["report"]["reshard"]) == ["train/step-flat"]
    timing = out["report"]["timing_s"]
    assert {"lower", "reshard", "memory"} <= set(timing)
    assert "lint" not in timing  # pass-3 flags select ONLY those legs
    from pytorch_distributed_training_tpu.obs import (
        read_events, validate_events,
    )

    events = read_events(str(tmp_path / "m" / "events.rank00000.jsonl"))
    validate_events(events)
    recs = [
        {k: v for k, v in e.items()
         if k not in ("v", "t", "rank", "kind")}
        for e in events if e.get("record") == "graftcheck_memory"
    ]
    assert len(recs) == 1 and recs[0]["program"] == "train/step-flat"
    validate_memory_records(recs)
    assert events[-1]["graftcheck_memory_programs"] == 1


def test_infer_state_shardings_structure(devices8):
    """The pinning tree matches the TrainState pytree leaf-for-leaf,
    with opt slots placed by opt_rules and everything host-scalar
    replicated."""
    import optax

    from pytorch_distributed_training_tpu.comm import (
        MeshConfig, make_mesh,
    )
    from pytorch_distributed_training_tpu.models.gpt2 import (
        GPT2, GPT2Config,
    )
    from pytorch_distributed_training_tpu.parallel.sharding import (
        DDP_RULES, ZERO1_OPT_RULES,
    )
    from pytorch_distributed_training_tpu.train import (
        create_train_state, infer_state_shardings,
    )
    import dataclasses as dc

    mesh = make_mesh(MeshConfig(data=-1), devices=devices8)
    cfg = GPT2Config(
        vocab_size=64, max_seq_len=8, num_layers=1, num_heads=2,
        hidden_dim=16,
    )
    opt_rules = dc.replace(ZERO1_OPT_RULES, min_fsdp_size=1)
    state = create_train_state(
        GPT2(cfg=cfg), jax.random.PRNGKey(0),
        jnp.zeros((8, 8), jnp.int32), optax.adam(1e-3), mesh=mesh,
        rules=DDP_RULES, opt_rules=opt_rules,
        init_kwargs={"train": False},
    )
    sh = infer_state_shardings(
        state, mesh, rules=DDP_RULES, opt_rules=opt_rules
    )
    assert jax.tree_util.tree_structure(sh) == \
        jax.tree_util.tree_structure(state)
    assert sh.step.spec == P()
    opt_specs = {
        str(s.spec) for s in jax.tree_util.tree_leaves(
            sh.opt_state, is_leaf=lambda x: hasattr(x, "spec")
        )
    }
    assert any("data" in s for s in opt_specs), opt_specs
    param_specs = {
        str(s.spec) for s in jax.tree_util.tree_leaves(
            sh.params, is_leaf=lambda x: hasattr(x, "spec")
        )
    }
    assert param_specs == {"PartitionSpec()"}
