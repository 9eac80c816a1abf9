"""graftcheck static analysis: lint fixtures, findings schema, HLO audit.

Contract (ISSUE 9): every lint rule has a known-bad fixture that FIRES
it, the live tree lints clean, and the HLO audit's parsers read a
hand-written module right.  The compiled-artifact audit over the real
programs (donation aliasing, zero host callbacks, the crossing census
against the byte model, the recompile guard) reads the session's lowering
cache, so it lives beside pass 3's pins in tests/test_shardcheck.py: one
worker builds that cache.
"""

import textwrap

import jax
import numpy as np
import pytest

from pytorch_distributed_training_tpu.analysis import (
    Finding,
    RULES,
    abstract_signature,
    finding_from_record,
    finding_record,
    lint_paths,
    lint_source,
    validate_finding_records,
)
from pytorch_distributed_training_tpu.analysis.hlo_audit import (
    dcn_crossing,
    parse_alias_entries,
)

jnp = jax.numpy


def _rules_of(findings):
    return [f.rule for f in findings]


def _lint(snippet: str, **kw):
    return lint_source(textwrap.dedent(snippet), "fixture.py", **kw)


# --------------------------------------------------------------------- #
# pass 1: one firing fixture per rule
# --------------------------------------------------------------------- #


def test_tracer_leak_fires_on_host_conversions():
    findings = _lint("""
        import jax
        import jax.numpy as jnp
        import numpy as np

        def body(x, y):
            a = float(x)
            b = y.item()
            c = np.asarray(x)
            return a + b + c

        step = jax.jit(body)
    """)
    assert _rules_of(findings) == ["tracer-leak"] * 3


def test_tracer_leak_ignores_static_shape_math_and_host_fns():
    findings = _lint("""
        import jax
        import numpy as np

        def body(x):
            n = int(x.shape[0])       # static metadata: fine
            k = float(len(x.shape))   # static: fine
            return x * n * k

        step = jax.jit(body)

        def host(x):
            return float(x)           # never traced: fine
    """)
    assert findings == []


def test_host_commit_fires_on_aot_operand():
    findings = _lint("""
        import jax
        import jax.numpy as jnp

        class Engine:
            def setup(self, fn, x):
                self._decode_fn = jax.jit(fn).lower(x).compile()

            def step(self, tokens):
                return self._decode_fn(jnp.asarray(tokens))
    """)
    assert _rules_of(findings) == ["host-commit"]


def test_host_commit_fires_through_compile_factory():
    # The REAL ServingEngine shape: the .compile() calls live inside a
    # helper and the program names are tuple-assigned from its result —
    # the rule must still know those names are AOT executables.
    findings = _lint("""
        import jax
        import jax.numpy as jnp

        class Engine:
            def __init__(self, fn, x):
                self._prefill_fn, self._decode_fn = self._compile(fn, x)

            def _compile(self, fn, x):
                def aot(lowered):
                    return lowered.compile()

                return (
                    aot(jax.jit(fn).lower(x)),
                    aot(jax.jit(fn).lower(x)),
                )

            def step(self, tokens):
                return self._decode_fn(jnp.asarray(tokens))
    """)
    assert _rules_of(findings) == ["host-commit"]


def test_host_commit_passes_raw_numpy():
    findings = _lint("""
        import jax
        import numpy as np

        class Engine:
            def setup(self, fn, x):
                self._decode_fn = jax.jit(fn).lower(x).compile()

            def step(self, tokens):
                return self._decode_fn(np.ascontiguousarray(tokens))
    """)
    assert findings == []


def test_select_gate_fires_on_shared_predicate_tree_select():
    findings = _lint("""
        import jax
        import jax.numpy as jnp

        def gate(bad, new_state, old_state):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(bad, o, n), new_state, old_state
            )
    """)
    assert _rules_of(findings) == ["select-gate"]


def test_select_gate_ignores_masked_accumulation():
    # The branch-free pipeline tick's masked aux accumulation (one
    # constant branch) is select-shaped BY DESIGN — must not fire.
    findings = _lint("""
        import jax
        import jax.numpy as jnp

        def accumulate(valid, acc_tree, aux_tree):
            return jax.tree_util.tree_map(
                lambda acc, a: acc + jnp.where(valid, a, 0.0),
                acc_tree, aux_tree,
            )
    """)
    assert findings == []


def test_donated_reuse_fires_and_rebind_passes():
    findings = _lint("""
        import jax

        step = jax.jit(lambda s, b: s, donate_argnums=(0,))

        def bad(state, batch):
            out = step(state, batch)
            return state            # donated buffer read again

        def good(state, batch):
            state = step(state, batch)
            return state            # rebound: fine
    """)
    assert _rules_of(findings) == ["donated-reuse"]


def test_debug_stray_fires():
    findings = _lint("""
        import jax
        import pdb

        def f(x):
            jax.debug.print("x={x}", x=x)
            breakpoint()
            return x
    """)
    assert sorted(_rules_of(findings)) == ["debug-stray"] * 3


def test_axis_literal_fires_only_on_mesh_axis_names():
    findings = _lint("""
        from jax import lax

        def f(x, g):
            a = lax.psum(x, "data")
            b = lax.all_gather(x, ("data", "fsdp"), axis=0)
            c = lax.psum(x, g)          # variable axis: fine
            d = lax.psum(x, "rows")     # not a mesh axis: fine
            return a + b + c + d
    """)
    assert _rules_of(findings) == ["axis-literal"] * 2


def test_host_entropy_fires_in_traced_code_only():
    findings = _lint("""
        import random
        import time
        import jax
        import numpy as np

        def body(x):
            r = random.random()
            t = time.time()
            n = np.random.default_rng(0)
            return x + r + t

        step = jax.jit(body)

        def host_loader():
            return np.random.default_rng(time.time())   # host: fine
    """)
    assert sorted(_rules_of(findings)) == ["host-entropy"] * 3


def test_host_entropy_ignores_jax_random():
    # ``from jax import random`` binds the same NAME to a deterministic
    # device-safe namespace — the canonical jax.random idiom must not
    # fire (only the stdlib module does).
    findings = _lint("""
        import jax
        from jax import random

        def body(key, x):
            k1, k2 = random.split(key)
            return x + random.normal(k1, x.shape)

        step = jax.jit(body)
    """)
    assert findings == []


def test_host_clock_in_trace_fires_on_spans_and_clock_reads():
    # Span bracketing inside a traced body measures trace time once and
    # bakes it in — every SpanRecorder entry point fires, and so does the
    # raw monotonic-clock read spans are built from (time.monotonic also
    # fires host-entropy: it IS host entropy; the clock rule adds the
    # span-specific fixit).
    findings = _lint("""
        import time
        import jax

        def body(spans, x):
            s = spans.start_span("train/step")
            t0 = time.monotonic()
            spans.record_span("train/host_sync", t0, time.perf_counter())
            spans.end_span(s)
            return x * 2

        step = jax.jit(body)
    """)
    by_rule: dict = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    # start_span, end_span, record_span + two clock reads = 5 firings.
    assert len(by_rule["host-clock-in-trace"]) == 5
    assert all(
        "trace time" in f.message or "host clock" in f.message
        for f in by_rule["host-clock-in-trace"]
    )


def test_host_clock_in_trace_fires_on_ambiguous_names_with_span_args():
    # `span`/`annotate` are generic method names; they fire only when
    # called the span-API way — a string span name as the first arg.
    findings = _lint("""
        import jax

        def body(spans, x):
            with spans.span("serve/decode"):
                y = x * 2
            return y

        step = jax.jit(body)
    """)
    assert _rules_of(findings) == ["host-clock-in-trace"]


def test_host_clock_in_trace_negative_fixtures():
    # Host-side spans at dispatch boundaries (the sanctioned pattern),
    # trace-time scope names inside compiled code, and UNRELATED methods
    # that merely share a span-API name (re.Match.span()) all stay clean.
    findings = _lint("""
        import re
        import time
        import jax
        from pytorch_distributed_training_tpu.obs import scope

        def body(x):
            with scope("grad_sync/ar_dcn"):   # HLO metadata: fine
                y = x * 2
            m = re.match("a+", "aaa")
            lo, hi = m.span()                 # not the span API: fine
            return y[lo:hi]

        step = jax.jit(body)

        def tick(spans, step_fn, x):
            s = spans.start_span("train/step")     # host: brackets dispatch
            t0 = time.monotonic()                  # host clock: fine
            out = step_fn(x)
            spans.end_span(s, host_t0=t0)
            return out
    """)
    assert findings == []


def test_host_clock_in_trace_disable_hatch():
    findings = _lint("""
        import jax

        def body(spans, x):
            # graftcheck: disable=host-clock-in-trace — fixture
            s = spans.start_span("train/step")
            spans.end_span(s)  # graftcheck: disable=host-clock-in-trace
            return x

        step = jax.jit(body)
    """)
    assert findings == []


def test_traced_context_propagates_through_local_calls():
    # make_step's inner helper is reached from the traced fn by NAME —
    # the per-module fixpoint must mark it traced.
    findings = _lint("""
        import jax

        def make_step():
            def helper(x):
                return float(x)

            def step(x):
                return helper(x)

            return jax.jit(step)
    """)
    assert _rules_of(findings) == ["tracer-leak"]


def test_disable_comment_suppresses_and_typos_are_reported():
    clean = _lint("""
        import jax

        def body(x):
            # graftcheck: disable=tracer-leak — fixture
            return float(x)

        step = jax.jit(body)
    """)
    assert clean == []
    file_wide = _lint("""
        # graftcheck: disable-file=tracer-leak
        import jax

        def body(x):
            return float(x)

        step = jax.jit(body)
    """)
    assert file_wide == []
    typo = _lint("""
        import jax

        def body(x):
            # graftcheck: disable=tracer-beak
            return float(x)

        step = jax.jit(body)
    """)
    assert sorted(_rules_of(typo)) == ["bad-disable", "tracer-leak"]


def test_disable_with_ascii_hyphen_reason_still_suppresses():
    # "disable=<rule> - why" (ASCII hyphen reason): the id must parse as
    # the id, not swallow the reason into a bogus rule name that both
    # fails to suppress and fires bad-disable.
    findings = _lint("""
        import jax

        def body(x):
            # graftcheck: disable=tracer-leak - legacy host read
            return float(x)

        step = jax.jit(body)
    """)
    assert findings == []


def test_trailing_disable_does_not_bleed_to_next_line():
    # A trailing disable covers ITS line only; the unreviewed violation
    # on the following line must still fire (a comment-only disable line
    # is the one that covers the statement below it).
    findings = _lint("""
        import jax

        def body(x, y):
            a = float(x)  # graftcheck: disable=tracer-leak — reviewed
            b = float(y)
            return a + b

        step = jax.jit(body)
    """)
    assert _rules_of(findings) == ["tracer-leak"]
    assert "'y'" in findings[0].message  # the NEXT line's violation


def test_every_rule_documented():
    for rule_id, rule in RULES.items():
        assert rule.description and rule.rule_id == rule_id


def test_live_tree_is_clean():
    """THE gate: the repo's own sources carry zero lint findings (every
    legitimate exception has an inline disable with a why)."""
    findings = lint_paths()
    assert findings == [], "\n".join(f.format() for f in findings)


# --------------------------------------------------------------------- #
# findings schema
# --------------------------------------------------------------------- #


def test_finding_record_roundtrip():
    f = Finding(
        rule="tracer-leak", message="m", path="a/b.py", line=3, col=7,
        fixit="fix", analysis_pass="lint", severity="error",
    )
    rec = finding_record(f)
    validate_finding_records([rec])
    assert finding_from_record(rec) == f


def test_finding_record_rejects_drift():
    rec = finding_record(Finding(rule="r", message="m", path="p"))
    bad = dict(rec, findings_schema=99)
    with pytest.raises(ValueError):
        validate_finding_records([bad])
    with pytest.raises(ValueError):
        validate_finding_records([dict(rec, line="3")])
    with pytest.raises(ValueError):
        Finding(rule="r", message="m", path="p", analysis_pass="vibes")


def test_findings_flow_through_obs_emitter(tmp_path):
    from pytorch_distributed_training_tpu.obs import (
        MetricsEmitter, read_events, validate_events,
    )

    f = Finding(rule="host-commit", message="m", path="x.py", line=9)
    with MetricsEmitter(str(tmp_path), rank=0, world=1) as em:
        em.emit("record", finding_record(f))
        em.summary(graftcheck_findings=1)
    events = read_events(str(tmp_path / "events.rank00000.jsonl"))
    validate_events(events)
    recs = [e for e in events if e.get("record") == "graftcheck_finding"]
    assert len(recs) == 1
    got = {k: v for k, v in recs[0].items()
           if k not in ("v", "t", "rank", "kind")}
    validate_finding_records([got])
    assert finding_from_record(got) == f


# --------------------------------------------------------------------- #
# crossing-census unit math (no compilation)
# --------------------------------------------------------------------- #

_FAKE_HLO = "\n".join([
    "HloModule fake, input_output_alias={ {0}: (1, {}, may-alias), "
    "{1}: (2, {}, may-alias) }, entry_computation_layout={()->()}",
    # DCN all-gather: 4 groups of {i, i+4}, result 2x the 100-byte shard.
    "  %ag = u8[2,100]{1,0} all-gather(u8[1,100]{1,0} %p), "
    "replica_groups={{0,4},{1,5},{2,6},{3,7}}, dimensions={0}",
    # ICI-only reduce-scatter: groups within a slice, crosses nothing.
    "  %rs = f32[25]{0} reduce-scatter(f32[100]{0} %q), "
    "replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}",
    # Spanning all-reduce: 2.(S-1).bytes convention.
    "  %ar = f32[100]{0} all-reduce(f32[100]{0} %r), "
    "replica_groups={{0,1,2,3,4,5,6,7}}",
    # Crossing permute: one 400-byte payload over edges 3->4 and 7->0.
    "  %cp = f32[100]{0} collective-permute(f32[100]{0} %s), "
    "source_target_pairs={{3,4},{7,0},{0,1}}",
])


def test_dcn_crossing_conventions():
    got = dcn_crossing(_FAKE_HLO, n_devices=8, n_slices=2, min_bytes=0)
    # ag: shard 100 B x 2 cross pairs x 4 groups = 800 u8
    # ar: 2 x (2-1) x 400 B = 800 f32; rs: 0; cp: 2 x 400 = 800 f32
    assert got["by_dtype"] == {"u8": 800, "f32": 1600}
    assert got["total"] == 2400
    assert parse_alias_entries(_FAKE_HLO) == [1, 2]


def test_abstract_signature_tracks_calling_convention():
    def f(a, b):
        return a + b

    lowered = jax.jit(f).lower(jnp.zeros((4,)), jnp.zeros((4,)))
    again = jax.jit(f).lower(jnp.zeros((4,)), jnp.zeros((4,)))
    other = jax.jit(f).lower(jnp.zeros((8,)), jnp.zeros((8,)))
    assert abstract_signature(lowered) == abstract_signature(again)
    assert abstract_signature(lowered) != abstract_signature(other)


# --------------------------------------------------------------------- #
# the runner
# --------------------------------------------------------------------- #


def test_graftcheck_runner_lint_only(capsys):
    from tools.graftcheck import main

    assert main(["--lint-only"]) == 0
    out = capsys.readouterr().out
    assert "0 finding(s)" in out


def test_graftcheck_runner_flags_a_bad_tree(tmp_path, capsys):
    from tools.graftcheck import main

    bad = tmp_path / "mod.py"
    bad.write_text(
        "import jax\n\ndef f(x):\n    return float(x)\n\ng = jax.jit(f)\n"
    )
    rc = main([
        "--lint-only", "--root", str(tmp_path), "--paths", "mod.py",
        "--metrics-dir", str(tmp_path / "m"),
    ])
    assert rc == 1
    assert "tracer-leak" in capsys.readouterr().out
    from pytorch_distributed_training_tpu.obs import (
        read_events, validate_events,
    )

    events = read_events(str(tmp_path / "m" / "events.rank00000.jsonl"))
    validate_events(events)
    recs = [e for e in events if e.get("record") == "graftcheck_finding"]
    assert len(recs) == 1 and recs[0]["rule"] == "tracer-leak"
    summary = events[-1]
    assert summary["kind"] == "summary"
    assert summary["graftcheck_findings"] == 1
