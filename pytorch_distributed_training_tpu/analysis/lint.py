"""graftcheck pass 1: AST lint for jit-safety and device-invariant bugs.

Every rule here is a bug class this repo has actually shipped (or nearly
shipped) and re-found at runtime — the point of the linter is that each
of those classes is *statically detectable*, so the next regression dies
in review instead of in a chip session:

- ``tracer-leak``      — ``.item()`` / ``float()`` / ``np.asarray`` on a
  traced value inside a ``jit``/``shard_map``/``scan`` body: a trace-time
  crash at best, a silently-baked constant at worst.
- ``host-commit``      — ``jnp.asarray`` on an operand fed to an
  AOT-compiled executable: commits the array to one device and fails (or
  worse, silently resolves) the compiled call's sharding contract — the
  PR 8 tensor-parallel serving bug class (serve/engine.py ``_dev``).
- ``select-gate``      — ``jnp.where`` gating a whole-pytree update from
  a shared predicate (a ``tree_map`` of selects): XLA is free to re-fuse
  each branch with the select and drift numerics — the PR 5 skip-step
  lesson (resilience/anomaly.py); use ``lax.cond``.
- ``donated-reuse``    — reading an argument you donated after the call:
  XLA owns (and may have freed or overwritten) that buffer — the PR 5
  restored-checkpoint segfault class.
- ``debug-stray``      — ``jax.debug.print`` / ``breakpoint()`` /
  ``pdb`` left in library code: a host callback in a steady-state
  program (and a compile break on some backends).
- ``axis-literal``     — raw mesh-axis string literals at collective
  call sites where ``comm.mesh`` constants and ``comm.collectives``
  helpers exist: a typo'd axis is a silent wrong-group reduce.
- ``host-entropy``     — Python ``random``/``time``/``np.random`` inside
  traced code: traces bake the first draw into the executable, so every
  step replays it.

The analysis is **per-module and syntactic** — no imports are executed.
Traced context is inferred from what the module does with a function:
decorating or wrapping it in ``jax.jit`` / ``shard_map`` / ``lax.scan``
(etc.), passing it to one of those by name, defining it inside an
already-traced function, or calling/passing it from one (a fixpoint over
the module's name→def map).  Cross-module tracing is out of scope by
design: the importing module sees its own call sites, the imported
module its own defs.

Escape hatch: a ``graftcheck: disable=<id>[,<id2>] — why`` comment on
the offending line or the line above suppresses those rules there; a
``graftcheck: disable-file=<id>`` comment near the top of a file
suppresses a rule for the whole file.  Suppressions are deliberate and visible — the
linter's contract is that the live tree lints clean, so every disable is
a reviewed exception, not a default.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Iterable

from .findings import Finding
from .shardflow import SHARDFLOW_AST_RULES, run_ast_rules

# ---------------------------------------------------------------------- #
# rule registry
# ---------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class Rule:
    rule_id: str
    description: str
    fixit: str


RULES: dict[str, Rule] = {
    r.rule_id: r
    for r in (
        Rule(
            "tracer-leak",
            "host conversion of a traced value inside a traced function",
            "keep the value on device (jnp ops), or move the host "
            "conversion outside the traced region",
        ),
        Rule(
            "host-commit",
            "jnp.asarray on an operand fed to an AOT-compiled executable",
            "pass raw numpy (np.ascontiguousarray) and let the compiled "
            "call place it against its input sharding — see "
            "ServingEngine._dev",
        ),
        Rule(
            "select-gate",
            "jnp.where gating a whole-pytree update from a shared "
            "predicate",
            "use lax.cond: a select invites XLA to re-fuse the update "
            "per branch and drift numerics (resilience/anomaly.py)",
        ),
        Rule(
            "donated-reuse",
            "donated argument read again after the donating call",
            "rebind the name from the call's outputs; the donated buffer "
            "now belongs to XLA",
        ),
        Rule(
            "debug-stray",
            "debug host-callback or debugger left in library code",
            "remove it (or gate it behind an explicit debug flag)",
        ),
        Rule(
            "axis-literal",
            "raw mesh-axis string literal at a collective call site",
            "use the comm.mesh AXIS_* constants / comm.collectives "
            "helpers so a typo'd axis cannot silently reduce over the "
            "wrong group",
        ),
        Rule(
            "host-entropy",
            "Python-side random/time call inside a traced function",
            "thread jax.random keys / step counters through the trace; "
            "host draws are baked in at trace time",
        ),
        Rule(
            "host-clock-in-trace",
            "span start/stop or host clock read inside a traced function",
            "spans must bracket dispatch on the HOST (the traced body "
            "runs once, at trace time — a span there records compile "
            "time and bakes it in); move the span/clock outside the "
            "jit/shard_map/scan body, or use obs.trace.scope for a "
            "trace-time phase name",
        ),
        # Sharding-flow rules (graftcheck pass 3a): defined in
        # analysis/shardflow.py (one module owns the axis vocabulary),
        # registered here so the disable hatch / typo check / --enabled
        # filtering treat them exactly like the core rules.
        *(
            Rule(rule_id, description, fixit)
            for rule_id, description, fixit in SHARDFLOW_AST_RULES
        ),
        Rule(
            "metric-name",
            "emitter metric name not declared in the schema registry",
            "declare the name (with its instrument type) in "
            "obs/schema.py — a typo'd name silently forks a new time "
            "series instead of failing",
        ),
        Rule(
            "bad-disable",
            "disable comment naming an unknown rule",
            "fix the rule id — a typo'd disable suppresses nothing",
        ),
        Rule(
            "parse-error",
            "module failed to parse",
            "fix the syntax error so the module can be analyzed",
        ),
    )
}

# Wrapper callables whose function-valued argument becomes traced code.
_TRACE_WRAPPERS = frozenset({
    "jit", "pjit", "shard_map", "scan", "cond", "while_loop", "switch",
    "map", "associative_scan", "vmap", "pmap", "grad", "value_and_grad",
    "checkpoint", "remat", "custom_vjp", "custom_jvp", "eval_shape",
    "linearize", "vjp", "jvp", "make_jaxpr",
})

# Mesh axis names whose literals at collective call sites should be the
# comm.mesh constants instead (comm/mesh.py owns the vocabulary).
_MESH_AXIS_LITERALS = frozenset({
    "data", "fsdp", "expert", "pipeline", "sequence", "tensor",
    "data_dcn", "data_ici",
})

# Collective entry points (jax.lax spellings and the comm.collectives
# wrappers) whose axis argument the axis-literal rule inspects.
_COLLECTIVE_NAMES = frozenset({
    "psum", "pmean", "pmax", "pmin", "all_gather", "psum_scatter",
    "reduce_scatter", "ppermute", "all_to_all", "axis_index", "broadcast",
})

# Attribute accesses that mark an expression as static shape metadata —
# ``int(x.shape[0])`` is host math over trace-time constants, not a leak.
_STATIC_ATTRS = frozenset({
    "shape", "size", "ndim", "dtype", "itemsize", "nbytes",
})

# (stdlib module, attr) pairs; None = any attribute.  Matched only when
# the base name is actually bound to THAT stdlib module in this file —
# ``from jax import random`` binds the same name to a deterministic,
# device-safe namespace and must not fire.
_ENTROPY_CALLS = (
    ("random", None),       # any random.* call
    ("time", "time"),
    ("time", "perf_counter"),
    ("time", "monotonic"),
    ("time", "process_time"),
    ("time", "sleep"),
    ("datetime", "now"),
    ("datetime", "utcnow"),
)
_ENTROPY_MODULES = frozenset({"random", "time", "datetime"})

# Span-API entry points (obs/spans.py SpanRecorder methods + the
# obs/trace.py host-side promotion helpers) whose appearance inside a
# traced function is the host-clock-in-trace bug class: the traced body
# executes ONCE, at trace time, so a span recorded there measures
# compilation and replays forever.  Monotonic-clock reads are the same
# class (and the raw material spans are built from).  The names below
# are distinctive enough to fire on alone; the AMBIGUOUS ones (`span`
# collides with re.Match.span(), `annotate` with plotting APIs) only
# fire when called the span-API way — with a string span NAME as the
# first argument — so legal trace-time host work cannot false-positive.
_SPAN_CALLS = frozenset({
    "start_span", "end_span", "record_span", "phase_span",
    "step_annotation",
})
_SPAN_CALLS_AMBIGUOUS = frozenset({"span", "annotate"})
_CLOCK_ATTRS = frozenset({"monotonic", "perf_counter", "perf_counter_ns"})

# Emitter instrument methods whose first argument is a metric name the
# schema registry (obs/schema.py) must declare.  The registry is loaded
# by FILE PATH, never imported as a package module: obs/__init__ pulls
# jax, and the metric-name rule must run at --lint-only speed.
_METRIC_METHODS = frozenset({"gauge", "counter_add", "observe"})
_metric_checker = None  # lazily loaded check_metric_name, or False on failure


def _load_metric_checker():
    global _metric_checker
    if _metric_checker is None:
        import importlib.util

        schema_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "..", "obs",
            "schema.py",
        )
        try:
            spec = importlib.util.spec_from_file_location(
                "_graft_metric_schema", schema_path
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _metric_checker = mod.check_metric_name
        except Exception:
            _metric_checker = False  # registry unreadable: rule goes silent
    return _metric_checker or None


# Rule ids are kebab-case tokens terminated at whitespace: an ASCII
# "- why" reason after the id must read as the reason, not get swallowed
# into a bogus rule name (which would both fail to suppress and fire
# bad-disable).
_DISABLE_RE = re.compile(
    r"#\s*graftcheck:\s*disable(?P<scope>-file)?\s*=\s*"
    r"(?P<rules>[a-z0-9_-]+(?:\s*,\s*[a-z0-9_-]+)*)"
)


# ---------------------------------------------------------------------- #
# small AST helpers
# ---------------------------------------------------------------------- #


def _dotted(node: ast.AST) -> str:
    """``a.b.c`` for Name/Attribute chains, '' otherwise."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _tail(node: ast.AST) -> str:
    """The final component of a call target: ``jax.jit`` → ``jit``."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _base_name(node: ast.AST) -> str:
    """Leftmost Name of an expression (``x.a[0].b`` → ``x``), '' if none."""
    while True:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, (ast.Attribute, ast.Subscript)):
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func
        else:
            return ""


def _contains_static_access(node: ast.AST) -> bool:
    """Whether the expression reads shape metadata or ``len()`` anywhere —
    the marker for host math over trace-time constants."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in _STATIC_ATTRS:
            return True
        if (
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Name)
            and sub.func.id == "len"
        ):
            return True
    return False


def _is_compile_call(node: ast.AST) -> bool:
    """``<expr>.compile()`` — the AOT endpoint (possibly chained)."""
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == "compile"
    )


# ---------------------------------------------------------------------- #
# per-module analysis
# ---------------------------------------------------------------------- #


class _ModuleIndex(ast.NodeVisitor):
    """One walk collecting everything the rules need:

    - every FunctionDef with its enclosing-function chain,
    - the traced seed set (decorated / wrapped / passed to a tracer),
    - names bound from ``.compile()`` calls (AOT executables) and from
      ``jax.jit(..., donate_argnums=...)`` (donating jits).
    """

    def __init__(self):
        self.defs: dict[str, list[ast.FunctionDef]] = {}
        self.parents: dict[ast.AST, ast.AST] = {}
        self.traced_seeds: set[ast.FunctionDef] = set()
        # name (Name id or Attribute attr) → True for AOT executables
        self.aot_names: set[str] = set()
        # name → donate positions for jit-with-donate results
        self.donating: dict[str, tuple[int, ...]] = {}
        # (target names, callee) assignments resolved in finalize() once
        # every def is indexed.
        self._deferred_assigns: list[tuple[tuple[str, ...], str]] = []
        self._fn_stack: list[ast.FunctionDef] = []

    def finalize(self) -> None:
        """Resolve deferred assignments: a call to a local function whose
        body contains a ``.compile()`` call is a compile factory, and its
        assignment targets are AOT executables (the ServingEngine's
        ``self._prefill_fn, ... = self._compile()`` shape)."""
        factories = {
            name for name, defs in self.defs.items()
            if any(
                _is_compile_call(sub)
                for fn in defs for sub in ast.walk(fn)
            )
        }
        for names, callee in self._deferred_assigns:
            if callee in factories:
                self.aot_names.update(names)

    # -- structure ------------------------------------------------------

    def visit(self, node):
        for child in ast.iter_child_nodes(node):
            self.parents[child] = node
        return super().visit(node)

    def _visit_fn(self, node):
        self.defs.setdefault(node.name, []).append(node)
        node._graft_enclosing = list(self._fn_stack)  # type: ignore
        for dec in node.decorator_list:
            if self._is_tracer(dec) or (
                isinstance(dec, ast.Call) and (
                    self._is_tracer(dec.func)
                    or any(self._is_tracer(a) for a in dec.args)
                )
            ):
                self.traced_seeds.add(node)
        self._fn_stack.append(node)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def _is_tracer(self, node: ast.AST) -> bool:
        tail = _tail(node)
        if tail in ("partial",) and isinstance(node, ast.Call):
            return False
        return tail in _TRACE_WRAPPERS

    # -- traced seeds and AOT/donation bookkeeping ----------------------

    def visit_Call(self, node: ast.Call):
        tail = _tail(node.func)
        if tail in _TRACE_WRAPPERS:
            for arg in list(node.args) + [
                kw.value for kw in node.keywords
            ]:
                if isinstance(arg, ast.Name) and arg.id in self.defs:
                    self.traced_seeds.update(self.defs[arg.id])
                # functools.partial(jax.jit, ...)(fn) style and
                # partial(fn, ...) passed onward are covered by the
                # fixpoint (the partial call references fn by name).
        if tail == "partial":
            for arg in node.args:
                if self._is_tracer(arg):
                    # partial(jax.jit, static_argnums=...)(fn): treat any
                    # sibling Name args as traced functions too.
                    for other in node.args:
                        if (
                            isinstance(other, ast.Name)
                            and other.id in self.defs
                        ):
                            self.traced_seeds.update(self.defs[other.id])
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign):
        value = node.value
        # x = <...>.compile()  /  self._x = <...>.compile()  /
        # self._a, self._b = self._compile()  where the local _compile's
        # body holds the .compile() calls (the ServingEngine shape — the
        # compile site must not need to be ON the assignment line for the
        # host-commit / donated-reuse rules to know the names are AOT).
        if (
            _is_compile_call(value)
            or (
                isinstance(value, ast.Tuple)
                and any(_is_compile_call(el) for el in value.elts)
            )
        ):
            for tgt in node.targets:
                for el in (
                    tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
                ):
                    name = _tail(el)
                    if name:
                        self.aot_names.add(name)
        elif isinstance(value, ast.Call) and _tail(value.func):
            # Maybe a compile factory — resolvable only after every def
            # has been indexed (methods can be defined after their
            # callers), so defer to finalize().
            names = tuple(
                name for tgt in node.targets
                for el in (
                    tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
                )
                if (name := _tail(el))
            )
            if names:
                self._deferred_assigns.append((names, _tail(value.func)))
        # x = jax.jit(f, donate_argnums=...)
        if (
            isinstance(value, ast.Call)
            and _tail(value.func) in ("jit", "pjit")
        ):
            donated = _donate_positions(value)
            if donated:
                for tgt in node.targets:
                    name = _tail(tgt)
                    if name:
                        self.donating[name] = donated
        self.generic_visit(node)


def _donate_positions(jit_call: ast.Call) -> tuple[int, ...]:
    for kw in jit_call.keywords:
        if kw.arg == "donate_argnums":
            val = kw.value
            if isinstance(val, ast.Constant) and isinstance(val.value, int):
                return (val.value,)
            if isinstance(val, (ast.Tuple, ast.List)):
                out = tuple(
                    el.value for el in val.elts
                    if isinstance(el, ast.Constant)
                    and isinstance(el.value, int)
                )
                if out:
                    return out
    return ()


def _traced_functions(index: _ModuleIndex) -> set[ast.FunctionDef]:
    """Fixpoint over the module's defs: traced seeds, their nested defs,
    and every local function a traced function calls or passes by name."""
    traced: set[ast.FunctionDef] = set()
    frontier = list(index.traced_seeds)
    while frontier:
        fn = frontier.pop()
        if fn in traced:
            continue
        traced.add(fn)
        for sub in ast.walk(fn):
            if sub is not fn and isinstance(
                sub, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                if sub not in traced:
                    frontier.append(sub)
            if isinstance(sub, ast.Name) and isinstance(
                sub.ctx, ast.Load
            ):
                for cand in index.defs.get(sub.id, ()):
                    # Only adopt defs from an enclosing scope or module
                    # level — a same-named method elsewhere stays host.
                    enclosing = getattr(cand, "_graft_enclosing", [])
                    if (
                        not enclosing
                        or fn in enclosing
                        or any(
                            e in getattr(fn, "_graft_enclosing", [])
                            for e in enclosing
                        )
                        or cand in traced
                    ):
                        if cand not in traced:
                            frontier.append(cand)
    return traced


# ---------------------------------------------------------------------- #
# suppression comments
# ---------------------------------------------------------------------- #


def _suppressions(
    src: str,
) -> tuple[dict[int, set[str]], set[str], list[tuple[int, str]]]:
    """(line → disabled rules, file-wide disabled rules, raw entries).
    A line suppression covers its own line and the next (comment-above
    style); ``raw`` keeps (lineno, rule) so typo'd ids can be reported
    with a location."""
    per_line: dict[int, set[str]] = {}
    file_wide: set[str] = set()
    raw: list[tuple[int, str]] = []
    for lineno, line in enumerate(src.splitlines(), start=1):
        mo = _DISABLE_RE.search(line)
        if not mo:
            continue
        rules = {
            r.strip() for r in mo.group("rules").split(",") if r.strip()
        }
        raw.extend((lineno, r) for r in rules)
        if mo.group("scope"):
            file_wide |= rules
        else:
            per_line.setdefault(lineno, set()).update(rules)
            # Comment-above style covers the NEXT line too — but only
            # for comment-only lines: a trailing disable must not bleed
            # onto the following statement (which nobody reviewed).
            if line.lstrip().startswith("#"):
                per_line.setdefault(lineno + 1, set()).update(rules)
    return per_line, file_wide, raw


# ---------------------------------------------------------------------- #
# the rule visitors
# ---------------------------------------------------------------------- #


class _RuleRunner:
    def __init__(self, tree: ast.Module, src: str, path: str,
                 enabled: set[str]):
        self.tree = tree
        self.path = path
        self.enabled = enabled
        self.findings: list[Finding] = []
        self.index = _ModuleIndex()
        self.index.visit(tree)
        self.index.finalize()
        self.traced = _traced_functions(self.index)
        self.per_line, self.file_wide, self.raw_disables = \
            _suppressions(src)
        self.np_aliases = {"np", "numpy"}
        self.jnp_aliases = {"jnp"}
        # Names bound to the STDLIB entropy modules in this file.  Bound
        # at import sites only, so ``from jax import random`` (the
        # canonical jax.random idiom) never qualifies — an attribute
        # call through it is deterministic device code, not host entropy.
        self.entropy_names: dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "numpy":
                        self.np_aliases.add(alias.asname or "numpy")
                    if alias.name == "jax.numpy":
                        self.jnp_aliases.add(alias.asname or "jax.numpy")
                    if alias.name in _ENTROPY_MODULES:
                        self.entropy_names[
                            alias.asname or alias.name
                        ] = alias.name
                    if alias.name == "datetime":
                        # ``datetime.datetime.now`` — the module and the
                        # class share the attr surface we match.
                        self.entropy_names.setdefault(
                            alias.asname or "datetime", "datetime"
                        )
            elif isinstance(node, ast.ImportFrom):
                if node.module == "datetime":
                    for alias in node.names:
                        if alias.name == "datetime":
                            self.entropy_names[
                                alias.asname or "datetime"
                            ] = "datetime"

    def report(self, rule_id: str, node: ast.AST, message: str) -> None:
        if rule_id not in self.enabled or rule_id in self.file_wide:
            return
        lineno = getattr(node, "lineno", 0)
        if rule_id in self.per_line.get(lineno, ()):
            return
        rule = RULES[rule_id]
        self.findings.append(Finding(
            rule=rule_id, message=message, path=self.path, line=lineno,
            col=getattr(node, "col_offset", 0), fixit=rule.fixit,
        ))

    # -- context helpers ------------------------------------------------

    def _enclosing_traced(self, fn_chain: list[ast.AST]):
        for fn in reversed(fn_chain):
            if fn in self.traced:
                return fn
        return None

    def run(self) -> list[Finding]:
        self._walk(self.tree, [])
        # Sharding-flow AST rules ride the same runner so suppressions,
        # the enabled set, and bad-disable detection apply uniformly.
        run_ast_rules(self.tree, self.report)
        return self.findings

    def _walk(self, node: ast.AST, fn_chain: list[ast.AST]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn_chain = fn_chain + [node]
            self._check_function(node, fn_chain)
        for child in ast.iter_child_nodes(node):
            self._check_node(child, fn_chain)
            self._walk(child, fn_chain)

    # -- per-node rules -------------------------------------------------

    def _check_node(self, node: ast.AST, fn_chain: list[ast.AST]) -> None:
        traced_fn = self._enclosing_traced(fn_chain)
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name in ("pdb", "ipdb"):
                    self.report(
                        "debug-stray", node,
                        f"import {alias.name} in library code",
                    )
        if not isinstance(node, ast.Call):
            return
        dotted = _dotted(node.func)
        tail = _tail(node.func)

        # debug-stray: anywhere in library code.
        if dotted in ("jax.debug.print", "jax.debug.breakpoint"):
            self.report("debug-stray", node, f"{dotted} left in code")
        elif dotted in ("pdb.set_trace", "ipdb.set_trace") or (
            isinstance(node.func, ast.Name)
            and node.func.id == "breakpoint"
        ):
            self.report(
                "debug-stray", node, f"{dotted or 'breakpoint()'} left in "
                "code",
            )

        # axis-literal: collective called with a raw mesh-axis string.
        if tail in _COLLECTIVE_NAMES:
            for arg in list(node.args) + [k.value for k in node.keywords]:
                if (
                    isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)
                    and arg.value in _MESH_AXIS_LITERALS
                ):
                    self.report(
                        "axis-literal", node,
                        f"{tail}(..., {arg.value!r}) uses a raw axis "
                        "literal",
                    )
                elif isinstance(arg, (ast.Tuple, ast.List)) and any(
                    isinstance(el, ast.Constant)
                    and isinstance(el.value, str)
                    and el.value in _MESH_AXIS_LITERALS
                    for el in arg.elts
                ):
                    self.report(
                        "axis-literal", node,
                        f"{tail}(...) takes a tuple with raw axis "
                        "literals",
                    )

        # select-gate: tree_map whose mapped fn is a shared-predicate
        # jnp.where select.
        if tail in ("tree_map", "map") and dotted.endswith(
            ("tree_map", "tree.map", "tree_util.tree_map")
        ):
            if node.args:
                self._check_select_gate(node.args[0], node)

        # host-commit: jnp.asarray fed to an AOT executable.
        if tail in self.index.aot_names or (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in self.index.aot_names
        ):
            for arg in node.args:
                if self._is_jnp_asarray(arg):
                    self.report(
                        "host-commit", arg,
                        "jnp.asarray operand fed to AOT-compiled "
                        f"{tail} commits it to one device",
                    )

        # metric-name: instrument call whose metric name is undeclared in
        # obs/schema.py or used via the wrong instrument method.
        if (
            tail in _METRIC_METHODS
            and isinstance(node.func, ast.Attribute)
            and node.args
        ):
            self._check_metric_name(node, tail)

        # Rules active only inside traced functions.
        if traced_fn is None:
            return
        params = {
            a.arg for a in (
                traced_fn.args.args + traced_fn.args.posonlyargs
                + traced_fn.args.kwonlyargs
            )
        } if isinstance(
            traced_fn, (ast.FunctionDef, ast.AsyncFunctionDef)
        ) else set()

        # tracer-leak: host conversions of traced values.
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in ("item", "tolist")
        ):
            self.report(
                "tracer-leak", node,
                f".{node.func.attr}() inside traced "
                f"{traced_fn.name}()",
            )
        elif (
            isinstance(node.func, ast.Name)
            and node.func.id in ("float", "int", "bool")
            and node.args
            and not isinstance(node.args[0], ast.Constant)
            and not _contains_static_access(node.args[0])
            and _base_name(node.args[0]) in params
        ):
            self.report(
                "tracer-leak", node,
                f"{node.func.id}() on traced value "
                f"{_base_name(node.args[0])!r} inside "
                f"{traced_fn.name}()",
            )
        elif (
            _base_name(node.func) in self.np_aliases
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in (
                "asarray", "array", "ascontiguousarray", "copy",
            )
            and node.args
            and _base_name(node.args[0]) in params
        ):
            self.report(
                "tracer-leak", node,
                f"np.{node.func.attr}() pulls traced value "
                f"{_base_name(node.args[0])!r} to host inside "
                f"{traced_fn.name}()",
            )

        # host-entropy: python-side nondeterminism in traced code.
        base = _base_name(node.func)
        if isinstance(node.func, ast.Attribute):
            stdlib_mod = self.entropy_names.get(base)
            for mod, attr in _ENTROPY_CALLS:
                if stdlib_mod == mod and (
                    attr is None or node.func.attr == attr
                ):
                    self.report(
                        "host-entropy", node,
                        f"{_dotted(node.func)}() inside traced "
                        f"{traced_fn.name}() is baked in at trace time",
                    )
                    break
            if (
                base in self.np_aliases
                and isinstance(node.func.value, ast.Attribute)
                and node.func.value.attr == "random"
            ):
                self.report(
                    "host-entropy", node,
                    f"np.random.{node.func.attr}() inside traced "
                    f"{traced_fn.name}() is baked in at trace time",
                )

        # host-clock-in-trace: span bracketing (SpanRecorder methods /
        # the obs.trace host-side helpers) or a monotonic-clock read in
        # traced code — the traced body runs once, at trace time, so the
        # "span" would record compilation and bake it in.  Trace-time
        # phase names (obs.trace.scope / named_scope) are the sanctioned
        # alternative and do not fire.
        if tail in _SPAN_CALLS or (
            tail in _SPAN_CALLS_AMBIGUOUS
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            self.report(
                "host-clock-in-trace", node,
                f"{dotted or tail}() inside traced {traced_fn.name}() "
                "would record trace time, not run time",
            )
        elif (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _CLOCK_ATTRS
            and self.entropy_names.get(base) == "time"
        ):
            self.report(
                "host-clock-in-trace", node,
                f"{_dotted(node.func)}() inside traced "
                f"{traced_fn.name}() reads the host clock at trace time",
            )

    def _check_metric_name(self, node: ast.Call, method: str) -> None:
        """Purely syntactic: literal first args, the static prefix of
        f-string names, and ``labeled("name", ...)`` wrappers are checked
        against obs/schema.py; a name that only exists in a variable is
        checked wherever its literal origin is."""
        arg = node.args[0]
        if (
            isinstance(arg, ast.Call)
            and _tail(arg.func) == "labeled"
            and arg.args
        ):
            arg = arg.args[0]  # labeled("ttft_s", **view) → "ttft_s"
        dynamic = False
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            name = arg.value
        elif isinstance(arg, ast.JoinedStr):
            parts: list[str] = []
            for v in arg.values:
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    parts.append(v.value)
                else:
                    break
            name = "".join(parts)
            dynamic = True
            if not name:
                return  # no static prefix: nothing checkable
        else:
            return
        checker = _load_metric_checker()
        if checker is None:
            return
        problem = checker(name, method, dynamic=dynamic)
        if problem:
            self.report("metric-name", node, problem)

    def _is_jnp_asarray(self, node: ast.AST) -> bool:
        return (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in ("asarray", "array")
            and _base_name(node.func) in self.jnp_aliases
        )

    def _check_select_gate(self, fn_arg: ast.AST, call: ast.Call) -> None:
        bodies: list[tuple[set[str], ast.AST]] = []
        if isinstance(fn_arg, ast.Lambda):
            bodies.append((
                {a.arg for a in fn_arg.args.args}, fn_arg.body,
            ))
        elif isinstance(fn_arg, ast.Name):
            for cand in self.index.defs.get(fn_arg.id, ()):
                bodies.append((
                    {a.arg for a in cand.args.args}, cand,
                ))
        for own_params, body in bodies:
            for sub in ast.walk(body):
                # The bug class is SELECTING BETWEEN TWO TREE VERSIONS
                # (update-vs-old, both mapped leaves) on one shared
                # predicate — that's a gated state update and wants
                # lax.cond.  Masked accumulation (where(valid, a, 0.0))
                # keeps a constant branch and stays select-shaped by
                # design (the branch-free pipeline tick loop).
                if (
                    isinstance(sub, ast.Call)
                    and _tail(sub.func) == "where"
                    and _base_name(sub.func) in self.jnp_aliases
                    and len(sub.args) >= 3
                    and _base_name(sub.args[0]) not in own_params
                    and _base_name(sub.args[0]) != ""
                    and _base_name(sub.args[1]) in own_params
                    and _base_name(sub.args[2]) in own_params
                ):
                    self.report(
                        "select-gate", call,
                        "tree_map of jnp.where on a shared predicate "
                        f"({_base_name(sub.args[0])!r}) gates a whole "
                        "pytree update through a select",
                    )
                    return

    # -- per-function rule: donated-reuse -------------------------------

    def _check_function(self, fn, fn_chain) -> None:
        donating_calls: list[tuple[ast.Call, str]] = []
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            name = _tail(sub.func)
            donated: tuple[int, ...] = ()
            if name in self.index.donating:
                donated = self.index.donating[name]
            elif name in self.index.aot_names:
                # Project convention: the engine's AOT programs donate
                # the cache at position 1 (params, cache, ...).
                donated = (1,)
            for pos in donated:
                if pos < len(sub.args) and isinstance(
                    sub.args[pos], ast.Name
                ):
                    donating_calls.append((sub, sub.args[pos].id))
        for call, donated_name in donating_calls:
            self._check_donated_reuse(fn, call, donated_name)

    def _check_donated_reuse(self, fn, call: ast.Call, name: str) -> None:
        call_line = call.lineno
        rebound_at: int | None = None
        for sub in ast.walk(fn):
            if (
                isinstance(sub, ast.Name)
                and sub.id == name
                and isinstance(sub.ctx, ast.Store)
                and sub.lineno >= call_line
            ):
                if rebound_at is None or sub.lineno < rebound_at:
                    rebound_at = sub.lineno
        for sub in ast.walk(fn):
            if (
                isinstance(sub, ast.Name)
                and sub.id == name
                and isinstance(sub.ctx, ast.Load)
                and sub.lineno > call_line
                and (rebound_at is None or sub.lineno < rebound_at)
            ):
                self.report(
                    "donated-reuse", sub,
                    f"{name!r} was donated at line {call_line} and read "
                    "again here",
                )
                return


# ---------------------------------------------------------------------- #
# entry points
# ---------------------------------------------------------------------- #

DEFAULT_LINT_TARGETS = (
    "pytorch_distributed_training_tpu",
    "tools",
    "__graft_entry__.py",
)

_SKIP_DIRS = {"__pycache__", ".git", "csrc", ".claude"}


def lint_source(
    src: str, path: str = "<string>", *,
    enabled: Iterable[str] | None = None,
) -> list[Finding]:
    """Lint one module's source.  ``enabled`` restricts the rule set
    (default: all rules)."""
    enabled_set = set(enabled) if enabled is not None else set(RULES)
    unknown = enabled_set - set(RULES)
    if unknown:
        raise ValueError(f"unknown rules {sorted(unknown)}")
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding(
            rule="parse-error", message=f"unparseable module: {e}",
            path=path, line=e.lineno or 0,
            fixit=RULES["parse-error"].fixit,
        )]
    runner = _RuleRunner(tree, src, path, enabled_set)
    findings = runner.run()
    # A disable comment naming an unknown rule silently suppresses
    # nothing — surface the typo as its own finding.
    for lineno, rule_id in runner.raw_disables:
        if rule_id not in RULES:
            findings.append(Finding(
                rule="bad-disable",
                message=f"disable comment names unknown rule "
                        f"{rule_id!r}",
                path=path, line=lineno,
                fixit=RULES["bad-disable"].fixit,
            ))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def iter_python_files(targets: Iterable[str], root: str) -> list[str]:
    out: list[str] = []
    for target in targets:
        full = os.path.join(root, target)
        if os.path.isfile(full):
            out.append(full)
            continue
        for dirpath, dirnames, filenames in os.walk(full):
            dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
            for fname in sorted(filenames):
                if fname.endswith(".py"):
                    out.append(os.path.join(dirpath, fname))
    return out


def lint_paths(
    targets: Iterable[str] | None = None, *, root: str | None = None,
    enabled: Iterable[str] | None = None,
) -> list[Finding]:
    """Lint every ``.py`` under ``targets`` (files or directories,
    relative to ``root`` — default: the repo's own source tree)."""
    if root is None:
        root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    files = iter_python_files(targets or DEFAULT_LINT_TARGETS, root)
    findings: list[Finding] = []
    for path in files:
        with open(path, encoding="utf-8") as f:
            src = f.read()
        rel = os.path.relpath(path, root)
        findings.extend(lint_source(src, rel, enabled=enabled))
    return findings
