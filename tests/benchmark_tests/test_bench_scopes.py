"""``benchmark/readers/scope_share.py`` and the fourteen entries that read it:
the program's scope table (``train/step.py::step_scopes``) joined to a device
trace's self times.  The trace here is synthetic — one second for every
instruction name of a tiny step compiled on the CPU — so only counts are held."""

import pytest

from benchmark.harness import load_json, load_manifest
from benchmark.readers import scope_share
from pytorch_distributed_training_tpu.obs.cost import scope_table
from pytorch_distributed_training_tpu.obs.trace import PHASES
from pytorch_distributed_training_tpu.train import step as step_module
from test_bench_nemotron_h import test_cells_keep_their_entries as pinned_sets
from test_scope_table import tiny_step

MANIFEST = load_manifest()
ENTRIES = [m for m in MANIFEST["per_layer"]
           if load_json("layers", m["name"] + ".json")["reader"] == "scope_share"]
# The rehearsal model that stands for each cell's configuration.
TINY_OF = {
    "gpt2-124m.train.1chip": "tiny-gpt2.train", "gpt2-124m.train.dp4": "tiny-gpt2.train",
    "vit-b16.train.1chip": "tiny-vit.train", "sdar-30b-a3b-chat.train.bd4k": "tiny-sdar.train.bd",
    "instella-moe-16b-a3b-base.train.causal8k": "tiny-instella.train.causal",
    "nemotron-labs-twotower-30b-a3b-base.train.causal8k": "tiny-nemotron-h.train.causal",
}
SMALL = ("train/mtp", "train/noise")      # scopes no entry reads: the ``scopes:`` line lists them


def facts_of(tiny, extra=()):
    """A reduced trace that gives every instruction of the tiny step one
    second under the name a TPU trace would give it, and the step's table."""
    table = scope_table(tiny_step(tiny)[-1])
    names = [f"%{name} = f32[8,8] fusion" for name in table] + list(extra)
    return {"trace": {"op_self_s": dict.fromkeys(names, 1.0), "busy_s": float(len(names))}}, table


def args_of(entry):
    return load_json("layers", entry["name"] + ".json")["args"]


def test_there_are_fourteen_entries_at_the_end():
    assert len(ENTRIES) == 14 and MANIFEST["per_layer"][-14:] == ENTRIES
    for m in ENTRIES:
        assert (m["unit"], m["better"], m["source"], m["moves"]) == ("%", "lower", "program_span", "train_mfu")
        scopes = args_of(m)["scopes"]
        assert scopes is None or set(scopes) <= set(PHASES)
    assert [m["name"] for m in ENTRIES if args_of(m)["scopes"] is None] == ["loop.unscoped_share.train"]


@pytest.mark.parametrize("tiny", sorted(set(TINY_OF.values())))
def test_the_shares_add_to_a_hundred(tiny):
    facts, table = facts_of(tiny)
    shares = {m["name"]: scope_share.read(facts, table=table, **args_of(m)) for m in ENTRIES}
    small = sum(scope_share.read(facts, [name], table=table) for name in SMALL)
    assert sum(shares.values()) + small == pytest.approx(100.0, abs=1e-9)
    census = {}
    for scope in table.values():
        census[scope] = census.get(scope, 0) + 1
    assert shares["loop.optimizer_share.train"] == pytest.approx(100.0 * census["train/optimizer"] / len(table))
    rest = sum(n for scope, n in census.items() if scope is None or scope in scope_share.WRAPPERS)
    assert shares["loop.unscoped_share.train"] == pytest.approx(100.0 * rest / len(table))


def test_an_unknown_name_lands_in_unscoped(capsys):
    facts, table = facts_of("tiny-gpt2.train")
    before = scope_share.read(facts, None, table=table)
    stale, _ = facts_of("tiny-gpt2.train", extra=["%fusion.99999 = f32[8] fusion", "no instruction at all"])
    n = len(table)
    assert scope_share.read(stale, None, table=table) == pytest.approx((before * n + 200.0) / (n + 2))
    assert scope_share.read(stale, ["train/optimizer"], table=table) < scope_share.read(facts, ["train/optimizer"], table=table)
    seconds, joined = scope_share.by_scope(stale["trace"]["op_self_s"], table)
    assert joined == n and sum(seconds.values()) == n + 2


def test_the_scopes_line_is_printed_once(capsys, monkeypatch):
    monkeypatch.setattr(scope_share, "_told", False)
    facts, table = facts_of("tiny-nemotron-h.train.causal")
    scope_share.read(facts, ["ssm/scan"], table=table)
    scope_share.read(facts, ["ssm/conv"], table=table)
    lines = [line for line in capsys.readouterr().out.splitlines() if line.startswith("scopes: ")]
    assert len(lines) == 1
    words = lines[0].split()
    assert words[1:3] == ["joined", "100.00"] and {"ssm/scan", "ssm/proj", "none", "train/loss"} <= set(words)
    assert sum(float(x) for x in words[4::2]) == pytest.approx(100.0, abs=0.2)


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda m: m["name"])
def test_a_program_without_a_table_gives_nothing(entry, monkeypatch):
    facts, _ = facts_of("tiny-gpt2.train")
    step_module.note_capture(True)
    step_module.note_capture(False)               # no step ran in a capture
    assert scope_share.read(facts, **args_of(entry)) is None
    monkeypatch.delattr(step_module, "step_scopes")      # the parent of the PR that added it
    assert scope_share.read(facts, **args_of(entry)) is None


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda m: m["name"])
def test_an_entrys_cells_have_its_scopes(entry):
    scopes = args_of(entry)["scopes"]
    for cell in entry["workloads"]:
        held = set(scope_table(tiny_step(TINY_OF[cell])[-1]).values())
        assert scopes is None or set(scopes) & held, (cell, scopes)
    if scopes is not None:         # and every cell that has one of them is listed
        have = {cell for cell, tiny in TINY_OF.items() if set(scopes) & set(scope_table(tiny_step(tiny)[-1]).values())}
        assert have == set(entry["workloads"])


@pytest.mark.parametrize("cell, config, metrics", pinned_sets.pytestmark[0].args[1],
                         ids=lambda x: x if isinstance(x, str) and "." in x else None)
def test_the_catalog_cells_report_at_least_what_they_did(cell, config, metrics):
    """``test_bench_nemotron_h.py::test_cells_keep_their_entries`` pins these
    two cells' metric sets with ``==`` in a file this PR may not edit, so its
    two cases fail in the open since PR 36 adds to them; what it guards —
    nothing a cell reported went away — is held here with ``<=``."""
    reported = {m["name"] for m in MANIFEST["per_layer"] + MANIFEST["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]}
    assert metrics <= reported
    assert reported - metrics == {m["name"] for m in ENTRIES if cell in m["workloads"]}
