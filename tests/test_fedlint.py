"""fedlint test matrix: every rule against its golden-bad fixture
(stable finding IDs + pinned line numbers), hatch suppression, wire-drift
detection via patched sources, and the live tree — which must be clean.

The analyzer lives at ``scripts/fedlint`` under the repo *root* (not
``src/``), so the root goes on ``sys.path`` before importing it.
"""

import pathlib
import re
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from scripts.fedlint.core import Context, SourceFile  # noqa: E402
from scripts.fedlint.rules import REGISTRY, rule_ids  # noqa: E402
from scripts.fedlint.rules.determinism import DeterminismRule  # noqa: E402
from scripts.fedlint.rules.elasticity import EpochRoutingRule  # noqa: E402
from scripts.fedlint.rules.kernels import KernelTwinRule  # noqa: E402
from scripts.fedlint.rules.locks import (  # noqa: E402
    HatchPolicyRule,
    LockDisciplineRule,
    LockOrderRule,
)
from scripts.fedlint.rules.obs import ObservabilityRule  # noqa: E402
from scripts.fedlint.rules.wire import (  # noqa: E402
    SERVER_PROC,
    TRANSPORT,
    WireDriftRule,
)

FIXTURES = REPO_ROOT / "tests" / "fixtures" / "fedlint"


def _ids(findings):
    return [(f.rule, f.line) for f in findings]


# =========================================================================
# lock discipline (FED101/FED102) + hatch policy (FED103)
# =========================================================================


def test_lock_discipline_fixture_findings():
    src = SourceFile(FIXTURES / "bad_lock_discipline.py")
    got = _ids(LockDisciplineRule().check(src))
    assert got == [
        ("FED101", 20),     # unlocked read of total
        ("FED102", 23),     # unlocked write to total
        ("FED102", 26),     # unlocked in-place mutation of pending
        ("FED101", 34),     # bare hatch suppresses nothing
    ]


def test_lock_discipline_valid_hatch_and_caller_holds_suppress():
    src = SourceFile(FIXTURES / "bad_lock_discipline.py")
    flagged_lines = {f.line for f in LockDisciplineRule().check(src)}
    # peek_hatched (reasoned hatch) and helper (Caller holds docstring)
    text = src.text.splitlines()
    hatched_line = next(i for i, ln in enumerate(text, 1)
                        if "suppressed, no finding" in ln)
    caller_line = next(i for i, ln in enumerate(text, 1)
                       if "documented convention" in ln)
    assert hatched_line not in flagged_lines
    assert caller_line not in flagged_lines


def test_hatch_without_reason_is_flagged():
    src = SourceFile(FIXTURES / "bad_lock_discipline.py")
    got = _ids(HatchPolicyRule().check(src))
    assert got == [("FED103", 34)]
    assert "needs a reason" in HatchPolicyRule().check(src)[0].message


# =========================================================================
# lock-order graph (FED201)
# =========================================================================


def test_lock_order_cycle_fixture(tmp_path):
    rule = LockOrderRule()
    rule.check(SourceFile(FIXTURES / "bad_lock_order.py"))
    ctx = Context(root=REPO_ROOT)
    ctx.graph_out = tmp_path / "lock_order.dot"
    findings = rule.finalize(ctx)
    assert _ids(findings) == [("FED201", 16)]
    msg = findings[0].message
    assert "self.a_lock" in msg and "self.b_lock" in msg
    dot = ctx.graph_out.read_text()
    assert '"self.a_lock" -> "self.b_lock"' in dot
    assert '"self.b_lock" -> "self.a_lock"' in dot


def test_lock_order_live_tree_is_acyclic():
    rule = LockOrderRule()
    for rel in ("src/repro/core/store.py", "src/repro/core/server_proc.py",
                "src/repro/core/transport.py"):
        rule.check(SourceFile(REPO_ROOT / rel, rel=rel))
    assert rule.finalize(Context(root=REPO_ROOT)) == []
    # the documented global order: record locks before shard locks
    assert ("rec.lock", "sh.journal_lock") in rule.graph()


# =========================================================================
# kernel-twin parity (FED301/FED302/FED303)
# =========================================================================


def test_kernel_twin_fixture_findings():
    rule = KernelTwinRule(root_rel="tests/fixtures/fedlint/kernels")
    findings = rule.finalize(Context(root=REPO_ROOT))
    got = sorted((f.rule, pathlib.PurePosixPath(f.path).name, f.line)
                 for f in findings)
    assert got == [
        ("FED301", "badkern.py", 1),      # never invokes pl.pallas_call
        ("FED301", "incomplete", 1),      # missing ops/ref/kernel files
        ("FED302", "ref.py", 4),          # scale_ref has no twin
        ("FED303", "__init__.py", 1),     # no re-export from ops
        ("FED303", "ops.py", 1),          # no kernel-module import
        ("FED303", "ops.py", 1),          # no interpret_mode call
    ]


def test_kernel_twins_live_tree_clean():
    assert KernelTwinRule().finalize(Context(root=REPO_ROOT)) == []


# =========================================================================
# wire drift (FED401/FED402/FED403)
# =========================================================================


def _wire_findings(old: str, new: str):
    text = (REPO_ROOT / TRANSPORT).read_text()
    assert old in text, f"expected {old!r} in {TRANSPORT}"
    ctx = Context(root=REPO_ROOT,
                  overrides={TRANSPORT: text.replace(old, new)})
    return WireDriftRule().finalize(ctx)


def test_wire_version_bump_without_doc_update_fails():
    findings = _wire_findings("WIRE_VERSION = 4", "WIRE_VERSION = 5")
    assert any(f.rule == "FED402" and "WIRE_VERSION" in f.message
               for f in findings)


def test_wire_kind_constant_drift_fails():
    findings = _wire_findings("KIND_REPLY = 0x01", "KIND_REPLY = 0x02")
    assert any(f.rule == "FED401" and "KIND_REPLY" in f.message
               for f in findings)


def test_wire_undocumented_op_fails():
    text = (REPO_ROOT / TRANSPORT).read_text() \
        + '\n_PROBE_MSG = ["brandnewop", 0]\n'
    findings = WireDriftRule().finalize(
        Context(root=REPO_ROOT, overrides={TRANSPORT: text}))
    assert any(f.rule == "FED403" and "brandnewop" in f.message
               for f in findings)


def test_wire_fetch_module_is_in_op_catalog():
    """v3 read path: an op invented in ``core/fetch.py`` — not just the
    transport — must trip FED403, i.e. the new module is in OP_FILES."""
    fetch_rel = "src/repro/core/fetch.py"
    text = (REPO_ROOT / fetch_rel).read_text() \
        + '\n_PROBE_MSG = ["sneakyfetch", 0]\n'
    findings = WireDriftRule().finalize(
        Context(root=REPO_ROOT, overrides={fetch_rel: text}))
    assert any(f.rule == "FED403" and "sneakyfetch" in f.message
               and f.path.endswith("fetch.py") for f in findings)


def test_wire_fetch_reply_contract_is_pinned():
    """`fetch` must stay in ``REPLY_OPS`` in lockstep with the spec's
    §4.7 request/reply table: dropping it from the set (while the doc
    still documents the ``fetched`` reply) is FED403 drift."""
    text = (REPO_ROOT / SERVER_PROC).read_text()
    assert '"stop", "fetch"' in text
    findings = WireDriftRule().finalize(Context(
        root=REPO_ROOT,
        overrides={SERVER_PROC: text.replace('"stop", "fetch"', '"stop"')}))
    assert any(f.rule == "FED403" and "`fetch`" in f.message
               and "REPLY_OPS" in f.message for f in findings)


def test_wire_migration_reply_contract_is_pinned():
    """The v4 migration ops answer on the command session; dropping one
    from ``REPLY_OPS`` while the spec's §4.8 table still documents its
    reply is FED403 drift."""
    text = (REPO_ROOT / SERVER_PROC).read_text()
    assert '"mig_export"' in text
    findings = WireDriftRule().finalize(Context(
        root=REPO_ROOT,
        overrides={SERVER_PROC: text.replace('"mig_export", ', '')}))
    assert any(f.rule == "FED403" and "`mig_export`" in f.message
               and "REPLY_OPS" in f.message for f in findings)


def test_wire_doc_and_impl_currently_agree():
    assert WireDriftRule().finalize(Context(root=REPO_ROOT)) == []


# =========================================================================
# epoch routing (FED404)
# =========================================================================


def test_epoch_routing_fixture_findings():
    src = SourceFile(FIXTURES / "bad_epoch_route.py",
                     rel="src/repro/core/bad_epoch_route.py")
    got = _ids(EpochRoutingRule().check(src))
    assert got == [
        ("FED404", 27),     # stable_shard modulo map
        ("FED404", 30),     # ring natural owner
    ]


def test_epoch_routing_ring_internal_and_hatch_suppressed():
    src = SourceFile(FIXTURES / "bad_epoch_route.py",
                     rel="src/repro/core/bad_epoch_route.py")
    flagged = {f.line for f in EpochRoutingRule().check(src)}
    text = src.text.splitlines()
    ring_internal = next(i for i, ln in enumerate(text, 1)
                         if "inside HashRing: allowed" in ln)
    hatched = next(i for i, ln in enumerate(text, 1)
                   if "hatched: not a finding" in ln)
    assert ring_internal not in flagged and hatched not in flagged


def test_epoch_routing_rule_scope():
    rule = EpochRoutingRule()
    assert rule.applies("src/repro/core/store.py")
    assert rule.applies("src/repro/launch/shard_server.py")
    assert not rule.applies("tests/test_store_equivalence.py")
    assert not rule.applies("src/repro/models/lstm.py")


def test_epoch_routing_live_tree_clean():
    rule = EpochRoutingRule()
    for rel in ("src/repro/core/store.py", "src/repro/core/server_proc.py",
                "src/repro/core/fetch.py", "src/repro/core/fedccl.py",
                "src/repro/launch/shard_server.py"):
        assert rule.check(SourceFile(REPO_ROOT / rel, rel=rel)) == []


# =========================================================================
# determinism (FED501-FED504)
# =========================================================================


def test_determinism_fixture_findings():
    src = SourceFile(FIXTURES / "bad_determinism.py")
    got = _ids(DeterminismRule().check(src))
    assert got == [
        ("FED502", 7),      # from random import shuffle
        ("FED501", 11),     # np.random.rand
        ("FED503", 15),     # time.time()
        ("FED504", 19),     # iterating set(keys)
    ]


def test_determinism_seeded_and_hatched_uses_pass():
    src = SourceFile(FIXTURES / "bad_determinism.py")
    flagged = {f.line for f in DeterminismRule().check(src)}
    text = src.text.splitlines()
    seeded = next(i for i, ln in enumerate(text, 1)
                  if "default_rng(7)" in ln)
    hatched = next(i for i, ln in enumerate(text, 1)
                   if "suppressed, no finding" in ln)
    assert seeded not in flagged and hatched not in flagged


def test_determinism_rule_scope():
    rule = DeterminismRule()
    assert rule.applies("src/repro/core/store.py")
    assert rule.applies("src/repro/obs/record.py")
    assert rule.applies("tests/test_store_equivalence.py")
    assert not rule.applies("src/repro/models/lstm.py")
    assert not rule.applies("tests/test_clustering.py")


def test_determinism_clock_shim_exempt_from_wall_clock_ban():
    """repro.obs.clock is the ONE sanctioned wall-clock site; the same
    read anywhere else in scope stays a FED503 finding."""
    clock_rel = "src/repro/obs/clock.py"
    src = SourceFile(REPO_ROOT / clock_rel, rel=clock_rel)
    assert [f for f in DeterminismRule().check(src)
            if f.rule == "FED503"] == []
    elsewhere = SourceFile(REPO_ROOT / clock_rel,
                           rel="src/repro/core/sneaky_clock.py")
    assert any(f.rule == "FED503"
               for f in DeterminismRule().check(elsewhere))


# =========================================================================
# observability (FED601/FED602)
# =========================================================================


def test_observability_fixture_findings():
    src = SourceFile(FIXTURES / "bad_obs.py",
                     rel="src/repro/core/bad_obs.py")
    got = _ids(ObservabilityRule().check(src))
    assert got == [
        ("FED601", 8),      # import logging
        ("FED601", 13),     # print() in core
        ("FED602", 18),     # time.monotonic_ns()
        ("FED602", 20),     # time.perf_counter()
        ("FED602", 26),     # hatch above covers only the print line
    ]


def test_observability_hatched_print_suppressed():
    src = SourceFile(FIXTURES / "bad_obs.py",
                     rel="src/repro/core/bad_obs.py")
    flagged = {f.line for f in ObservabilityRule().check(src)}
    text = src.text.splitlines()
    hatched = next(i for i, ln in enumerate(text, 1)
                   if "hatched: not a finding" in ln)
    assert hatched not in flagged


def test_observability_rule_scope_and_clock_sanction():
    rule = ObservabilityRule()
    assert rule.applies("src/repro/core/store.py")
    assert rule.applies("src/repro/obs/record.py")
    # CLI entry points and examples may print
    assert not rule.applies("src/repro/launch/shard_server.py")
    assert not rule.applies("examples/quickstart.py")
    # the clock shim itself reads time.monotonic freely (FED602 exempt)
    clock_rel = "src/repro/obs/clock.py"
    src = SourceFile(REPO_ROOT / clock_rel, rel=clock_rel)
    assert rule.check(src) == []


# =========================================================================
# CLI + live tree + registry/docs coherence
# =========================================================================


def test_cli_live_tree_clean_and_graph_artifact(tmp_path, capsys):
    from scripts.fedlint.__main__ import main
    dot_path = tmp_path / "lock_order.dot"
    assert main(["src", "tests", "--graph-out", str(dot_path)]) == 0
    assert "fedlint OK" in capsys.readouterr().err
    dot = dot_path.read_text()
    assert dot.startswith("digraph lock_order")
    # the committed acquisition order (record -> shard) shows up as edges
    assert '"rec.lock" -> "sh.journal_lock"' in dot


def test_cli_list_rules(capsys):
    from scripts.fedlint.__main__ import main
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in rule_ids():
        assert rid in out


def test_rule_ids_match_invariants_doc():
    doc = (REPO_ROOT / "docs" / "INVARIANTS.md").read_text()
    doc_ids = set(re.findall(r"\bFED\d{3}\b", doc))
    assert doc_ids == set(rule_ids())


def test_registry_is_class_based():
    # run() must instantiate rules fresh each time: LockOrderRule
    # accumulates per-run state, a cached instance would leak analyses
    for cls in REGISTRY.values():
        assert isinstance(cls, type)
