"""Mutation fixtures for the static twin-contract auditor + determinism
linter (tools/twincheck/).

The discipline: copy the contract-bearing sources into a scratch tree,
perturb EXACTLY ONE twin surface, and assert the named finding fires —
then assert the real tree produces zero findings with every waiver
carrying a written reason.  If a check can't catch its seeded drift, it
isn't a gate.
"""

from __future__ import annotations

import re
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools" / "twincheck"))

import det_lint  # noqa: E402
import twin_audit  # noqa: E402

COLCORE = REPO / "native" / "colcore" / "colcore.c"


# -- scratch twin tree --------------------------------------------------------

@pytest.fixture()
def tree(tmp_path):
    """A minimal copy of the audited surfaces: shadow_tpu/ (sans caches),
    colcore.c, shring.h + shim.c, MIGRATION.md."""
    shutil.copytree(REPO / "shadow_tpu", tmp_path / "shadow_tpu",
                    ignore=shutil.ignore_patterns("__pycache__", "*.so"))
    (tmp_path / "native" / "colcore").mkdir(parents=True)
    shutil.copy(COLCORE, tmp_path / "native" / "colcore" / "colcore.c")
    (tmp_path / "native" / "shim").mkdir(parents=True)
    shutil.copy(REPO / "native" / "shring.h",
                tmp_path / "native" / "shring.h")
    shutil.copy(REPO / "native" / "shim" / "shim.c",
                tmp_path / "native" / "shim" / "shim.c")
    shutil.copy(REPO / "MIGRATION.md", tmp_path / "MIGRATION.md")
    return tmp_path


def mutate(tree: Path, relpath: str, old: str, new: str):
    p = tree / relpath
    src = p.read_text()
    assert src.count(old) >= 1, "mutation anchor %r missing in %s" % (
        old, relpath)
    p.write_text(src.replace(old, new, 1))


def append(tree: Path, relpath: str, code: str):
    p = tree / relpath
    p.write_text(p.read_text() + "\n" + code + "\n")


def mutate_define(tree: Path, cname: str):
    """Add one to the value of colcore.c's ``#define <cname>``."""
    p = tree / "native" / "colcore" / "colcore.c"
    src, n = re.subn(r"^(#define %s )(.+)$" % re.escape(cname),
                     r"\1(\2 + 1)", p.read_text(), count=1, flags=re.M)
    assert n == 1, "no #define %s in colcore.c" % cname
    p.write_text(src)


def rules(findings):
    return [f.rule for f in findings]


# -- the clean tree is clean --------------------------------------------------

def test_real_tree_audit_zero_findings():
    findings = twin_audit.audit(REPO)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_real_tree_detlint_zero_findings_and_reasoned_waivers():
    findings, waivers = det_lint.lint_with_waivers(REPO)
    assert findings == [], "\n".join(str(f) for f in findings)
    assert waivers, "the tree documents its deliberate wall-clock uses"
    for path, line, rule, reason in waivers:
        assert reason.strip(), "%s:%d waives %r with no reason" % (
            path, line, rule)


def test_scratch_copy_is_clean(tree):
    assert twin_audit.audit(tree) == []


# -- twin-contract mutations --------------------------------------------------

def test_abi_bump_without_migration_entry(tree):
    mutate(tree, "native/colcore/colcore.c",
           'PyModule_AddIntConstant(m, "ABI", 4)',
           'PyModule_AddIntConstant(m, "ABI", 5)')
    assert "abi-migration" in rules(twin_audit.audit(tree))


def test_version_bump_without_migration_entry(tree):
    mutate(tree, "shadow_tpu/checkpoint.py", "VERSION = 5", "VERSION = 9")
    assert "version-migration" in rules(twin_audit.audit(tree))


def test_rto_max_drift_python_side(tree):
    mutate(tree, "shadow_tpu/network/transport.py",
           "RTO_MAX_NS = 60_000 * NS_PER_MS",
           "RTO_MAX_NS = 61_000 * NS_PER_MS")
    assert "const-drift:RTO_MAX_NS" in rules(twin_audit.audit(tree))


def test_rto_max_drift_c_side(tree):
    mutate(tree, "native/colcore/colcore.c",
           "#define RTO_MAX_NS_C 60000000000LL",
           "#define RTO_MAX_NS_C 59000000000LL")
    assert "const-drift:RTO_MAX_NS" in rules(twin_audit.audit(tree))


def test_export_field_drop_is_caught(tree):
    # drop the final field code from the CEp export format — a checkpoint
    # written by such a build could not restore
    mutate(tree, "native/colcore/colcore.c",
           '"(iiiiOiiiOLOLLLLLLLLLLiiONNLLLLLiNOOOOOOiLLOLOLOiLLiLLNN)"',
           '"(iiiiOiiiOLOLLLLLLLLLLiiONNLLLLLiNOOOOOOiLLOLOLOiLLiLLN)"')
    assert "export-arity" in rules(twin_audit.audit(tree))


def test_fingerprint_field_drop_is_caught(tree):
    mutate(tree, "shadow_tpu/network/transport.py",
           "1 if s.in_recovery else 0, s.recover, s.sack_high,",
           "1 if s.in_recovery else 0, s.recover,")
    assert "fingerprint-arity" in rules(twin_audit.audit(tree))


def test_folded_counter_rename_is_caught(tree):
    mutate(tree, "native/colcore/colcore.c",
           '"stream_sack_retransmits"};', '"stream_sack_retx"};')
    assert "counter-name:stream_sack_retx" in rules(twin_audit.audit(tree))


def test_cubic_beta_drift_is_caught(tree):
    # beta 0.7 -> 0.8 on the C side only: the integer-literal sets of the
    # on_loss twins diverge
    mutate(tree, "native/colcore/colcore.c",
           "int64_t nc = e->cwnd * 7 / 10;",
           "int64_t nc = e->cwnd * 8 / 10;")
    assert "cubic-arith:on_loss" in rules(twin_audit.audit(tree))


def test_cc_on_ack_literal_drift_python_side_is_caught(tree):
    # the on_ack literal sets are compared in both directions: drift
    # seeded on the Python side fails as surely as on the C side
    mutate(tree, "shadow_tpu/network/transport.py",
           "nn = min(newly, 1 << 20)", "nn = min(newly, 1 << 21)")
    assert "cubic-arith:on_ack" in rules(twin_audit.audit(tree))


@pytest.mark.parametrize(
    "pyname,cname",
    [pytest.param(py, c, id=py) for _mod, py, c in twin_audit.CONST_PAIRS
     if py != "RTO_MAX_NS"])  # RTO_MAX_NS has its own two tests above
def test_const_pair_drift_is_caught(tree, pyname, cname):
    # move the C side's value by one: every audited pair fails by name
    mutate_define(tree, cname)
    assert "const-drift:%s" % pyname in rules(twin_audit.audit(tree))


def test_new_struct_field_without_export_is_caught(tree):
    mutate(tree, "native/colcore/colcore.c",
           "int64_t recover, sack_high, w_max, epoch_start;",
           "int64_t recover, sack_high, w_max, epoch_start, new_knob;")
    assert "struct-export:new_knob" in rules(twin_audit.audit(tree))


def test_interned_attr_rename_is_caught(tree):
    # rename the Python-side attribute out from under the C intern table
    for py in (tree / "shadow_tpu").rglob("*.py"):
        src = py.read_text()
        if "_uid_counter" in src:
            py.write_text(src.replace("_uid_counter", "_uid_ctr"))
    found = rules(twin_audit.audit(tree))
    assert "attr-name:_uid_counter" in found


def test_intern_call_outside_init_is_caught(tree):
    mutate(tree, "native/colcore/colcore.c",
           "ok = attr_i64(params, S_seed, &seed) == 0;",
           'ok = attr_i64(params, PyUnicode_InternFromString("seed"), '
           "&seed) == 0;")
    found = rules(twin_audit.audit(tree))
    assert any(r.startswith("c-intern:") for r in found)


def test_cc_registry_drift_is_caught(tree):
    mutate(tree, "shadow_tpu/config/schema.py",
           'CONGESTION_CONTROL_NAMES = ("newreno", "cubic")',
           'CONGESTION_CONTROL_NAMES = ("newreno", "cubic", "bbr")')
    assert "cc-enum" in rules(twin_audit.audit(tree))


# -- the shim fast-plane ABI (fourth surface, PR 13) --------------------------

def test_shim_page_word_drift_c_side_is_caught(tree):
    # shim would fold in-shim ring reads from the wrong clock-page word
    mutate(tree, "native/shring.h",
           "#define SHIM_PAGE_CLS_RING_R 7",
           "#define SHIM_PAGE_CLS_RING_R 12")
    assert "shim-abi-drift:SHIM_PAGE_CLS_RING_R" in rules(
        twin_audit.audit(tree))


def test_shim_ready_off_drift_python_side_is_caught(tree):
    # worker would publish readiness bytes where the shim doesn't look
    mutate(tree, "shadow_tpu/native/managed.py",
           "SHIM_READY_OFF = 256", "SHIM_READY_OFF = 264")
    assert "shim-abi-drift:SHIM_READY_OFF" in rules(twin_audit.audit(tree))


def test_shim_vfd_base_drift_is_caught(tree):
    # the hex-literal sentinel that separates simulated fds from real ones
    mutate(tree, "native/shim/shim.c",
           "#define SHIM_VFD_BASE 0x100000",
           "#define SHIM_VFD_BASE 0x200000")
    assert "shim-abi-drift:VFD_BASE" in rules(twin_audit.audit(tree))


def test_shim_ring_magic_drift_is_caught(tree):
    mutate(tree, "native/shring.h",
           "#define SHRING_MAGIC 0x53524E47u",
           "#define SHRING_MAGIC 0x53524E48u")
    assert "shim-abi-drift:SHRING_MAGIC" in rules(twin_audit.audit(tree))


def test_shim_epoch_drift_is_caught(tree):
    # realtime family would disagree with core/time.EMULATED_EPOCH
    mutate(tree, "native/shim/shim.c",
           "#define SHIM_EMULATED_EPOCH_NS 946684800000000000LL",
           "#define SHIM_EMULATED_EPOCH_NS 946684800000000001LL")
    assert "shim-abi-drift:EMULATED_EPOCH" in rules(twin_audit.audit(tree))


def test_shim_wbudget_offset_drift_is_caught(tree):
    # worker would arm the tx write budget at the wrong struct offset
    mutate(tree, "shadow_tpu/native/managed.py",
           "SHRING_OFF_WBUDGET = 56", "SHRING_OFF_WBUDGET = 48")
    assert "shim-abi-drift:SHRING_OFF_WBUDGET" in rules(
        twin_audit.audit(tree))


# -- determinism-lint mutations -----------------------------------------------

def _lint(tree):
    return det_lint.lint(tree)


def test_wallclock_injection_is_caught(tree):
    mutate(tree, "shadow_tpu/models/gossip.py",
           "TX_SIZE = 400",
           "import time\nTX_SIZE = 400\n_T0 = time.time()")
    found = _lint(tree)
    assert any(f.rule == "wallclock"
               and f.path.endswith("models/gossip.py") for f in found)


def test_wallclock_waiver_with_reason_passes(tree):
    mutate(tree, "shadow_tpu/models/gossip.py",
           "TX_SIZE = 400",
           "import time as _walltime  "
           "# detlint: ok(wallclock): test-only wall probe\nTX_SIZE = 400")
    assert not any(f.rule == "wallclock" for f in _lint(tree))


def test_waiver_without_reason_is_itself_a_finding(tree):
    mutate(tree, "shadow_tpu/models/gossip.py",
           "TX_SIZE = 400",
           "import time as _walltime  # detlint: ok(wallclock)\n"
           "TX_SIZE = 400")
    found = _lint(tree)
    assert any(f.rule == "waiver-reason" for f in found)
    assert not any(f.rule == "wallclock" for f in found)


def test_stdlib_random_is_caught(tree):
    append(tree, "shadow_tpu/models/echo.py", "import random")
    assert any(f.rule == "modrandom" for f in _lint(tree))


def test_foreign_env_read_is_caught(tree):
    append(tree, "shadow_tpu/models/echo.py",
           "import os\n_H = os.environ.get(\"HOME\")")
    assert any(f.rule == "envread" for f in _lint(tree))


def test_id_ordering_is_caught(tree):
    append(tree, "shadow_tpu/models/echo.py",
           "_ORDER = sorted([object()], key=id)")
    assert any(f.rule == "idorder" for f in _lint(tree))


def test_unsorted_set_iteration_in_digest_path_is_caught(tree):
    append(tree, "shadow_tpu/models/echo.py",
           "def _digest_probe(xs):\n"
           "    return [x for x in set(xs)]")
    assert any(f.rule == "unordered-iter" for f in _lint(tree))


def test_set_materialization_in_digest_path_is_caught(tree):
    append(tree, "shadow_tpu/models/echo.py",
           "def _export_state_probe(xs):\n"
           "    return list(set(xs))")
    assert any(f.rule == "unordered-iter" for f in _lint(tree))


def test_sorted_set_iteration_in_digest_path_passes(tree):
    append(tree, "shadow_tpu/models/echo.py",
           "def _digest_probe(xs):\n"
           "    return [x for x in sorted(set(xs))]")
    assert not any(f.rule == "unordered-iter" for f in _lint(tree))
