"""Twin-contract auditor: statically prove the Python/C twin contract.

Every headline identity gate in this repo (byte-identical trees, flows,
digests across the Python and C planes) rests on hand-maintained twin
surfaces: shared constants, the 28-field determinism fingerprint, the
55-field endpoint export, the folded counter-name tables, the interned
attribute names, the congestion-control registry, the cubic arithmetic,
and the checkpoint ABI/VERSION gates.  This module cross-checks those
surfaces between `native/colcore/colcore.c` and the Python twins WITHOUT
running anything, and fails by name on any drift — so a mismatch cannot
merge and wait for a runtime identity matrix to catch it.

Every check emits findings with stable rule ids (asserted by
tests/test_twincheck.py's mutation fixtures):

  const-drift:<NAME>       a shared constant differs between the twins
  fingerprint-arity        StreamEndpoint.fingerprint vs CEp_fingerprint
  export-arity             CEp _export_state vs _restore_state formats
  struct-export:<field>    a CEp struct field neither exported nor exempt
  counter-name:<name>      a C-folded counter name unknown to Python
  attr-name:<name>         an interned C attribute name absent in Python
  cc-enum                  congestion-control registry drift (3 surfaces)
  cubic-arith:<hook>       cubic/newreno literal drift between the twins
  abi-migration            colcore ABI bumped without a MIGRATION entry
  version-migration        checkpoint VERSION bumped without a MIGRATION entry
  c-intern:<line>          PyUnicode_InternFromString outside module init
  shim-abi-drift:<NAME>    a shim fast-plane ABI constant (ring header
                           layout, clock-page words, readiness/oplog
                           regions, protocol sentinels) differs between
                           native/shring.h + native/shim/shim.c and the
                           worker twin (shadow_tpu/native/managed.py,
                           vfs.py, core/time.py) — PR 13
  extract:<what>           an audit anchor disappeared (refactor moved a
                           contract surface: update the auditor WITH it)
"""

from __future__ import annotations

from pathlib import Path

import c_extract as C
import py_extract as P
from report import Finding


#: shared constants: (python module key, python name, C define)
CONST_PAIRS = [
    ("transport", "MSS", "MSS_C"),
    ("transport", "INIT_CWND", "INIT_CWND_C"),
    ("transport", "MIN_CWND", "MIN_CWND_C"),
    ("transport", "RTO_MIN_NS", "RTO_MIN_NS_C"),
    ("transport", "RTO_MAX_NS", "RTO_MAX_NS_C"),
    ("transport", "SYN_RETRIES", "SYN_RETRIES_C"),
    ("transport", "FIN_RETRIES", "FIN_RETRIES_C"),
    ("transport", "DATA_RETRIES", "DATA_RETRIES_C"),
    ("transport", "SACK_MAX_BLOCKS", "SACK_MAX_BLOCKS_C"),
    ("fluid", "MTU", "MTU"),
    ("fluid", "HEADER", "HEADER"),
    ("fluid", "HARD_MAX_PKTS", "HARD_MAX_PKTS"),
    ("time", "NS_PER_SEC", "NS_PER_SEC"),
    ("gossip", "TX_SIZE", "TX_SIZE"),
    ("tor", "HDR", "TCELL_HDR"),
]

#: network/unit.py kind enum name -> C define (KIND_DGRAM is the one the
#: C row format carries; TK_* are the stream machine's unit kinds)
KIND_PAIRS = [
    ("SYN", "TK_SYN"), ("SYNACK", "TK_SYNACK"), ("DATA", "TK_DATA"),
    ("ACK", "TK_ACK"), ("FIN", "TK_FIN"), ("FINACK", "TK_FINACK"),
    ("DGRAM", "KIND_DGRAM"),
]

#: models/tor.py cell enum name -> C define (CONNECTED has no C twin:
#: the C sink never originates it)
TOR_CELL_PAIRS = [
    ("CREATE", "TC_CREATE"), ("CREATED", "TC_CREATED"),
    ("EXTEND", "TC_EXTEND"), ("EXTENDED", "TC_EXTENDED"),
    ("BEGIN", "TC_BEGIN"), ("DATA", "TC_DATA"), ("END", "TC_END"),
]

#: shim fast-plane ABI: (python module key, python name, C define).
#: The C side is native/shring.h plus shim.c's own protocol defines;
#: the Python side is the worker twin that packs/reads the same shared
#: pages.  Any drift here silently corrupts the in-shim fast path (the
#: shim and worker would disagree about where a counter or ring field
#: lives), so every mirrored constant is audited by name.
SHIM_ABI_PAIRS = [
    # clock-page u64 word indices + flag bit (shim increments, worker folds)
    ("managed", "SHIM_PAGE_FLAGS", "SHIM_PAGE_FLAGS"),
    ("managed", "SHIM_PAGE_CLS_TIME", "SHIM_PAGE_CLS_TIME"),
    ("managed", "SHIM_PAGE_CLS_IDENT", "SHIM_PAGE_CLS_IDENT"),
    ("managed", "SHIM_PAGE_CLS_RING_R", "SHIM_PAGE_CLS_RING_R"),
    ("managed", "SHIM_PAGE_CLS_RING_W", "SHIM_PAGE_CLS_RING_W"),
    ("managed", "SHIM_PAGE_CLS_READY", "SHIM_PAGE_CLS_READY"),
    ("managed", "SHIM_PAGE_OPLOG_N", "SHIM_PAGE_OPLOG_N"),
    ("managed", "SHIM_PAGE_F_FAST", "SHIM_PAGE_F_FAST"),
    # per-vfd readiness bytes (worker publishes, shim's poll consumes)
    ("managed", "SHIM_READY_OFF", "SHIM_READY_OFF"),
    ("managed", "SHIM_READY_LEN", "SHIM_READY_LEN"),
    ("managed", "SHIM_READY_VALID", "SHIM_READY_VALID"),
    ("managed", "SHIM_READY_IN", "SHIM_READY_IN"),
    ("managed", "SHIM_READY_OUT", "SHIM_READY_OUT"),
    ("managed", "SHIM_READY_HUP", "SHIM_READY_HUP"),
    ("managed", "SHIM_READY_ERR", "SHIM_READY_ERR"),
    # socket-op log (shim appends, worker replays at the round fold)
    ("managed", "SHIM_OPLOG_OFF", "SHIM_OPLOG_OFF"),
    ("managed", "SHIM_OPLOG_MAX", "SHIM_OPLOG_MAX"),
    ("managed", "SHIM_OP_RECV", "SHIM_OP_RECV"),
    ("managed", "SHIM_OP_SEND", "SHIM_OP_SEND"),
    # struct shring socket extensions (flags word + tx write budget)
    ("managed", "SHRING_OFF_FLAGS", "SHRING_OFF_FLAGS"),
    ("managed", "SHRING_OFF_WBUDGET", "SHRING_OFF_WBUDGET"),
    ("managed", "SHRING_F_HUP", "SHRING_F_HUP"),
    ("managed", "SHRING_F_ERR", "SHRING_F_ERR"),
    ("managed", "SHRING_F_SOCK", "SHRING_F_SOCK"),
    ("managed", "SHRING_CAP_MIN", "SHRING_CAP_MIN"),
    ("managed", "SHRING_CAP_MAX", "SHRING_CAP_MAX"),
    # wire protocol sentinels (different spellings across the twins)
    ("managed", "SHIM_IPC_FD", "SHIM_IPC_FD"),
    ("managed", "VFD_BASE", "SHIM_VFD_BASE"),
    ("managed", "MAPRING", "SHIM_RET_MAPRING"),
    ("vfs", "RETRY_NATIVE", "SHIM_RET_NATIVE"),
    ("time", "EMULATED_EPOCH", "SHIM_EMULATED_EPOCH_NS"),
]

#: mmap'd ring layout twins carried as class attributes on the worker
#: side: (python class, attr, C define)
SHIM_RING_ATTR_PAIRS = [
    ("RingPipeBuf", "HDR", "SHRING_HDR"),
    ("RingPipeBuf", "MAGIC", "SHRING_MAGIC"),
    ("PipeBuf", "CAP", "SHRING_CAP"),
]

#: CEp struct fields deliberately NOT in _export_state — rebuild-time
#: wiring, each re-established by the owning object's restore path:
#:   core   Core.adopt() sets it when the endpoint joins a core
#:   sink   the owning CRelay's _restore_state re-links its conns
#:   tsink  the owning CTorSink's _restore_state re-links its client ep
#:   btc    a BtcNode's peer (models/bitcoin.py); Bitcoin nodes do not
#:          checkpoint: a BtcNode cannot be pickled, so a checkpoint of
#:          one refuses by its type's name
STRUCT_EXPORT_EXEMPT = {"core", "sink", "tsink", "btc"}

def _codes_align(export, restore) -> bool:
    """Positional compatibility of an export Py_BuildValue format with
    its restore PyArg_ParseTuple format: N (steal) and O (borrow) both
    parse as O, and a bool exported as an object (O: Py_True/Py_False)
    legitimately parses back as i."""
    if len(export) != len(restore):
        return False
    for e, r in zip(export, restore):
        e = "O" if e == "N" else e
        if e == r or (e == "O" and r == "i"):
            continue
        return False
    return True


def audit(root) -> list:
    root = Path(root)
    findings: list = []

    def fail(rule, path, msg, line=0):
        findings.append(Finding(rule, str(path), line, msg))

    csrc_path = root / "native" / "colcore" / "colcore.c"
    try:
        csrc = csrc_path.read_text()
    except OSError as e:
        fail("extract:colcore", csrc_path, str(e))
        return findings
    cdef = C.resolve_defines(csrc)

    py_files = sorted(p for p in (root / "shadow_tpu").rglob("*.py")
                      if "__pycache__" not in p.parts)

    # Python constant environments, chained through the import graph
    envs = {}
    try:
        envs["time"] = P.module_constants(
            P.parse(root / "shadow_tpu" / "core" / "time.py"))
        envs["fluid"] = P.module_constants(
            P.parse(root / "shadow_tpu" / "network" / "fluid.py"),
            envs["time"])
        transport_tree = P.parse(
            root / "shadow_tpu" / "network" / "transport.py")
        envs["transport"] = P.module_constants(transport_tree, envs["time"])
        envs["gossip"] = P.module_constants(
            P.parse(root / "shadow_tpu" / "models" / "gossip.py"))
        tor_tree = P.parse(root / "shadow_tpu" / "models" / "tor.py")
        envs["tor"] = P.module_constants(tor_tree)
    except (OSError, P.ExtractError, SyntaxError) as e:
        fail("extract:python-consts", root, str(e))
        return findings

    # 1. shared constants ----------------------------------------------------
    for mod, pyname, cname in CONST_PAIRS:
        pv = envs[mod].get(pyname)
        cv = cdef.get(cname)
        if pv is None:
            fail("extract:const", "shadow_tpu", "%s.%s not found" %
                 (mod, pyname))
        elif cv is None:
            fail("extract:const", csrc_path, "#define %s not found" % cname)
        elif pv != cv:
            fail("const-drift:%s" % pyname, csrc_path,
                 "%s=%d (Python %s) but %s=%d (C)" %
                 (pyname, pv, mod, cname, cv))

    # unit kinds + tor cell kinds (range enums vs defines)
    try:
        kinds = P.range_enum(P.parse(
            root / "shadow_tpu" / "network" / "unit.py"))
        for pyname, cname in KIND_PAIRS:
            if kinds.get(pyname) != cdef.get(cname):
                fail("const-drift:%s" % pyname, csrc_path,
                     "unit kind %s=%s (Python) vs %s=%s (C)" %
                     (pyname, kinds.get(pyname), cname, cdef.get(cname)))
        cells = P.range_enum(tor_tree)
        for pyname, cname in TOR_CELL_PAIRS:
            if cells.get(pyname) != cdef.get(cname):
                fail("const-drift:tor.%s" % pyname, csrc_path,
                     "tor cell %s=%s (Python) vs %s=%s (C)" %
                     (pyname, cells.get(pyname), cname, cdef.get(cname)))
    except (P.ExtractError, SyntaxError, OSError) as e:
        fail("extract:kind-enums", root, str(e))

    # 2. fingerprint arity ---------------------------------------------------
    try:
        ep_cls = P.class_def(transport_tree, "StreamEndpoint")
        py_arity = P.return_tuple_arity(P.method_def(ep_cls, "fingerprint"))
        c_codes = C.format_codes(C.buildvalue_format(csrc, "CEp_fingerprint"))
        if py_arity != len(c_codes):
            fail("fingerprint-arity", csrc_path,
                 "StreamEndpoint.fingerprint has %d fields but "
                 "CEp_fingerprint builds %d — the determinism sentinel "
                 "twins diverged" % (py_arity, len(c_codes)))
    except (P.ExtractError, C.ExtractError) as e:
        fail("extract:fingerprint", csrc_path, str(e))

    # 3. CEp export/restore format alignment ---------------------------------
    try:
        exp = C.format_codes(C.buildvalue_format(csrc, "CEp_export_state"))
        res = C.format_codes(C.parsetuple_format(csrc, "CEp_restore_state"))
        if not _codes_align(exp, res):
            fail("export-arity", csrc_path,
                 "CEp_export_state builds %d fields (%s) but "
                 "CEp_restore_state parses %d (%s) — a checkpoint written "
                 "by this build cannot restore" %
                 (len(exp), "".join(exp), len(res), "".join(res)))
    except C.ExtractError as e:
        fail("extract:cep-export", csrc_path, str(e))

    # 4. CEp struct fields all exported or exempt ----------------------------
    try:
        fields = set(C.struct_fields(csrc, "CEp")) - {"PyObject_HEAD"}
        body = C.function_body(csrc, "CEp_export_state")
        import re as _re
        referenced = set(_re.findall(r"e->(\w+)", body))
        for f in sorted(fields - referenced - STRUCT_EXPORT_EXEMPT):
            fail("struct-export:%s" % f, csrc_path,
                 "CEp field %r is neither exported by CEp_export_state "
                 "nor in the documented exempt set — a checkpoint would "
                 "silently drop it" % f)
    except C.ExtractError as e:
        fail("extract:cep-struct", csrc_path, str(e))

    # 5. folded counter names ------------------------------------------------
    try:
        folded = C.string_array(csrc, "names2")
        known = P.counter_names(py_files)
        for name in folded:
            if name not in known:
                fail("counter-name:%s" % name, csrc_path,
                     "C folds counter %r but no Python twin increments a "
                     "counter of that name — rename drift between the "
                     "planes" % name)
    except C.ExtractError as e:
        fail("extract:counter-fold", csrc_path, str(e))

    # 6. interned attribute names -------------------------------------------
    try:
        vocab = P.identifier_vocab(py_files)
        # names the C module itself defines (PyMethodDef/getset tables):
        # the timer-callback methods (_rto_fire & co) are interned to be
        # looked up on C objects, not Python ones
        import re as _re2
        vocab |= set(_re2.findall(r'\{\s*"(\w+)"', csrc))
        for name in C.interned_names(csrc):
            if name not in vocab:
                fail("attr-name:%s" % name, csrc_path,
                     "C interns attribute %r but the identifier no longer "
                     "appears anywhere in shadow_tpu/ — the C engine would "
                     "read a stale attribute" % name)
    except C.ExtractError as e:
        fail("extract:interned", csrc_path, str(e))

    # 7. congestion-control registry -----------------------------------------
    try:
        registry = P.dict_literal_keys(transport_tree, "CONGESTION_CONTROLS")
        schema_names = set(P.string_tuple(
            P.parse(root / "shadow_tpu" / "config" / "schema.py"),
            "CONGESTION_CONTROL_NAMES"))
        if set(registry) != schema_names:
            fail("cc-enum", csrc_path,
                 "transport CONGESTION_CONTROLS keys %s != config-schema "
                 "CONGESTION_CONTROL_NAMES %s" %
                 (sorted(registry), sorted(schema_names)))
        for name, clsname in registry.items():
            cc_id = P.class_attr(P.class_def(transport_tree, clsname),
                                 "cc_id")
            c_id = cdef.get("CC_%s" % name.upper())
            if c_id != cc_id:
                fail("cc-enum", csrc_path,
                     "cc %r: Python cc_id=%s vs C CC_%s=%s" %
                     (name, cc_id, name.upper(), c_id))
    except (P.ExtractError, SyntaxError, OSError) as e:
        fail("extract:cc-enum", root, str(e))

    # 8. congestion-control arithmetic ---------------------------------------
    # The cubic beta/C constants and clamp bounds live as inline integer
    # literals in BOTH twins.  Compare the resolved literal SET (>= 3;
    # 0/1/2 are structural noise) per hook — C merges both algorithms in
    # one cc_* function, so the Python side is the union over the
    # registry classes.
    try:
        env = envs["transport"]
        for hook in ("on_ack", "on_loss", "on_rto"):
            py_lits: set = set()
            for clsname in P.dict_literal_keys(
                    transport_tree, "CONGESTION_CONTROLS").values():
                py_lits |= P.int_literal_set(
                    P.method_def(P.class_def(transport_tree, clsname), hook),
                    env)
            c_lits = set(C.int_literals(csrc, "cc_%s" % hook, cdef))
            if py_lits != c_lits:
                fail("cubic-arith:%s" % hook, csrc_path,
                     "congestion-control literals diverged in %s: "
                     "Python-only %s, C-only %s" %
                     (hook, sorted(py_lits - c_lits) or "{}",
                      sorted(c_lits - py_lits) or "{}"))
    except (P.ExtractError, C.ExtractError) as e:
        fail("extract:cc-arith", csrc_path, str(e))

    # 9. ABI / VERSION bumps require a MIGRATION.md entry --------------------
    import re as _re
    try:
        abi = C.module_int_constant(csrc, "ABI")
    except C.ExtractError as e:
        abi = None
        fail("extract:abi", csrc_path, str(e))
    try:
        version = P.module_constants(
            P.parse(root / "shadow_tpu" / "checkpoint.py")).get("VERSION")
    except (OSError, SyntaxError) as e:
        version = None
        fail("extract:version", root / "shadow_tpu" / "checkpoint.py", str(e))
    mig_path = root / "MIGRATION.md"
    mig = mig_path.read_text() if mig_path.exists() else ""
    if abi is not None and not _re.search(
            r"\bABI\b\D{0,40}\b%d\b" % abi, mig):
        fail("abi-migration", mig_path,
             "colcore ABI is %d but MIGRATION.md has no entry mentioning "
             "it — every ABI bump must document what breaks and why "
             "old checkpoints refuse" % abi)
    if version is not None and not _re.search(
            r"(?i)\bversion\b\D{0,40}\b%d\b" % version, mig):
        fail("version-migration", mig_path,
             "checkpoint VERSION is %d but MIGRATION.md has no entry "
             "mentioning it — every format bump must document the break" %
             version)

    # 10. interning discipline ----------------------------------------------
    for line, text in C.intern_calls_outside_init(csrc):
        fail("c-intern:%d" % line, csrc_path,
             "PyUnicode_InternFromString outside module init leaks a "
             "reference per call and its NULL return is typically "
             "unchecked — pre-intern in PyInit (INTERN table): %s" % text,
             line)

    # 11. shim fast-plane ABI ----------------------------------------------
    # native/shring.h + shim.c define the shared-page layout the guest
    # shim writes; shadow_tpu/native/managed.py mirrors every offset,
    # word index and flag bit to read/arm the same pages.  Disagreement
    # is silent corruption (a counter folded from the wrong word, a
    # budget armed at the wrong offset), so the mirror is audited by
    # name.
    shring_path = root / "native" / "shring.h"
    shim_path = root / "native" / "shim" / "shim.c"
    managed_path = root / "shadow_tpu" / "native" / "managed.py"
    shimdef = managed_tree = None
    try:
        shimdef = C.resolve_defines(shring_path.read_text())
        shimdef.update(C.resolve_defines(shim_path.read_text()))
    except OSError as e:
        fail("extract:shim-abi", shring_path, str(e))
    try:
        managed_tree = P.parse(managed_path)
        envs["managed"] = P.module_constants(managed_tree)
        envs["vfs"] = P.module_constants(
            P.parse(root / "shadow_tpu" / "native" / "vfs.py"))
    except (OSError, P.ExtractError, SyntaxError) as e:
        fail("extract:shim-abi", managed_path, str(e))
    if shimdef is not None and managed_tree is not None:
        for mod, pyname, cname in SHIM_ABI_PAIRS:
            pv = envs[mod].get(pyname)
            cv = shimdef.get(cname)
            if pv is None:
                fail("shim-abi-drift:%s" % pyname, managed_path,
                     "shim ABI constant %s not found on the Python side "
                     "(module %r)" % (pyname, mod))
            elif cv is None:
                fail("shim-abi-drift:%s" % pyname, shring_path,
                     "shim ABI constant %s has no C define %s in "
                     "shring.h/shim.c" % (pyname, cname))
            elif pv != cv:
                fail("shim-abi-drift:%s" % pyname, shring_path,
                     "shim ABI drift: Python %s=%d but C %s=%d — shim "
                     "and worker would disagree about the shared-page "
                     "layout" % (pyname, pv, cname, cv))
        for clsname, attr, cname in SHIM_RING_ATTR_PAIRS:
            cv = shimdef.get(cname)
            try:
                pv = P.class_attr(P.class_def(managed_tree, clsname), attr)
            except P.ExtractError as e:
                fail("extract:shim-abi", managed_path, str(e))
                continue
            if cv is None or pv != cv:
                fail("shim-abi-drift:%s" % cname, shring_path,
                     "ring layout drift: %s.%s=%r but C %s=%r" %
                     (clsname, attr, pv, cname, cv))

    return findings
