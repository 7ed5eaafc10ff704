#!/usr/bin/env bash
# The full local CI gate (SURVEY.md §2 "CI", §4): unit+integration tests on
# the 8-virtual-device CPU platform, the multichip dry run, and a 1k-host
# scale determinism check (twice-run, full output-tree hash compare).
set -euo pipefail
cd "$(dirname "$0")/.."

# The static gates run FIRST: twin-contract drift, determinism-discipline
# violations, and sanitizer findings fail in seconds, before the
# expensive identity matrices below ever start.

echo "== twincheck: twin-contract audit (C vs Python surfaces) =="
python tools/twincheck audit

echo "== twincheck: determinism lint (shadow_tpu/ sim-state modules) =="
python tools/twincheck detlint

echo "== sanitize smoke (ASan+UBSan colcore+shim: gossip_churn + web_cdn) =="
make -C native sanitize
ASAN_LIB=$(gcc -print-file-name=libasan.so)
# jax throws C++ exceptions in normal operation; ASan's __cxa_throw
# interceptor needs libstdc++ resolvable at preload time
STDCXX_LIB=$(gcc -print-file-name=libstdc++.so.6)
# probe that the sanitized extension is the one that imports under the
# exact smoke environment, so the gate can never "pass" while sanitizing
# nothing (a missing _colcore fails a device-policy run outright)
LD_PRELOAD="$ASAN_LIB $STDCXX_LIB" \
ASAN_OPTIONS=detect_leaks=0 \
SHADOW_TPU_COLCORE_SO=native/build/asan/_colcore.so \
python -c '
from shadow_tpu.native import _colcore
assert "build/asan" in _colcore.__file__, _colcore.__file__
print("sanitized _colcore imports (ABI %d)" % _colcore.ABI)'
sanrun() {
    rm -rf "/tmp/ci-san-$1"
    LD_PRELOAD="$ASAN_LIB $STDCXX_LIB" \
    LSAN_OPTIONS=exitcode=0 \
    SHADOW_TPU_COLCORE_SO=native/build/asan/_colcore.so \
    JAX_PLATFORMS=cpu \
    python -m shadow_tpu "examples/$1.yaml" --quiet --json-summary \
        --data-directory "/tmp/ci-san-$1" \
        --scheduler-policy tpu_batch \
        --set experimental.native_colcore=true \
        > "/tmp/ci-san-$1.json" 2> "/tmp/ci-san-$1.err"
    # a memory error or unrecovered UB aborts the run above (set -e);
    # exit-time leak reports are CPython/jax noise EXCEPT frames inside
    # the colcore extension — those gate
    if grep -q "colcore" "/tmp/ci-san-$1.err"; then
        echo "sanitize smoke: colcore frames in the sanitizer report:" >&2
        grep -B3 -A12 "colcore" "/tmp/ci-san-$1.err" | head -80 >&2
        exit 1
    fi
    python - "$1" <<'EOF'
import json, sys
d = json.load(open("/tmp/ci-san-%s.json" % sys.argv[1]))
assert d["process_errors"] == [], d["process_errors"]
assert d["events"] > 0, "sanitized run simulated nothing"
print("sanitize smoke OK: %s ran %d events under ASan/UBSan with the "
      "C engine, no colcore-attributed leaks" % (sys.argv[1], d["events"]))
EOF
}
sanrun gossip_churn
sanrun web_cdn

echo "== pytest (CPU JAX, 8 virtual devices) =="
python -m pytest tests/ -q

echo "== multichip dry run (8-shard virtual mesh) =="
GRAFT_NDEV=8 python __graft_entry__.py

echo "== 1k-host scale determinism (twice-run hash compare) =="
export JAX_PLATFORMS=cpu
run() {
    python -m shadow_tpu examples/tgen_1k.yaml --quiet --json-summary \
        --data-directory "/tmp/ci-det-$1" \
        | python -c 'import json,sys; from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS as V; d=json.load(sys.stdin); [d.pop(k, None) for k in V]; print(json.dumps(d,sort_keys=True))' \
        > "/tmp/ci-det-$1.json"
    (cd "/tmp/ci-det-$1" && find hosts -type f | sort | xargs sha256sum) \
        > "/tmp/ci-det-$1.hashes"
}
run a
run b
diff /tmp/ci-det-a.json /tmp/ci-det-b.json
diff /tmp/ci-det-a.hashes /tmp/ci-det-b.hashes
echo "determinism OK: $(python -c 'import json;print(json.load(open("/tmp/ci-det-a.json"))["events"])') events bit-identical"

echo "== fused-window smoke (forced device, K=4 vs K=1 determinism + windows served) =="
wrun() {
    python -m shadow_tpu examples/tgen_1k.yaml --quiet --json-summary \
        --data-directory "/tmp/ci-win-$1" \
        --scheduler-policy tpu_batch \
        --set experimental.tpu_device_floor=1 \
        --set "experimental.device_window_rounds=$2" \
        | python -c '
import json, sys
from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS
d = json.load(sys.stdin)
assert d["device_windows_dispatched"] > 0, \
    "forced device serviced zero fused windows"
for k in VOLATILE_SUMMARY_KEYS:
    d.pop(k, None)
print(json.dumps(d, sort_keys=True))' > "/tmp/ci-win-$1.json"
    (cd "/tmp/ci-win-$1" && find hosts -type f | sort | xargs sha256sum) \
        > "/tmp/ci-win-$1.hashes"
}
wrun k1 1
wrun k4 4
diff /tmp/ci-win-k1.json /tmp/ci-win-k4.json
diff /tmp/ci-win-k1.hashes /tmp/ci-win-k4.hashes
echo "fused-window smoke OK: K=4 bit-identical to K=1 with windows served"

echo "== tor C-twin smoke (tor_400relay: C tor control plane vs Python twin hash) =="
trun() {
    python -m shadow_tpu examples/tor_400relay.yaml --quiet --json-summary \
        --data-directory "/tmp/ci-tor-$1" \
        --scheduler-policy tpu_batch \
        --set "experimental.native_colcore=$2" \
        --set general.stop_time=10s \
        | python -c 'import json,sys; from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS as V; d=json.load(sys.stdin); [d.pop(k, None) for k in V]; print(json.dumps(d,sort_keys=True))' \
        > "/tmp/ci-tor-$1.json"
    (cd "/tmp/ci-tor-$1" && find hosts -type f | sort | xargs sha256sum) \
        > "/tmp/ci-tor-$1.hashes"
}
trun c true
trun py false
diff /tmp/ci-tor-c.json /tmp/ci-tor-py.json
diff /tmp/ci-tor-c.hashes /tmp/ci-tor-py.hashes
echo "tor C-twin smoke OK: C tor control plane bit-identical to the Python model ($(python -c 'import json;print(json.load(open("/tmp/ci-tor-c.json"))["events"])') events)"

echo "== checkpoint/resume smoke (tgen_100host: snapshot mid-run, resume, tree-hash equality) =="
rm -rf /tmp/ci-ckpt-full /tmp/ci-ckpt-src /tmp/ci-ckpt-resume
python -m shadow_tpu examples/tgen_100host.yaml --quiet \
    --data-directory /tmp/ci-ckpt-full
python -m shadow_tpu examples/tgen_100host.yaml --quiet \
    --data-directory /tmp/ci-ckpt-src --checkpoint-every 5s
ck=$(ls /tmp/ci-ckpt-src/checkpoints/ckpt_*.ckpt | head -1)
echo "resuming from $ck"
python -m shadow_tpu examples/tgen_100host.yaml --quiet \
    --data-directory /tmp/ci-ckpt-resume --resume-from "$ck"
(cd /tmp/ci-ckpt-full && find hosts -type f | sort | xargs sha256sum) \
    > /tmp/ci-ckpt-full.hashes
(cd /tmp/ci-ckpt-resume && find hosts -type f | sort | xargs sha256sum) \
    > /tmp/ci-ckpt-resume.hashes
diff /tmp/ci-ckpt-full.hashes /tmp/ci-ckpt-resume.hashes
echo "checkpoint/resume OK: resumed output tree bit-identical ($(wc -l < /tmp/ci-ckpt-full.hashes) files)"

echo "== fault-injection smoke (gossip_churn: partition heal + degrade + host churn) =="
python -m shadow_tpu examples/gossip_churn.yaml --quiet --json-summary \
    --data-directory /tmp/ci-churn \
    | python -c '
import json, sys
d = json.load(sys.stdin)
c = d["counters"]
trans = d["fault_transitions_applied"]
crashes, boots = c.get("host_crashes", 0), c.get("host_boots", 0)
bh, rto = d["units_blackholed"], c.get("stream_rto_retransmits", 0)
assert d["process_errors"] == [], d["process_errors"]
assert crashes > 0 and boots > 0, c
assert bh > 0, "partition cut no traffic"
assert rto > 0, "no transport recovery seen"
print(f"fault smoke OK: {trans} transitions, {crashes} crashes/"
      f"{boots} reboots, {bh} blackholed, {rto} RTO retransmits")
'

echo "== multi-shard smoke (gossip_churn: shards=2 vs shards=1, tree/stream hash diff) =="
shrun() {
    rm -rf "/tmp/ci-shard-$1"
    python -m shadow_tpu examples/gossip_churn.yaml --quiet --json-summary \
        --data-directory "/tmp/ci-shard-$1" \
        --scheduler-policy tpu_batch --shards "$2" \
        --set general.stop_time=40s \
        --state-digest-every 100 --sample-every 5s \
        | python -c 'import json,sys; from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS as V; d=json.load(sys.stdin); [d.pop(k, None) for k in V]; print(json.dumps(d,sort_keys=True))' \
        > "/tmp/ci-shard-$1.json"
    (cd "/tmp/ci-shard-$1" && find hosts -type f | sort | xargs sha256sum && \
     sha256sum flows.jsonl metrics.jsonl state_digests.jsonl) \
        > "/tmp/ci-shard-$1.hashes"
}
shrun one 1
shrun two 2
diff /tmp/ci-shard-one.json /tmp/ci-shard-two.json
diff /tmp/ci-shard-one.hashes /tmp/ci-shard-two.hashes
echo "multi-shard smoke OK: shards=2 byte-identical to the single-process run (trees + flows + metrics + digests)"

echo "== chaos self-healing smoke (supervised sharded run: 2 worker SIGKILLs + 1 ring-stall wedge auto-recover to the clean run's bytes; fleet: wedged member retried to ok) =="
chrun() {
    rm -rf "/tmp/ci-chaos-$1"
    env SHADOW_TPU_CHAOS="$2" \
        SHADOW_TPU_STALL_FLOOR_S=3 SHADOW_TPU_STALL_MULT=20 \
        python -m shadow_tpu examples/gossip_churn.yaml --quiet --json-summary \
        --data-directory "/tmp/ci-chaos-$1" \
        --scheduler-policy tpu_batch --shards 2 \
        --checkpoint-every 2s \
        --set general.stop_time=40s \
        --set "general.supervise={max_restarts: 4, backoff: 0.2}" \
        --state-digest-every 100 --sample-every 5s \
        > "/tmp/ci-chaos-$1.json"
    (cd "/tmp/ci-chaos-$1" && find hosts -type f | sort | xargs sha256sum && \
     sha256sum flows.jsonl metrics.jsonl state_digests.jsonl) \
        > "/tmp/ci-chaos-$1.hashes"
}
chrun clean ""
chrun hurt "s0:kill@r700,s1:kill@r1400,s0:wedge@r2000"
diff /tmp/ci-chaos-clean.hashes /tmp/ci-chaos-hurt.hashes
python - <<'EOF'
import json

d = json.load(open("/tmp/ci-chaos-hurt.json"))
s = d["supervisor"]
assert len(s["restarts"]) == 3, s  # every injection recovered from
reasons = " | ".join(r["reason"] for r in s["restarts"])
assert "died" in reasons, reasons            # the SIGKILLs, named
assert "dead or wedged" in reasons, reasons  # the wedge, named by shard
for r in s["restarts"]:
    assert r["mttr_s"] < 90, r  # bounded detection, never a hang
print(f"chaos self-healing smoke OK: 2 kills + 1 wedge recovered in "
      f"{s['attempts']} attempts (mttr "
      f"{[r['mttr_s'] for r in s['restarts']]}s), bytes == clean run")
EOF
rm -rf /tmp/ci-chaos-fleet
env SHADOW_TPU_FLEET_CHAOS_WEDGE_SEEDS=131 SHADOW_TPU_FLEET_STALL_S=8 \
    python -m shadow_tpu.fleet sweep examples/gossip_churn.yaml \
    --seeds 2 --seed-base 130 --jobs 2 --sweep-dir /tmp/ci-chaos-fleet \
    --set general.stop_time=10s --no-device-service --quiet --json \
    > /tmp/ci-chaos-fleet.json
python - <<'EOF'
import json

d = json.load(open("/tmp/ci-chaos-fleet.json"))
assert d["completed"] == [130, 131], d["failed"]
assert d["failed"] == {}, d["failed"]
assert d["respawns"] >= 1, d  # the wedged member WAS killed + respawned
print("chaos fleet smoke OK: wedged member detected, killed, retried to ok")
EOF

echo "== fleet smoke (3-seed gossip_churn sweep at jobs=2: per-seed identity vs standalone + CIs in sweep_summary) =="
rm -rf /tmp/ci-fleet /tmp/ci-fleet-solo-*
python -m shadow_tpu.fleet sweep examples/gossip_churn.yaml \
    --seeds 3 --seed-base 120 --jobs 2 --sweep-dir /tmp/ci-fleet \
    --set general.stop_time=25s --quiet --json > /tmp/ci-fleet.json
for s in 120 121 122; do
    # standalone twin of each sweep member (same stop + telemetry); the
    # workload may legitimately exit nonzero on process_errors at this
    # truncated stop time — the hash comparison below is the gate
    python -m shadow_tpu examples/gossip_churn.yaml --quiet --seed "$s" \
        --data-directory "/tmp/ci-fleet-solo-$s" \
        --set general.stop_time=25s --sample-every 10s || true
done
python - <<'EOF'
import json
from shadow_tpu import fleet

summary = json.load(open("/tmp/ci-fleet.json"))
assert summary["completed"] == [120, 121, 122], summary["failed"]
for s in (120, 121, 122):
    d = fleet.seed_dir("/tmp/ci-fleet", s)
    man = json.loads((d / fleet.SEED_MANIFEST).read_text())
    solo = f"/tmp/ci-fleet-solo-{s}"
    assert fleet.output_tree_digest(d) == fleet.output_tree_digest(solo), \
        f"seed {s}: in-fleet tree != standalone tree"
    assert fleet._stream_digests(d) == fleet._stream_digests(solo), \
        f"seed {s}: streams diverged"
    assert man["tree_sha256"] == fleet.output_tree_digest(solo)
doc = json.loads((fleet.Path("/tmp/ci-fleet") / fleet.SWEEP_SUMMARY)
                 .read_text())
assert doc["format"] == "shadow_tpu-sweep-summary"
assert doc["flows"], "sweep summary has no flow groups"
for kind, row in doc["flows"].items():
    ci = row["ci95"]["p50_ms"]
    assert ci["n"] == 3 and ci["lo"] <= ci["mean"] <= ci["hi"], (kind, ci)
    assert set(row["pooled"]) >= {"p50_ms", "p99_ms"}
print(f"fleet smoke OK: 3 seeds byte-identical to standalone, "
      f"{len(doc['flows'])} flow group(s) with t-based CI95 in "
      f"sweep_summary.json")
EOF

echo "== fork smoke (gossip_churn: 3-branch what-if fork off a mid-run checkpoint, reducer diff + bisect first-divergence) =="
rm -rf /tmp/ci-fork-trunk /tmp/ci-fork
# the trunk: one checkpointing run; the 10s snapshot is the fork point.
# The workload may legitimately exit nonzero on process_errors at this
# truncated stop time — the branch assertions below are the gate.
python -m shadow_tpu examples/gossip_churn.yaml --quiet \
    --data-directory /tmp/ci-fork-trunk \
    --set general.stop_time=25s --checkpoint-every 10s \
    --state-digest-every 100 --sample-every 5s || true
ck=$(ls /tmp/ci-fork-trunk/checkpoints/ckpt_*.ckpt | head -1)
echo "forking from $ck"
# one branch diverges via an injected live-command script (replayed
# through the commands.jsonl machinery), one changes the seed (an
# honest cold re-run: the seed is part of the config identity)
cat > /tmp/ci-fork-cmds.jsonl <<'EOF'
{"cmd": {"cmd": "link_degrade", "src_nodes": [0], "dst_nodes": [1], "latency_factor": 3.0, "loss_add": 0.05, "bandwidth_scale": 0.5, "duration": "3000000000 ns"}, "round": 0, "seq": 1, "t": 15000000000}
EOF
cat > /tmp/ci-fork-branches.yaml <<'EOF'
branches:
  - name: baseline
  - name: lossy
    command_script: /tmp/ci-fork-cmds.jsonl
  - name: seed9
    seed: 9
EOF
python -m shadow_tpu fork examples/gossip_churn.yaml \
    --from "$ck" --branches /tmp/ci-fork-branches.yaml \
    --fork-dir /tmp/ci-fork --jobs 3 --quiet \
    --set general.stop_time=25s --set general.checkpoint_every=10s \
    --set general.state_digest_every=100 --set telemetry.sample_every=5s \
    > /tmp/ci-fork-report.txt
python tools/compare.py /tmp/ci-fork --json > /tmp/ci-fork-summary.json
python - <<'EOF'
import json
from shadow_tpu import fleet, forks

s = json.load(open("/tmp/ci-fork-summary.json"))
assert s["completed"] == ["baseline", "lossy", "seed9"], s["failed"]
b = s["branches"]
assert b["baseline"]["mode"] == "restore" and b["lossy"]["mode"] == "restore"
assert b["seed9"]["mode"] == "cold" and "seed" in (
    json.loads((forks.branch_dir("/tmp/ci-fork", "seed9")
                / forks.FORK_MANIFEST).read_text())["cold_reason"])
# the honesty gate, spot-checked in CI: the no-divergence restore
# branch IS the trunk run, byte for byte (tree + streams)
assert (fleet.output_tree_digest(forks.branch_dir("/tmp/ci-fork", "baseline"))
        == fleet.output_tree_digest("/tmp/ci-fork-trunk")), \
    "baseline branch tree != trunk tree"
assert (fleet._stream_digests(forks.branch_dir("/tmp/ci-fork", "baseline"))
        == fleet._stream_digests("/tmp/ci-fork-trunk")), \
    "baseline branch streams != trunk streams"
assert s["trunk_flows"], "reducer found no trunk flow telemetry"
report = open("/tmp/ci-fork-report.txt").read()
assert "Δp50" in report and "CI95" in report, report
EOF
# bisect localizes the what-if: the undiverged branch agrees with the
# trunk (exit 0); the command-injected branch names its first divergent
# round, strictly after the fork boundary
python tools/bisect_divergence.py \
    --a /tmp/ci-fork-trunk --b /tmp/ci-fork/branch_baseline
rc=0
python tools/bisect_divergence.py --json \
    --a /tmp/ci-fork-trunk --b /tmp/ci-fork/branch_lossy \
    > /tmp/ci-fork-bisect.json || rc=$?
test "$rc" -eq 1
python - "$ck" <<'EOF'
import json, sys
from shadow_tpu import checkpoint as ckpt

d = json.load(open("/tmp/ci-fork-bisect.json"))
fork_rounds = ckpt.read_header(sys.argv[1])["rounds"]
assert d["kind"] == "digest", d
assert d["round"] > fork_rounds, (d, fork_rounds)
assert d["t"] >= 15_000_000_000, d  # not before the injected command
print(f"fork smoke OK: baseline byte-identical to the trunk, lossy "
      f"branch first diverges at round {d['round']} "
      f"(t={d['t']} ns, fork point round {fork_rounds})")
EOF

echo "== fast+robust smoke (gossip_churn: faults + checkpoints + digests with the C engine ON vs the Python plane) =="
frrun() {
    rm -rf "/tmp/ci-fr-$1"
    python -m shadow_tpu examples/gossip_churn.yaml --quiet --json-summary \
        --data-directory "/tmp/ci-fr-$1" \
        --scheduler-policy tpu_batch \
        --set "experimental.native_colcore=$2" \
        --checkpoint-every 10s --state-digest-every 100 --sample-every 5s \
        | python -c 'import json,sys; from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS as V; d=json.load(sys.stdin); [d.pop(k, None) for k in V]; print(json.dumps(d,sort_keys=True))' \
        > "/tmp/ci-fr-$1.json"
    (cd "/tmp/ci-fr-$1" && find hosts -type f | sort | xargs sha256sum && \
     sha256sum flows.jsonl metrics.jsonl state_digests.jsonl) \
        > "/tmp/ci-fr-$1.hashes"
}
frrun c true
frrun py false
diff /tmp/ci-fr-c.json /tmp/ci-fr-py.json
diff /tmp/ci-fr-c.hashes /tmp/ci-fr-py.hashes
python - <<'EOF'
from pathlib import Path
from shadow_tpu import checkpoint as ckpt
from shadow_tpu.native import _colcore
paths = sorted(Path('/tmp/ci-fr-c/checkpoints').glob('*.ckpt'))
assert paths, 'C run wrote no checkpoints'
h = ckpt.read_header(paths[0])
assert h['colcore'] == _colcore.ABI, f"checkpoint missing colcore ABI: {h}"
print(f"fast+robust smoke OK: churned+checkpointed+digested C run "
      f"bit-identical to the Python plane ({len(paths)} C-state "
      f"checkpoints, colcore ABI {h['colcore']})")
EOF

echo "== modern-web smoke (web_cdn: cross-policy + C on/off hashes, SACK counters) =="
webrun() {
    rm -rf "/tmp/ci-web-$1"
    python -m shadow_tpu examples/web_cdn.yaml --quiet --json-summary \
        --data-directory "/tmp/ci-web-$1" \
        --scheduler-policy "$2" \
        --set "experimental.native_colcore=$3" \
        --set general.stop_time=26s \
        | python -c 'import json,sys; from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS as V; d=json.load(sys.stdin); [d.pop(k, None) for k in V]; print(json.dumps(d,sort_keys=True))' \
        > "/tmp/ci-web-$1.json"
    (cd "/tmp/ci-web-$1" && find hosts -type f | sort | xargs -r sha256sum && \
     sha256sum flows.jsonl metrics.jsonl) > "/tmp/ci-web-$1.hashes"
}
webrun tpc thread_per_core true
webrun tpu tpu_batch true
webrun py tpu_batch false
diff /tmp/ci-web-tpc.json /tmp/ci-web-tpu.json
diff /tmp/ci-web-tpu.json /tmp/ci-web-py.json
diff /tmp/ci-web-tpc.hashes /tmp/ci-web-tpu.hashes
diff /tmp/ci-web-tpu.hashes /tmp/ci-web-py.hashes
python - <<'EOF'
import json
d = json.load(open("/tmp/ci-web-tpu.json"))
c = d["counters"]
flows = d["telemetry"]["flows"]
for kind in ("web.fetch", "web.origin", "dns.resolve"):
    assert flows.get(kind, {}).get("count", 0) > 0, f"no {kind} flows"
assert c.get("stream_fast_retransmits", 0) > 0, "no fast retransmits"
assert c.get("stream_sack_retransmits", 0) > 0, \
    "SACK recovered no extra holes under the lossy degrade window"
print(f"modern-web smoke OK: {d['events']} events bit-identical across "
      f"thread_per_core/tpu_batch and C on/off; "
      f"{flows['web.fetch']['count']} fetches, "
      f"{c['stream_sack_retransmits']} SACK hole retransmits")
EOF

echo "== ABR smoke (abr_1k: C on/off hash + report ABR rows) =="
abrrun() {
    rm -rf "/tmp/ci-abr-$1"
    python -m shadow_tpu examples/abr_1k.yaml --quiet --json-summary \
        --data-directory "/tmp/ci-abr-$1" \
        --scheduler-policy tpu_batch \
        --set "experimental.native_colcore=$2" \
        --set general.stop_time=16s \
        | python -c 'import json,sys; from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS as V; d=json.load(sys.stdin); [d.pop(k, None) for k in V]; print(json.dumps(d,sort_keys=True))' \
        > "/tmp/ci-abr-$1.json"
    (cd "/tmp/ci-abr-$1" && sha256sum flows.jsonl metrics.jsonl) \
        > "/tmp/ci-abr-$1.hashes"
}
abrrun c true
abrrun py false
diff /tmp/ci-abr-c.json /tmp/ci-abr-py.json
diff /tmp/ci-abr-c.hashes /tmp/ci-abr-py.hashes
python tools/metrics_report.py /tmp/ci-abr-c --json | python -c '
import json, sys
r = json.load(sys.stdin)
assert r["abr"], "report has no ABR rows"
seg = sum(g["segments"] for g in r["abr"])
assert seg > 0, r["abr"]
assert any(g["mean_rate_bps"] > 0 for g in r["abr"])
groups = len(r["abr"])
print(f"ABR smoke OK: C on/off bit-identical, {seg} segments across "
      f"{groups} host-groups in the report")
'

echo "== device-transport smoke (web_cdn: devt on/off identity; web_cdn_100k: 100k-host short leg) =="
devtrun() {
    rm -rf "/tmp/ci-devt-$1"
    python -m shadow_tpu examples/web_cdn.yaml --quiet --json-summary \
        --data-directory "/tmp/ci-devt-$1" \
        --scheduler-policy tpu_batch \
        --set experimental.native_colcore=false \
        --set "experimental.device_transport=$2" \
        --set general.stop_time=26s \
        > "/tmp/ci-devt-$1.raw.json"
    python -c 'import json,sys; from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS as V; d=json.load(open(sys.argv[1])); [d.pop(k, None) for k in V]; print(json.dumps(d,sort_keys=True))' \
        "/tmp/ci-devt-$1.raw.json" > "/tmp/ci-devt-$1.json"
    (cd "/tmp/ci-devt-$1" && find hosts -type f | sort | xargs -r sha256sum && \
     sha256sum flows.jsonl metrics.jsonl) > "/tmp/ci-devt-$1.hashes"
}
devtrun off false
devtrun on true
diff /tmp/ci-devt-off.json /tmp/ci-devt-on.json
diff /tmp/ci-devt-off.hashes /tmp/ci-devt-on.hashes
# the committed 100k-endpoint config builds and runs end to end with the
# columnar transport engaged (short leg: the full 6s config is the bench
# row's artifact — bench.py web_cdn_100k_row)
rm -rf /tmp/ci-devt-100k
python -m shadow_tpu examples/web_cdn_100k.yaml --quiet --json-summary \
    --data-directory /tmp/ci-devt-100k \
    --scheduler-policy tpu_batch \
    --set experimental.native_colcore=false \
    --set experimental.device_transport=true \
    --stop-time 500ms > /tmp/ci-devt-100k.json
python - <<'EOF'
import json
# vacuity guards: BOTH devt-on runs must actually have advanced cohorts
# through the batched kernel — otherwise the identity diffs above
# compared scalar against scalar and prove nothing
on = json.load(open("/tmp/ci-devt-on.raw.json"))
assert on.get("device_transport_engaged"), \
    "web_cdn on-leg advanced zero cohorts — the identity diff is vacuous"
big = json.load(open("/tmp/ci-devt-100k.json"))
assert big.get("device_transport_engaged"), \
    "100k leg advanced zero cohorts through the batched kernel"
dt = big.get("device_transport", {})
print(f"device-transport smoke OK: web_cdn byte-identical on/off "
      f"({on['device_transport']['cohorts']} cohorts served); "
      f"100k-host leg ran {big['events']} events, "
      f"{dt.get('cohorts')} cohorts / {dt.get('acks_batched')} acks "
      f"batched, {dt.get('misguesses')} misguesses")
EOF

echo "== telemetry smoke (gossip_churn: cross-policy stream hashes + report parse) =="
telrun() {
    python -m shadow_tpu examples/gossip_churn.yaml --quiet \
        --data-directory "/tmp/ci-tel-$1" \
        --scheduler-policy "$2" --sample-every 5s > /dev/null
    sha256sum "/tmp/ci-tel-$1/metrics.jsonl" "/tmp/ci-tel-$1/flows.jsonl" \
        | awk '{print $1}' > "/tmp/ci-tel-$1.hashes"
}
telrun a tpu_batch
telrun b thread_per_core
diff /tmp/ci-tel-a.hashes /tmp/ci-tel-b.hashes
python tools/metrics_report.py /tmp/ci-tel-a --json | python -c '
import json, sys
r = json.load(sys.stdin)
assert r["samples"] > 0, "no telemetry samples"
assert r["flows"] > 0, "no flow records"
assert r["fault_transitions"] > 0, "fault timeline missing from metrics"
assert r["fault_windows"], "no fault windows folded"
print(f"telemetry smoke OK: {r[\"samples\"]} samples, {r[\"flows\"]} flows, "
      f"{r[\"fault_transitions\"]} fault transitions, streams bit-identical "
      f"across tpu_batch/thread_per_core")
'

echo "== managed smoke (managed_smoke.yaml: real binaries, shim fast plane on/off identity) =="
make -C native -s
mrun() {
    rm -rf "/tmp/ci-managed-$1"
    SHADOW_TPU_SHIM_FASTPATH=$2 \
    python -m shadow_tpu examples/managed_smoke.yaml --quiet --json-summary \
        --data-directory "/tmp/ci-managed-$1" \
        > "/tmp/ci-managed-$1.raw.json"
    # shim_fast_* counters are informational (they say WHERE a syscall
    # completed, not WHAT the simulation did) and legitimately differ
    # across the two legs — everything else must be byte-identical
    python -c 'import json,sys; from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS as V; d=json.load(open(sys.argv[1])); [d.pop(k, None) for k in V]; d["counters"]={k:v for k,v in d["counters"].items() if not k.startswith("shim_fast_")}; print(json.dumps(d,sort_keys=True))' \
        "/tmp/ci-managed-$1.raw.json" > "/tmp/ci-managed-$1.json"
    # *.clock is the process's live shim scratch page (fast-op counters,
    # flags, oplog residue) file-backed into the data dir — plumbing of
    # the same informational class as shim_fast_*, not an observable
    (cd "/tmp/ci-managed-$1" && find hosts -type f ! -name "*.clock" \
        | sort | xargs sha256sum) > "/tmp/ci-managed-$1.hashes"
}
mrun fast 1
mrun slow 0
diff /tmp/ci-managed-fast.json /tmp/ci-managed-slow.json
diff /tmp/ci-managed-fast.hashes /tmp/ci-managed-slow.hashes
python - <<'EOF'
import json
fast = json.load(open("/tmp/ci-managed-fast.raw.json"))
slow = json.load(open("/tmp/ci-managed-slow.raw.json"))
c = fast["counters"]
assert fast["process_errors"] == [], fast["process_errors"]
# vacuity guards: the fast leg must actually have completed a majority
# of its syscalls in-shim, and the slow leg must actually have been slow
# — otherwise the identity diffs above compared like against like
assert c.get("shim_fast_syscalls", 0) * 2 > c["syscalls"], c
assert slow["counters"].get("shim_fast_ring_read", 0) == 0, slow["counters"]
out = open("/tmp/ci-managed-fast/hosts/client/ring_probe.0.stdout").read()
assert "bytes=300000" in out and "eof=1" in out, out
print(f"managed smoke OK: transfer byte-exact both legs, "
      f"{c['shim_fast_syscalls']}/{c['syscalls']} syscalls in-shim on "
      f"the fast leg, observables bit-identical fast on/off")
EOF

echo "== managed-checkpoint smoke (managed_smoke.yaml: reexec snapshot mid-transfer, resume, identity) =="
mckrun() {   # $1 = tag, rest = extra args
    local tag=$1; shift
    rm -rf "/tmp/ci-mckpt-$tag"
    python -m shadow_tpu examples/managed_smoke.yaml --quiet --json-summary \
        --data-directory "/tmp/ci-mckpt-$tag" --state-digest-every 5 "$@" \
        > "/tmp/ci-mckpt-$tag.raw.json"
    python -c 'import json,sys; from shadow_tpu.core.controller import VOLATILE_SUMMARY_KEYS as V; d=json.load(open(sys.argv[1])); [d.pop(k, None) for k in V]; print(json.dumps(d,sort_keys=True))' \
        "/tmp/ci-mckpt-$tag.raw.json" > "/tmp/ci-mckpt-$tag.json"
    # *.clock excluded for the same reason as the managed gate above
    (cd "/tmp/ci-mckpt-$tag" && find hosts -type f ! -name "*.clock" \
        | sort | xargs sha256sum) > "/tmp/ci-mckpt-$tag.hashes"
}
mckrun full
mckrun src --checkpoint-every 500ms
ck=$(ls /tmp/ci-mckpt-src/checkpoints/ckpt_*.ckpt | head -1)
echo "resuming managed run from $ck (re-execution)"
mckrun resume --resume-from "$ck"
# the checkpointing run itself is unperturbed, and the resumed run
# reproduces the uninterrupted one: summaries, host trees, digest stream
diff /tmp/ci-mckpt-full.json /tmp/ci-mckpt-src.json
diff /tmp/ci-mckpt-full.hashes /tmp/ci-mckpt-src.hashes
diff /tmp/ci-mckpt-full.json /tmp/ci-mckpt-resume.json
diff /tmp/ci-mckpt-full.hashes /tmp/ci-mckpt-resume.hashes
cmp /tmp/ci-mckpt-full/state_digests.jsonl /tmp/ci-mckpt-resume/state_digests.jsonl
python - "$ck" <<'EOF'
import json, sys
hdr = json.loads(open(sys.argv[1]).readline())
assert hdr["mode"] == "reexec" and hdr["managed"] is True, hdr
assert hdr["version"] == 5, hdr
payload = json.loads(open(sys.argv[1]).read().splitlines()[1])
assert payload["cursors"], "snapshot carries no guest journal cursors"
for tag in ("full", "src", "resume"):
    s = json.load(open(f"/tmp/ci-mckpt-{tag}.raw.json"))
    assert s["process_errors"] == [], (tag, s["process_errors"])
print(f"managed-checkpoint smoke OK: v5 reexec snapshot "
      f"({len(payload['cursors'])} journal cursor(s)) resumed "
      f"byte-identical — trees, summaries, digest stream")
EOF

echo "== live-ops smoke (gossip_churn: --follow attach + live link_down + replay tree-hash identity) =="
rm -rf /tmp/ci-live /tmp/ci-live-replay /tmp/ci-live.sock
# follower first: it retries the connect until the run binds the socket
python tools/metrics_report.py --follow /tmp/ci-live.sock \
    --follow-timeout 120 > /tmp/ci-live-follow.txt &
follow_pid=$!
python -m shadow_tpu examples/gossip_churn.yaml --quiet --json-summary \
    --data-directory /tmp/ci-live --scheduler-policy tpu_batch \
    --set general.stop_time=25s --set general.heartbeat_interval=2s \
    --live-endpoint /tmp/ci-live.sock \
    --state-digest-every 100 --sample-every 5s > /tmp/ci-live.json &
run_pid=$!
# inject a runtime fault into the RUNNING sim; the ack is the gate
python -m shadow_tpu.live send /tmp/ci-live.sock \
    '{"cmd":"link_down","src_nodes":[0],"dst_nodes":[1],"duration":"3s"}' \
    > /tmp/ci-live-ack.json
# the workload may legitimately exit nonzero on process_errors at this
# truncated stop time — the hash comparison below is the gate
wait "$run_pid" || true
wait "$follow_pid"
python -m shadow_tpu examples/gossip_churn.yaml --quiet --json-summary \
    --data-directory /tmp/ci-live-replay --scheduler-policy tpu_batch \
    --set general.stop_time=25s \
    --replay-commands /tmp/ci-live/commands.jsonl \
    --state-digest-every 100 --sample-every 5s > /tmp/ci-live-replay.json \
    || true
for d in /tmp/ci-live /tmp/ci-live-replay; do
    (cd "$d" && find hosts -type f | sort | xargs sha256sum && \
     sha256sum commands.jsonl flows.jsonl metrics.jsonl state_digests.jsonl) \
        > "$d.hashes"
done
diff /tmp/ci-live.hashes /tmp/ci-live-replay.hashes
python - <<'EOF'
import json

ack = json.load(open("/tmp/ci-live-ack.json"))
assert ack["type"] == "ack", ack
follow = open("/tmp/ci-live-follow.txt").read().splitlines()
hbs = [ln for ln in follow if ln.startswith("hb  ")]
samples = [ln for ln in follow if ln.startswith("sample @")]
assert len(hbs) >= 3, f"want >=3 heartbeats, got {len(hbs)}"
assert samples, "no telemetry samples reached the follower"
assert any(ln.startswith("command applied: link_down") for ln in follow), \
    "follower never saw the injected command"
assert any(ln.startswith("run ended:") for ln in follow), \
    "follower missed the end record"
live = json.load(open("/tmp/ci-live.json"))
assert live["exit_reason"] == "completed", live
assert live.get("fault_transitions_applied", 0) >= 2, live
replay = json.load(open("/tmp/ci-live-replay.json"))
assert replay["exit_reason"] == "completed", replay
print(f"live-ops smoke OK: {len(hbs)} heartbeats + {len(samples)} samples "
      f"followed, link_down ack'd + applied, replay-from-commands.jsonl "
      f"byte-identical (trees + flows + metrics + digests + command log)")
EOF

echo "== CI gate passed =="
