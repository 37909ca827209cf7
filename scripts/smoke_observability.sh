#!/usr/bin/env bash
# Smoke-check the observability pipeline end to end on one machine:
# a tiny standalone launch with $TPU_RESILIENCY_EVENTS_FILE set must yield an
# events JSONL from which BOTH the Chrome-trace export and the metrics dump
# produce non-empty, schema-valid output. Exits non-zero on any gap.
#
# Usage: scripts/smoke_observability.sh [workdir]
set -euo pipefail

cd "$(dirname "$0")/.."
WORKDIR="${1:-$(mktemp -d /tmp/tpu_obs_smoke.XXXXXX)}"
mkdir -p "$WORKDIR"
EVENTS="$WORKDIR/events.jsonl"
export JAX_PLATFORMS=cpu
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"

echo "== smoke: standalone launch (1 fault, 1 restart) -> $EVENTS"
cat > "$WORKDIR/worker.py" <<'PY'
import os, sys
round_no = int(os.environ["TPU_FT_RESTART_COUNT"])
if round_no == 0:
    sys.exit(3)
print("recovered in round", round_no)
PY
python -m tpu_resiliency.launcher.launch \
    --standalone --nproc-per-node 1 --max-restarts 2 --no-ft-monitors \
    --rdzv-last-call 0.2 --monitor-interval 0.1 \
    --events-file "$EVENTS" --run-dir "$WORKDIR/run" \
    "$WORKDIR/worker.py"

test -s "$EVENTS" || { echo "FAIL: events file empty"; exit 1; }

echo "== smoke: trace export"
python -m tpu_resiliency.tools.trace_export "$EVENTS" -o "$WORKDIR/trace.json"
python - "$WORKDIR/trace.json" <<'PY'
import json, sys
doc = json.load(open(sys.argv[1]))
evs = doc["traceEvents"]
assert evs, "empty traceEvents"
assert all({"name", "ph", "pid"} <= set(e) for e in evs), "malformed trace event"
slices = {e["name"] for e in evs if e["ph"] == "X"}
assert "launcher.job" in slices and "launcher.round" in slices, slices
assert sum(1 for e in evs if e["ph"] == "X" and e["name"] == "launcher.round") >= 2, \
    "restart chain missing its second round"
print(f"trace OK: {len(evs)} events, spans: {sorted(slices)}")
PY

echo "== smoke: metrics dump"
python -m tpu_resiliency.tools.metrics_dump "$EVENTS" --format json -o "$WORKDIR/metrics.json"
python - "$WORKDIR/metrics.json" <<'PY'
import json, sys
doc = json.load(open(sys.argv[1]))
m = doc["metrics"]
assert m, "empty metrics"
restarts = sum(e["value"] for e in m.get("tpu_restarts_total", []))
assert restarts >= 1, f"no restarts aggregated: {sorted(m)}"
spans = m.get("tpu_span_seconds", [])
assert any(e["labels"].get("span") == "rendezvous.round" and e["count"] >= 1
           for e in spans), "no rendezvous duration quantiles"
print(f"metrics OK: {len(m)} families, restarts={int(restarts)}")
PY
python -m tpu_resiliency.tools.metrics_dump "$EVENTS" | sed 's/^/    /'

echo "== smoke: checkpoint integrity (v2 checksums + ckpt_info --verify preflight)"
python - "$WORKDIR" <<'PY'
import os, sys
import numpy as np
from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager
from tpu_resiliency.checkpoint.state_dict import PyTreeStateDict

root = os.path.join(sys.argv[1], "ckpt_root")
mgr = LocalCheckpointManager(root, rank=0)
mgr.save(1, PyTreeStateDict({"w": np.arange(4096, dtype=np.float32)}), is_async=False)
mgr.close()
PY
python -m tpu_resiliency.tools.ckpt_info "$WORKDIR/ckpt_root" --verify
python - "$WORKDIR" <<'PY'
import os, sys
rdir = os.path.join(sys.argv[1], "ckpt_root", "s0", "r0")
path = [os.path.join(rdir, n) for n in os.listdir(rdir) if n.endswith(".ckpt")][0]
with open(path, "r+b") as f:          # flip one payload bit
    f.seek(os.path.getsize(path) // 2)
    b = f.read(1); f.seek(-1, 1); f.write(bytes([b[0] ^ 1]))
PY
if python -m tpu_resiliency.tools.ckpt_info "$WORKDIR/ckpt_root" --verify; then
    echo "FAIL: ckpt_info --verify missed an injected bit flip"; exit 1
else
    echo "integrity OK: --verify caught the flipped bit (exit 1 as designed)"
fi
# The chunk-manifest view must LOCATE the flip (exact leaf/chunk coordinates).
if python -m tpu_resiliency.tools.ckpt_info "$WORKDIR/ckpt_root" --chunks > "$WORKDIR/chunks.out" 2>&1; then
    echo "FAIL: ckpt_info --chunks missed the injected bit flip"; exit 1
fi
sed 's/^/    /' "$WORKDIR/chunks.out"
grep -q "chunk" "$WORKDIR/chunks.out" || { echo "FAIL: --chunks named no chunk"; exit 1; }
echo "chunk-manifest OK: --chunks located the corrupt chunk (exit 1 as designed)"

echo "== smoke: goodput plane (live /metrics + /goodput on the launcher vs offline --goodput)"
GP="$WORKDIR/goodput"
mkdir -p "$GP"
cat > "$GP/worker.py" <<'PY'
import os, sys, time
import numpy as np
from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager
from tpu_resiliency.checkpoint.state_dict import PyTreeStateDict
from tpu_resiliency.utils.events import record

stop, ckpt_root = sys.argv[1], sys.argv[2]
round_no = int(os.environ["TPU_FT_RESTART_COUNT"])
rank = int(os.environ.get("RANK", "0"))
for i in range(10):
    record("inprocess", "iteration_start", iteration=i)
    time.sleep(0.05)
m = LocalCheckpointManager(ckpt_root, rank=rank)
m.save(round_no, PyTreeStateDict({"w": np.arange(8192, dtype=np.float32)}), is_async=False)
m.close()
if round_no == 0 and rank == 0:
    sys.exit(3)  # round 0 fault: the restart phase must show up in /goodput
i = 10
deadline = time.time() + 90
while not os.path.exists(stop) and time.time() < deadline:
    record("inprocess", "iteration_start", iteration=i)
    i += 1
    time.sleep(0.05)
PY
python -m tpu_resiliency.launcher.launch \
    --standalone --nproc-per-node 2 --max-restarts 2 --no-ft-monitors \
    --rdzv-last-call 0.2 --monitor-interval 0.1 --telemetry-port 0 \
    --events-file "$GP/events.jsonl" --run-dir "$GP/run" \
    "$GP/worker.py" "$GP/stop" "$GP/ckpt" &
GP_PID=$!
python - "$GP" <<'PY'
import json, os, sys, time, urllib.error, urllib.request

gp = sys.argv[1]
port_file = os.path.join(gp, "run", "telemetry.port")
deadline = time.time() + 60
while not os.path.exists(port_file):
    assert time.time() < deadline, "telemetry.port handshake file never appeared"
    time.sleep(0.2)
port = int(open(port_file).read().strip())
summary = None
while time.time() < deadline:
    try:
        summary = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/goodput", timeout=5).read())
    except OSError:
        time.sleep(0.3)
        continue
    ph = summary["phases"]
    if ph["train"] > 0 and ph["ckpt_stall"] > 0 and ph["restart"] > 0:
        break
    time.sleep(0.3)
ph = summary["phases"]
assert ph["train"] > 0 and ph["ckpt_stall"] > 0 and ph["restart"] > 0, summary
wall = summary["wall_clock_s"]
assert abs(sum(ph.values()) - wall) <= 0.05 * wall, (
    f"attribution phases {ph} do not sum to wall clock {wall}")
prom = urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
assert "tpu_goodput_ratio" in prom, prom[:2000]
assert "tpu_time_attributed_seconds_total" in prom, prom[:2000]
assert "tpu_step_seconds_bucket" in prom, prom[:2000]
# Forensics plane: the live /storez document must answer 200 with nonzero
# op counts from the launcher-hosted coordination store.
sz = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{port}/storez", timeout=5).read())
assert sz["schema"] == "tpu-storez-1", sz
assert sz.get("enabled") is True, sz
assert sum(r.get("count", 0) for r in (sz.get("ops") or {}).values()) > 0, sz
print(f"/storez OK: {len(sz.get('ops') or {})} op families, "
      f"conns={sz.get('conns')}")
try:
    hz = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=5).read())
except urllib.error.HTTPError as e:
    hz = json.loads(e.read())  # 503 mid-restart still carries the document
assert "healthy" in hz, hz
print(f"goodput live OK: ratio={summary['goodput_ratio']} phases={ph}")
PY
touch "$GP/stop"
wait "$GP_PID"
python -m tpu_resiliency.tools.metrics_dump "$GP/events.jsonl" --goodput | sed 's/^/    /'
python -m tpu_resiliency.tools.metrics_dump "$GP/events.jsonl" --goodput --format json | \
    python -c "import json,sys; d=json.load(sys.stdin); assert d['phases']['restart']>0 and d['phases']['ckpt_stall']>0, d" \
    || { echo "FAIL: offline --goodput lost the restart/ckpt attribution"; exit 1; }

echo "== smoke: performance forensics (critical path + byte-flow ledger + store op storm)"
# The restart episode in the goodput run's stream must name rendezvous.round
# on its critical path, and the milestone decomposition must be present.
CP=$(python -m tpu_resiliency.tools.critpath "$GP/events.jsonl" --episode restart)
echo "$CP" | sed 's/^/    /'
echo "$CP" | grep -q "rendezvous.round" \
    || { echo "FAIL: rendezvous.round missing from the restart critical path"; exit 1; }
echo "$CP" | grep -q "rendezvous " \
    || { echo "FAIL: milestone segments missing from tpu-critpath output"; exit 1; }
# Highlighted trace export round-trips.
python -m tpu_resiliency.tools.critpath "$GP/events.jsonl" --trace "$GP/crit.trace.json" > /dev/null
python - "$GP/crit.trace.json" <<'PY'
import json, sys
doc = json.load(open(sys.argv[1]))
crit = [e for e in doc["traceEvents"] if e.get("args", {}).get("critical_path")]
assert crit, "no critical-path spans highlighted in the trace"
assert all("self_time_ms" in e["args"] for e in doc["traceEvents"]
           if e.get("ph") == "X"), "span slices lost self_time_ms"
print(f"highlighted trace OK: {len(crit)} critical-path spans")
PY
# Byte-flow ledger: the run's bytes attribute to purposes with <5% residue.
python -m tpu_resiliency.tools.metrics_dump "$GP/events.jsonl" --bytes | sed 's/^/    /'
python -m tpu_resiliency.tools.metrics_dump "$GP/events.jsonl" --bytes --format json | \
    python -c "import json,sys; d=json.load(sys.stdin); assert d['total_bytes']>0 and d['accounted_frac']>=0.95, d" \
    || { echo "FAIL: byte-flow ledger residue exceeds 5%"; exit 1; }

echo "== smoke: store scale (clique shard map + per-shard op totals render)"
SSDIR="$WORKDIR/store_scale"
mkdir -p "$SSDIR"
python - "$SSDIR" <<'PY'
import subprocess, sys
from tpu_resiliency.platform.shardstore import CLIQUE_KEY, SpawnedClique
from tpu_resiliency.platform.store import CoordStore

clique = SpawnedClique(2)
try:
    shard0 = CoordStore(*clique.endpoints[0])
    shard0.set(CLIQUE_KEY, clique.spec)
    st = clique.client()
    for i in range(32):
        st.set(f"smoke/{i}", i)
    # Single classic endpoint in, whole-clique aggregate out (discovery).
    out = subprocess.run(
        [sys.executable, "-m", "tpu_resiliency.tools.store_info",
         f"127.0.0.1:{clique.port}", "--stats"],
        capture_output=True, text=True, timeout=60,
    )
    sys.stdout.write(out.stdout)
    assert out.returncode == 0, out.stderr
    assert "backend: epoll" in out.stdout, out.stdout
    assert "shards: 2 (crc32" in out.stdout, out.stdout
    assert "per-shard op totals:" in out.stdout, out.stdout
    assert out.stdout.count("epoll") >= 3, out.stdout  # header + 2 shard rows
    st.close(); shard0.close()
finally:
    clique.close()
print("store-scale stats render OK: backend + shard map + per-shard totals")
PY

echo "== smoke: elastic reshard plan preflight (ckpt_info --plan)"
RS="$WORKDIR/reshard"
mkdir -p "$RS"
python - "$RS" <<'PY'
import os, sys
import numpy as np
from tpu_resiliency.checkpoint import reshard as R
from tpu_resiliency.checkpoint.local_manager import LocalCheckpointManager
from tpu_resiliency.checkpoint.state_dict import PyTreeStateDict

root = os.path.join(sys.argv[1], "root")
G = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
layout = R.TreeLayout([("dp", 2)], [0, 1], [R.LeafSpec(G.shape, "float32", ("dp",))])
for rank in (0, 1):
    m = LocalCheckpointManager(root, rank=rank)
    m.save(1, PyTreeStateDict({"w": R.slice_local([G], layout, rank)[0]}),
           is_async=False, layout=layout)
    m.close()
PY
python -m tpu_resiliency.tools.ckpt_info "$RS/root" --world 0 --plan | sed 's/^/    /'
rm -rf "$RS/root/s0/r1"
if python -m tpu_resiliency.tools.ckpt_info "$RS/root" --world 0 --plan > "$RS/plan.out" 2>&1; then
    echo "FAIL: --plan missed the uncovered source rank"; exit 1
else
    grep -q "UNCOVERED" "$RS/plan.out" || { echo "FAIL: --plan exit 1 without naming the gap"; exit 1; }
    echo "reshard plan OK: --plan caught the uncovered rank (exit 1 as designed)"
fi

echo "== smoke: chaos (seeded fault injection across store/p2p/ipc/disk channels + mixed campaign + elastic chain + store failover)"
python scripts/chaos_soak.py --smoke --workdir "$WORKDIR/chaos" --out "$WORKDIR/chaos/report.json"
# The store-failover campaign (SIGKILL a shard mid-barrier-storm and
# mid-rendezvous) must have run inside the seeded pass and reproduced: exact
# deduped counter, a keyspace digest, and both victims recorded.
python - "$WORKDIR/chaos/report.json" <<'PY'
import json, sys
run = json.load(open(sys.argv[1]))["runs"][0]
assert run.get("store_failover_digest"), "store-failover scenario left no keyspace digest"
assert run.get("store_failover_counter", 0) > 0, run.get("store_failover_counter")
assert len(run.get("store_failover_victims", [])) == 2, run
print(f"store-failover chaos OK: kill_round={run['store_failover_kill_round']} "
      f"victims={run['store_failover_victims']} counter={run['store_failover_counter']}")
PY

echo "== smoke: cold start (job-tree SIGKILL -> fresh-workdir resume from the cold tier + offline --cold audit)"
COLD_DIR="$WORKDIR/chaos/cold_1234"
# The chaos leg already ran scenario_cold_start twice-per-seed: clean restore
# on a different world size resumed iter 2, the seeded archive bitflip climbed
# to iter 1, and the two legs restored different bytes.
python - "$WORKDIR/chaos/report.json" <<'PY'
import json, sys
run = json.load(open(sys.argv[1]))["runs"][0]
assert run["cold_start_resumed"]["clean"] == [2, 2], run["cold_start_resumed"]
assert run["cold_start_resumed"]["bitflip"] == [1, 1], run["cold_start_resumed"]
assert run["cold_start_digests"]["clean"] != run["cold_start_digests"]["bitflip"]
f = run["cold_start_fault"]
print(f"cold-start chaos OK: clean resume iter 2, seeded bitflip "
      f"(owner {f['victim_owner']} @ byte {f['flip_at']}) climbed to iter 1")
PY
# Offline audit of the killed job's workdir: archived owners join coverage as
# the third rung and render per iteration.
python -m tpu_resiliency.tools.ckpt_info "$COLD_DIR/root" --cold "$COLD_DIR/cold" \
    > "$COLD_DIR/coldinfo.out"
sed 's/^/    /' "$COLD_DIR/coldinfo.out"
grep -q "in cold tier" "$COLD_DIR/coldinfo.out" \
    || { echo "FAIL: --cold audit lost the cold-tier iteration count"; exit 1; }
grep -q "cold: \[0, 1, 2\]" "$COLD_DIR/coldinfo.out" \
    || { echo "FAIL: --cold audit lost the archived owners"; exit 1; }
# Restore-anywhere: an EMPTY workdir still audits what a new job could
# bootstrap from the object store alone.
mkdir -p "$COLD_DIR/nowhere"
python -m tpu_resiliency.tools.ckpt_info "$COLD_DIR/nowhere" --cold "$COLD_DIR/cold" \
    | grep -q "resumable from: iter" \
    || { echo "FAIL: empty workdir + --cold found nothing resumable"; exit 1; }
# --verify must catch the scenario's seeded archive bitflip (exit 1) and name
# the digest mismatch.
if python -m tpu_resiliency.tools.ckpt_info "$COLD_DIR/nowhere" --cold "$COLD_DIR/cold" \
    --verify > "$COLD_DIR/coldverify.out" 2>&1; then
    echo "FAIL: --cold --verify missed the seeded archive bitflip"; exit 1
fi
sed 's/^/    /' "$COLD_DIR/coldverify.out"
grep -q "digest mismatch" "$COLD_DIR/coldverify.out" \
    || { echo "FAIL: --cold --verify verdict lost the digest mismatch"; exit 1; }
# The tpu_coldtier_* families aggregate from the restore legs' event stream.
python -m tpu_resiliency.tools.metrics_dump "$COLD_DIR/events.jsonl" --format prom | \
    grep -q "tpu_coldtier_fetch_total" \
    || { echo "FAIL: tpu_coldtier_fetch_total missing from metrics dump"; exit 1; }
python -m tpu_resiliency.tools.metrics_dump "$COLD_DIR/events.jsonl" --format prom | \
    grep -q 'outcome="corrupt"' \
    || { echo "FAIL: corrupt cold fetch never reached the metrics plane"; exit 1; }
echo "cold-start smoke OK: offline --cold audit, empty-workdir bootstrap view, archive verify, metrics"

echo "== smoke: incident plane (artifact renders + tpu_incident_*/tpu_remediation_* metrics)"
MIXED_DIR="$WORKDIR/chaos/mixed_1234"
python -m tpu_resiliency.tools.incident_report "$MIXED_DIR/incidents" --list
python -m tpu_resiliency.tools.incident_report "$MIXED_DIR/incidents" | sed 's/^/    /'
python -m tpu_resiliency.tools.metrics_dump "$MIXED_DIR/events.jsonl" --format prom | \
    grep -q "tpu_incidents_total" || { echo "FAIL: tpu_incident_* missing from metrics dump"; exit 1; }
python -m tpu_resiliency.tools.metrics_dump "$MIXED_DIR/events.jsonl" --format prom | \
    grep -q "tpu_remediation_actions_total" || { echo "FAIL: tpu_remediation_actions_total missing"; exit 1; }
python -m tpu_resiliency.tools.events_summary "$MIXED_DIR/events.jsonl" --kind incident_closed --no-timeline > /dev/null

echo "== smoke: hang forensics (/hangz census + stack dumps + incident table)"
HANG_DIR="$WORKDIR/chaos/hang_1234"
# The live /hangz view captured mid-stall must name the seeded victim and a
# blocked barrier with missing ranks.
python - "$HANG_DIR/hangz.json" <<'PY'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["schema"] == "tpu-hangz-1", doc.get("schema")
assert doc["suspects"], "no suspects in the captured /hangz census"
assert any(b.get("missing") for b in doc["barriers"]), doc["barriers"]
assert any(r.get("stuck_s") for r in doc["ranks"]), doc["ranks"]
print(f"/hangz OK: suspects={[s['rank'] for s in doc['suspects']]} "
      f"open_barriers={len(doc['barriers'])}")
PY
# The rendered incident table shows who was stuck where and who never
# arrived. Captured once: `grep -q` would close the pipe early and turn the
# CLI's deliberate SIGPIPE exit (141) into a pipefail failure.
HANG_REPORT=$(python -m tpu_resiliency.tools.incident_report "$HANG_DIR/incidents")
echo "$HANG_REPORT" | sed 's/^/    /'
echo "$HANG_REPORT" | grep -q "hang census" \
    || { echo "FAIL: incident report lost the hang census table"; exit 1; }
echo "$HANG_REPORT" | grep -q "never arrived" \
    || { echo "FAIL: census table lost the missing ranks"; exit 1; }
# The new metric families aggregate from the hang run's events stream.
python -m tpu_resiliency.tools.metrics_dump "$HANG_DIR/events.jsonl" --format prom | \
    grep -q "tpu_stack_dumps_total" || { echo "FAIL: tpu_stack_dumps_total missing"; exit 1; }
python -m tpu_resiliency.tools.metrics_dump "$HANG_DIR/events.jsonl" --format prom | \
    grep -q "tpu_hang_suspects_total" || { echo "FAIL: tpu_hang_suspects_total missing"; exit 1; }
# --kind composes: slice the stream to the forensics chain only.
python -m tpu_resiliency.tools.events_summary "$HANG_DIR/events.jsonl" \
    --kind hang_detected,stack_dump,kill_ladder,hang_census --no-timeline | sed 's/^/    /'
python -m tpu_resiliency.tools.store_info --help | grep -q -- "--barriers" \
    || { echo "FAIL: store_info lost --barriers"; exit 1; }

echo "== smoke: autoscale act mode (controlled goodput strictly beats the no-controller baseline)"
AS_DIR="$WORKDIR/chaos/autoscale_1234"
# The chaos leg already ran scenario_autoscale (twice-per-seed controlled arm
# + baseline); the offline CLI must agree that the controller won.
python -m tpu_resiliency.tools.metrics_dump "$AS_DIR/controlled.jsonl" \
    --goodput --baseline "$AS_DIR/baseline.jsonl" | sed 's/^/    /'
python -m tpu_resiliency.tools.metrics_dump "$AS_DIR/controlled.jsonl" \
    --goodput --baseline "$AS_DIR/baseline.jsonl" --format json | \
    python -c "import json,sys; d=json.load(sys.stdin); assert d['ratio_delta']>0, d" \
    || { echo "FAIL: controlled run did not beat the baseline"; exit 1; }
for fam in tpu_autoscale_decisions_total tpu_autoscale_predicted_vs_realized tpu_preemption_rescinded_total; do
    python -m tpu_resiliency.tools.metrics_dump "$AS_DIR/controlled.jsonl" --format prom | \
        grep -q "$fam" || { echo "FAIL: $fam missing from metrics dump"; exit 1; }
done
python -m tpu_resiliency.tools.events_summary "$AS_DIR/controlled.jsonl" \
    --kind autoscale_decision,autoscale_outcome,preemption_rescinded | sed 's/^/    /'

echo "== smoke: autoscale advise mode (live decisions audited on /autoscale without acting)"
AD="$WORKDIR/advise"
mkdir -p "$AD"
cat > "$AD/worker.py" <<'PY'
import os, sys, time
from tpu_resiliency.utils.events import record

stop = sys.argv[1]
rank = int(os.environ.get("RANK", "0"))
i = 0
deadline = time.time() + 90
while not os.path.exists(stop) and time.time() < deadline:
    if rank == 0:
        record("inprocess", "iteration_start", iteration=i)
        if i == 20:
            # An injected straggler signal: the advise-mode controller must
            # turn it into an audited decision without acting on it.
            record("telemetry", "degraded_set", degraded=[1], newly=[1],
                   recovered=[], scores={"0": 1.0, "1": 0.2})
    i += 1
    time.sleep(0.05)
PY
python -m tpu_resiliency.launcher.launch \
    --standalone --nproc-per-node 2 --max-restarts 1 --no-ft-monitors \
    --rdzv-last-call 0.2 --monitor-interval 0.1 --telemetry-port 0 \
    --autoscale advise --warm-spares 1 --warm-spare-preload os \
    --events-file "$AD/events.jsonl" --run-dir "$AD/run" \
    "$AD/worker.py" "$AD/stop" &
AD_PID=$!
python - "$AD" <<'PY'
import json, os, sys, time, urllib.request

ad = sys.argv[1]
port_file = os.path.join(ad, "run", "telemetry.port")
deadline = time.time() + 60
while not os.path.exists(port_file):
    assert time.time() < deadline, "telemetry.port never appeared"
    time.sleep(0.2)
port = int(open(port_file).read().strip())
doc = None
while time.time() < deadline:
    try:
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/autoscale", timeout=5).read())
    except OSError:
        time.sleep(0.3)
        continue
    if doc.get("decisions_total", 0) >= 1:
        break
    time.sleep(0.3)
assert doc is not None and doc["schema"] == "tpu-autoscale-1", doc
assert doc["mode"] == "advise", doc
assert doc["decisions_total"] >= 1, f"/autoscale never showed a decision: {doc}"
d = doc["decisions"][0]
assert d["outcome"] == "advised", d  # advise mode must not act
assert d["predicted_delta_s"] is not None, d
print(f"autoscale advise OK: {doc['decisions_total']} decision(s), "
      f"first={d['action']}{d['victims']} predicted={d['predicted_delta_s']}s")
PY
touch "$AD/stop"
wait "$AD_PID"
grep -q '"kind": *"autoscale_decision"' "$AD/events.jsonl" \
    || { echo "FAIL: advise run left no autoscale_decision events"; exit 1; }
grep -q '"kind": *"autoscale_outcome"' "$AD/events.jsonl" \
    || { echo "FAIL: advise run never settled a realized outcome"; exit 1; }

echo "== smoke: watchtower (seeded straggler -> /alerts fires then resolves; offline replay reproduces the live record)"
WT="$WORKDIR/watchtower"
mkdir -p "$WT"
cat > "$WT/worker.py" <<'PY'
import os, sys, time
from tpu_resiliency.utils.events import record

stop = sys.argv[1]
rank = int(os.environ.get("RANK", "0"))
i = 0
deadline = time.time() + 120
while not os.path.exists(stop) and time.time() < deadline:
    if rank == 0:
        record("inprocess", "iteration_start", iteration=i)
    i += 1
    # Seeded straggler: steps 30..37 run ~25x slower, then recover — the
    # step_anomaly early warning must fire on /alerts, then resolve.
    time.sleep(1.2 if 30 <= i < 38 else 0.05)
PY
python -m tpu_resiliency.launcher.launch \
    --standalone --nproc-per-node 2 --max-restarts 1 --no-ft-monitors \
    --rdzv-last-call 0.2 --monitor-interval 0.1 --telemetry-port 0 \
    --alerts on \
    --events-file "$WT/events.jsonl" --run-dir "$WT/run" \
    "$WT/worker.py" "$WT/stop" &
WT_PID=$!
python - "$WT" <<'PY'
import json, os, sys, time, urllib.request

wt = sys.argv[1]
port_file = os.path.join(wt, "run", "telemetry.port")
deadline = time.time() + 90
while not os.path.exists(port_file):
    assert time.time() < deadline, "telemetry.port never appeared"
    time.sleep(0.2)
port = int(open(port_file).read().strip())
doc = None
seen = set()
while time.time() < deadline:
    try:
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/alerts", timeout=5).read())
    except OSError:
        time.sleep(0.3)
        continue
    seen = {(h.get("kind"), h.get("rule")) for h in doc.get("history", [])}
    if {("alert_fired", "step_anomaly"),
        ("alert_resolved", "step_anomaly")} <= seen:
        break
    time.sleep(0.3)
assert doc is not None and doc["schema"] == "tpu-alerts-1", doc
assert ("alert_fired", "step_anomaly") in seen, (
    f"straggler never fired step_anomaly: {doc}")
assert ("alert_resolved", "step_anomaly") in seen, (
    f"step_anomaly never resolved after recovery: {doc}")
snap = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{port}/snapshot", timeout=5).read())
assert snap.get("alerts", {}).get("schema") == "tpu-alerts-1", (
    "alerts section missing from /snapshot")
with open(os.path.join(wt, "alerts_live.json"), "w") as f:
    json.dump(doc, f)
fired = next(h for h in doc["history"]
             if (h["kind"], h["rule"]) == ("alert_fired", "step_anomaly"))
print(f"watchtower live OK: step_anomaly fired at {fired['fire_ts']:.3f} "
      f"and resolved; {len(doc['history'])} transition(s) recorded")
PY
touch "$WT/stop"
wait "$WT_PID"
# The live run's alert record must fall out of a cold offline replay of its
# events JSONL — same engine, stream clock, so the live history is a
# byte-exact prefix of the replayed sequence (the doc froze mid-run).
python - "$WT" <<'PY'
import json, os, sys

from tpu_resiliency.telemetry.watchtower import replay

wt = sys.argv[1]
doc = json.load(open(os.path.join(wt, "alerts_live.json")))
recs = []
for line in open(os.path.join(wt, "events.jsonl")):
    line = line.strip()
    if line:
        try:
            recs.append(json.loads(line))
        except ValueError:
            pass
_, seq = replay(recs)
hist = doc["history"]
enc = lambda rows: [json.dumps(r, sort_keys=True) for r in rows]
assert enc(seq[:len(hist)]) == enc(hist), (
    f"offline replay diverged from the live /alerts history:\n"
    f"{enc(seq[:len(hist)])}\n{enc(hist)}")
print(f"watchtower replay OK: live history ({len(hist)} transition(s)) is a "
      f"byte-exact prefix of the {len(seq)}-transition offline replay")
PY
python -m tpu_resiliency.tools.alerts_cli "$WT/events.jsonl" | sed 's/^/    /'
python -m tpu_resiliency.tools.alerts_cli --rules | sed 's/^/    /'
# The chaos campaign's saved stream replays byte-identically through the CLI.
AL_DIR="$WORKDIR/chaos/alerts_1234"
python -m tpu_resiliency.tools.alerts_cli "$AL_DIR/events.jsonl" --json \
    | diff - "$AL_DIR/sequence.jsonl" \
    || { echo "FAIL: tpu-alerts replay diverged from the campaign sequence"; exit 1; }

echo "== smoke: fleet federation (2 concurrent jobs -> fleetd scoreboard; SIGKILL one, fleet endpoints stay up)"
FL="$WORKDIR/fleet"
mkdir -p "$FL"
cat > "$FL/worker.py" <<'PY'
import os, sys, time
from tpu_resiliency.utils.events import record

stop = sys.argv[1]
i = 0
deadline = time.time() + 120
while not os.path.exists(stop) and time.time() < deadline:
    record("inprocess", "iteration_start", iteration=i)
    i += 1
    time.sleep(0.1)
PY
FLEET_PIDS=()
for J in alpha beta; do
    setsid python -m tpu_resiliency.launcher.launch \
        --standalone --nproc-per-node 2 --max-restarts 1 --no-ft-monitors \
        --rdzv-last-call 0.2 --monitor-interval 0.1 \
        --rdzv-id "job-$J" --fleet-dir "$FL/dir" \
        --events-file "$FL/events-$J.jsonl" --run-dir "$FL/run-$J" \
        "$FL/worker.py" "$FL/stop" > "$FL/launcher-$J.log" 2>&1 &
    FLEET_PIDS+=($!)
done
python -m tpu_resiliency.tools.fleetd --fleet-dir "$FL/dir" --port 0 \
    --scrape-interval 1 --snapshot "$FL/fleet.json" > "$FL/fleetd.log" 2>&1 &
FLEETD_PID=$!
python - "$FL" <<'PY'
import json, os, sys, time, urllib.request

fl = sys.argv[1]
port_file = os.path.join(fl, "dir", "fleetd.port")
deadline = time.time() + 60
while not os.path.exists(port_file):
    assert time.time() < deadline, "fleetd.port handshake never appeared"
    time.sleep(0.2)
port = int(open(port_file).read().strip())
doc, rows = None, {}
while time.time() < deadline:
    try:
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/fleet/goodput", timeout=5).read())
    except OSError:
        time.sleep(0.3)
        continue
    rows = {r["job"]: r["status"] for r in doc.get("jobs", [])}
    if rows.get("job-alpha") == "ok" and rows.get("job-beta") == "ok":
        break
    time.sleep(0.3)
assert rows.get("job-alpha") == "ok" and rows.get("job-beta") == "ok", doc
print(f"fleet scoreboard OK: {rows}")
with open(os.path.join(fl, "fleetd.port.resolved"), "w") as f:
    f.write(str(port))
PY
FLEETD_PORT=$(cat "$FL/fleetd.port.resolved")
# SIGKILL one whole job (launcher + workers): the fleet view must keep
# serving with the dead job marked unreachable, never a non-200.
kill -9 -- "-${FLEET_PIDS[0]}" 2>/dev/null || kill -9 "${FLEET_PIDS[0]}"
python - "$FLEETD_PORT" <<'PY'
import json, sys, time, urllib.request

port = int(sys.argv[1])
deadline = time.time() + 30
rows = {}
while time.time() < deadline:
    slo = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/fleet/slo", timeout=10).read())
    rows = {r["job"]: r["status"] for r in slo.get("jobs", [])}
    if rows.get("job-alpha") == "unreachable":
        break
    time.sleep(0.3)
assert rows.get("job-alpha") == "unreachable", rows
assert rows.get("job-beta") == "ok", rows
for ep in ("/fleet/metrics", "/fleet/goodput", "/fleet/slo",
           "/fleet/incidents", "/fleet/hangz", "/fleet/alerts",
           "/fleet/snapshot"):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{ep}", timeout=10) as r:
        assert r.status == 200, (ep, r.status)
# The cross-job alert feed degrades the dead job to a row, never a non-200.
al = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{port}/fleet/alerts", timeout=10).read())
assert al["schema"] == "tpu-fleet-alerts-1", al
al_rows = {r["job"]: r["status"] for r in al.get("jobs", [])}
assert al_rows.get("job-alpha") == "unreachable", al_rows
assert al_rows.get("job-beta") == "ok", al_rows
assert "job-alpha" in (al.get("unreachable") or []), al
prom = urllib.request.urlopen(
    f"http://127.0.0.1:{port}/fleet/metrics", timeout=10).read().decode()
assert 'job="job-beta"' in prom, prom[:2000]
assert "tpu_fleet_jobs" in prom and "tpu_fleet_scrape_seconds" in prom, prom[:2000]
assert 'tpu_fleet_scrape_errors_total{job="job-alpha"}' in prom, prom[:2000]
print("fleet kill leg OK: job-alpha unreachable, all /fleet/* endpoints 200")
PY
touch "$FL/stop"
# The persisted snapshot renders offline, and --job slices the dead job's
# stamped stream back out of its events file.
python -m tpu_resiliency.tools.fleet_cli scoreboard --snapshot "$FL/fleet.json" | sed 's/^/    /'
python -m tpu_resiliency.tools.fleet_cli slo --snapshot "$FL/fleet.json" | sed 's/^/    /'
python -m tpu_resiliency.tools.events_summary "$FL/events-beta.jsonl" \
    --job job-beta --no-timeline | sed 's/^/    /'
python -m tpu_resiliency.tools.metrics_dump "$FL/events-beta.jsonl" \
    --job job-beta --format prom | grep -q "tpu_events_total" \
    || { echo "FAIL: --job slice lost the job's own events"; exit 1; }
kill "$FLEETD_PID" 2>/dev/null || true
kill -- "-${FLEET_PIDS[1]}" 2>/dev/null || kill "${FLEET_PIDS[1]}" 2>/dev/null || true
wait "${FLEET_PIDS[1]}" 2>/dev/null || true
wait "$FLEETD_PID" 2>/dev/null || true

echo "smoke_observability: PASS ($WORKDIR)"
