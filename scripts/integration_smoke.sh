#!/usr/bin/env bash
# Multi-process end-to-end smoke (reference: scripts/docker-integration-tests/
# simple/test.sh, but over real cooperating processes): 1 KV metadata service
# + 2 dbnodes + 1 standalone coordinator + 2 aggregators sharing cluster
# state through the KV process. Verifies: scatter-gather write/query across
# both dbnodes via the coordinator HTTP API, and an aggregator placement
# change observed via KV watch reassigning shards without restart.
set -euo pipefail

cd "$(dirname "$0")/.."
WORKDIR=$(mktemp -d)
PIDS=()
trap 'kill "${PIDS[@]}" 2>/dev/null || true; rm -rf "$WORKDIR"' EXIT

# Several cooperating processes: a chip belongs to one process at a time,
# so all of them compute on the CPU backend here.
export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}

await_log() { # file pattern
  for i in $(seq 1 120); do
    grep -q "$2" "$1" 2>/dev/null && return 0
    sleep 0.5
  done
  echo "timeout waiting for '$2' in $1:"; cat "$1"; return 1
}

# --- 1. KV metadata service ------------------------------------------------
cat > "$WORKDIR/kv.yml" <<EOF
listen_address: 127.0.0.1:0
EOF
python -m m3_tpu.services kv -f "$WORKDIR/kv.yml" > "$WORKDIR/kv.log" 2>&1 &
PIDS+=($!)
await_log "$WORKDIR/kv.log" "m3_tpu kv listening on"
KV=$(grep "m3_tpu kv listening on" "$WORKDIR/kv.log" | awk '{print $NF}')
echo "kv: $KV"

# --- 2. two dbnodes --------------------------------------------------------
DB1_PORT=$(python -c "import socket; s=socket.socket(); s.bind(('127.0.0.1',0)); print(s.getsockname()[1])")
DB2_PORT=$(python -c "import socket; s=socket.socket(); s.bind(('127.0.0.1',0)); print(s.getsockname()[1])")
for i in 1 2; do
  PORT_VAR="DB${i}_PORT"
  cat > "$WORKDIR/dbnode$i.yml" <<EOF
host_id: dbnode-$i
listen_address: 127.0.0.1:${!PORT_VAR}
data_dir: $WORKDIR/data$i
num_shards: 16
kv_endpoint: $KV
namespaces:
  - name: default
    retention: 2h
EOF
  python -m m3_tpu.services dbnode -f "$WORKDIR/dbnode$i.yml" > "$WORKDIR/dbnode$i.log" 2>&1 &
  PIDS+=($!)
done
await_log "$WORKDIR/dbnode1.log" "m3_tpu dbnode listening on"
await_log "$WORKDIR/dbnode2.log" "m3_tpu dbnode listening on"
echo "dbnodes: 127.0.0.1:$DB1_PORT 127.0.0.1:$DB2_PORT"

# --- 3. dbnode placement in KV --------------------------------------------
python - "$KV" "127.0.0.1:$DB1_PORT" "127.0.0.1:$DB2_PORT" <<'EOF'
import sys
from m3_tpu.cluster.kv_service import RemoteStore
from m3_tpu.cluster.placement import Instance, PlacementService
kv, db1, db2 = sys.argv[1:4]
st = RemoteStore(kv)
PlacementService(st, "_placement").init(
    [Instance("dbnode-1", db1), Instance("dbnode-2", db2)],
    num_shards=16, replica_factor=1)
print("dbnode placement initialized")
EOF

# --- 4. standalone coordinator --------------------------------------------
cat > "$WORKDIR/coord.yml" <<EOF
namespace: default
kv_endpoint: $KV
carbon_listen_address: 127.0.0.1:0
EOF
python -m m3_tpu.services coordinator -f "$WORKDIR/coord.yml" > "$WORKDIR/coord.log" 2>&1 &
PIDS+=($!)
await_log "$WORKDIR/coord.log" "m3_tpu coordinator listening on"
COORD=$(grep "m3_tpu coordinator listening on" "$WORKDIR/coord.log" | awk '{print $NF}')
await_log "$WORKDIR/coord.log" "m3_tpu carbon listening on"
CARBON=$(grep "m3_tpu carbon listening on" "$WORKDIR/coord.log" | awk '{print $NF}')
echo "coordinator: $COORD  carbon: $CARBON"

curl -fsS "$COORD/health" > /dev/null

# --- 5. scatter-gather writes + PromQL reads across both dbnodes ----------
NOW=$(python -c "import time; print(int(time.time()))")
for h in a b c d e f; do  # several hosts so shards land on both dbnodes
  for i in 0 1 2 3 4; do
    curl -fsS -X POST "$COORD/api/v1/json/write" \
      -d "{\"tags\":{\"__name__\":\"smoke_metric\",\"host\":\"$h\"},\"timestamp\":$((NOW - 40 + i * 10)),\"value\":$((10 + i))}" > /dev/null
  done
done

RESULT=$(curl -fsS "$COORD/api/v1/query_range?query=smoke_metric&start=$((NOW-60))&end=$NOW&step=10")
echo "$RESULT" | python -c "
import json, sys
out = json.load(sys.stdin)
assert out['status'] == 'success', out
series = out['data']['result']
assert len(series) == 6, [s['metric'] for s in series]
for s in series:
    vals = [float(v) for _, v in s['values']]
    assert vals[-1] == 14.0, (s['metric'], vals)
print('scatter-gather query_range across 2 dbnodes OK (6 series)')
"

RESULT2=$(curl -fsS "$COORD/api/v1/query_range?query=sum(rate(smoke_metric%5B30s%5D))&start=$((NOW-30))&end=$NOW&step=10")
echo "$RESULT2" | python -c "
import json, sys
out = json.load(sys.stdin)
assert out['status'] == 'success', out
print('promql function over HTTP OK')
"

# Modern promql surface against the real cluster: a subquery over an
# @-pinned selector (max_over_time of 10s-resolution evals), and an
# instant scalar-typed query returning resultType scalar.
SUBQ="max_over_time(smoke_metric%5B30s:10s%5D%20@%20$NOW)"
RESULT3=$(curl -fsS "$COORD/api/v1/query_range?query=$SUBQ&start=$((NOW-30))&end=$NOW&step=10")
echo "$RESULT3" | python -c "
import json, sys
out = json.load(sys.stdin)
assert out['status'] == 'success', out
series = out['data']['result']
assert len(series) == 6, [s['metric'] for s in series]
for s in series:
    vals = {float(v) for _, v in s['values']}
    # @-pinned window => one constant value at every output step; the
    # 10s-aligned eval times may cut one sample before NOW (13 or 14).
    assert len(vals) == 1 and vals <= {13.0, 14.0}, (s['metric'], vals)
print('subquery + @-modifier over HTTP OK (6 series, constant pinned max)')
"
RESULT4=$(curl -fsS "$COORD/api/v1/query?query=scalar(sum(smoke_metric))&time=$NOW")
echo "$RESULT4" | python -c "
import json, sys
out = json.load(sys.stdin)
assert out['data']['resultType'] == 'scalar', out
assert out['data']['result'][1] == '84', out  # 6 series x 14, Go formatting
print('instant scalar resultType + formatting OK (84)')
"

# --- 6. aggregators with placement watch ----------------------------------
for a in a b; do
  cat > "$WORKDIR/agg$a.yml" <<EOF
instance_id: agg-$a
listen_address: 127.0.0.1:0
num_shards: 8
kv_endpoint: $KV
placement_key: _placement/agg
election_id: agg-election-$a
flush_interval: 5s
EOF
  python -m m3_tpu.services aggregator -f "$WORKDIR/agg$a.yml" > "$WORKDIR/agg$a.log" 2>&1 &
  PIDS+=($!)
done
await_log "$WORKDIR/agga.log" "m3_tpu aggregator listening on"
await_log "$WORKDIR/aggb.log" "m3_tpu aggregator listening on"
AGG_A=$(grep "m3_tpu aggregator listening on" "$WORKDIR/agga.log" | awk '{print $NF}')
AGG_B=$(grep "m3_tpu aggregator listening on" "$WORKDIR/aggb.log" | awk '{print $NF}')

# Initial aggregator placement: agg-a owns everything.
python - "$KV" "$AGG_A" <<'EOF'
import sys
from m3_tpu.cluster.kv_service import RemoteStore
from m3_tpu.cluster.placement import Instance, PlacementService
kv, agg_a = sys.argv[1:3]
PlacementService(RemoteStore(kv), "_placement/agg").init(
    [Instance("agg-a", agg_a)], num_shards=8, replica_factor=1)
print("aggregator placement initialized (agg-a only)")
EOF
await_log "$WORKDIR/agga.log" "placement update: owned=\[0, 1, 2, 3, 4, 5, 6, 7\]"
echo "agg-a owns all 8 shards"

# Placement change: add agg-b; both instances observe via KV watch push.
python - "$KV" "$AGG_B" <<'EOF'
import sys
from m3_tpu.cluster.kv_service import RemoteStore
from m3_tpu.cluster.placement import Instance, PlacementService
kv, agg_b = sys.argv[1:3]
PlacementService(RemoteStore(kv), "_placement/agg").add_instance(
    Instance("agg-b", agg_b))
print("aggregator placement changed (added agg-b)")
EOF
await_log "$WORKDIR/aggb.log" "placement update: owned=\[[0-7]"
echo "agg-b picked up shards from the placement change via watch (no restart)"

# --- 7. prometheus flavor: real snappy+protobuf remote write/read ---------
# (reference: scripts/docker-integration-tests/prometheus/test.sh — a real
# Prometheus remote_write body, not JSON.)
python - "$COORD" "$NOW" <<'EOF'
import sys, urllib.request, json
from m3_tpu.coordinator import promremote
coord, now = sys.argv[1], int(sys.argv[2])
body = promremote.snappy_compress(promremote.encode_write_request([
    ({b"__name__": b"prom_remote_metric", b"job": b"smoke"},
     [((now - 20 + i * 10) * 1000, 5.0 + i) for i in range(3)]),
]))
req = urllib.request.Request(coord + "/api/v1/prom/remote/write", data=body,
                             method="POST",
                             headers={"Content-Encoding": "snappy",
                                      "Content-Type": "application/x-protobuf"})
with urllib.request.urlopen(req) as r:
    assert json.loads(r.read())["wrote"] == 3
q = f"{coord}/api/v1/query_range?query=prom_remote_metric&start={now-30}&end={now}&step=10"
with urllib.request.urlopen(q) as r:
    out = json.loads(r.read())
vals = [float(v) for _, v in out["data"]["result"][0]["values"]]
assert vals[-1] == 7.0, vals
print("prometheus snappy+protobuf remote write -> query_range OK")
EOF

# --- 8. carbon flavor: graphite line in -> render out ---------------------
# (reference: scripts/docker-integration-tests/carbon/test.sh)
python - "$CARBON" "$COORD" "$NOW" <<'EOF'
import sys, socket, time, urllib.request, json
carbon, coord, now = sys.argv[1], sys.argv[2], int(sys.argv[3])
host, _, port = carbon.rpartition(":")
with socket.create_connection((host, int(port)), timeout=5) as s:
    for i in range(3):
        s.sendall(b"smoke.carbon.count %d %d\n" % (100 + i, now - 20 + i * 10))
deadline = time.time() + 10
out, vals = None, []
while time.time() < deadline:
    q = f"{coord}/api/v1/graphite/render?target=smoke.carbon.count&from={now-30}&until={now}&step=10"
    with urllib.request.urlopen(q) as r:
        out = json.loads(r.read())
    vals = [v for v, _ in out[0]["datapoints"] if v is not None] if out else []
    # All three lines ingest asynchronously: wait for the full batch, not
    # the first arrival, before asserting the final value.
    if len(vals) == 3:
        break
    time.sleep(0.2)
assert len(vals) == 3 and vals[-1] == 102.0, out
assert out[0]["target"] == "smoke.carbon.count"
print("carbon line in -> graphite render OK")
EOF

# --- 9. leader/follower failover: SIGKILL the leader mid-stream -----------
# (reference: src/aggregator/integration election suites + election_mgr.go:99)
# Two HA aggregators share one election; both ingest the same dual-written
# counter stream; the leader is SIGKILLed and the follower must promote and
# resume flushing from the KV flush times — every window flushed EXACTLY
# once across the two processes' durable flush logs.
for a in ha-a ha-b; do
  cat > "$WORKDIR/$a.yml" <<EOF
instance_id: $a
listen_address: 127.0.0.1:0
num_shards: 8
kv_endpoint: $KV
election_id: agg-ha
election_ttl: 3s
flush_interval: 1s
flush_log: $WORKDIR/$a.flush.log
EOF
done
python -m m3_tpu.services aggregator -f "$WORKDIR/ha-a.yml" > "$WORKDIR/ha-a.log" 2>&1 &
HA_A_PID=$!
PIDS+=($HA_A_PID)
await_log "$WORKDIR/ha-a.log" "m3_tpu aggregator listening on"
sleep 1.5  # let ha-a win the election before the follower starts
python -m m3_tpu.services aggregator -f "$WORKDIR/ha-b.yml" > "$WORKDIR/ha-b.log" 2>&1 &
HA_B_PID=$!
PIDS+=($HA_B_PID)
await_log "$WORKDIR/ha-b.log" "m3_tpu aggregator listening on"
HA_A=$(grep "m3_tpu aggregator listening on" "$WORKDIR/ha-a.log" | awk '{print $NF}')
HA_B=$(grep "m3_tpu aggregator listening on" "$WORKDIR/ha-b.log" | awk '{print $NF}')

# Dual-write one TIMED counter point per 10s window, spanning windows that
# close progressively over the next ~25s (mirrored-replica ingest).
python - "$HA_A" "$HA_B" <<'EOF'
import socket, sys, time
from m3_tpu.metrics.metric import MetricType
from m3_tpu.rpc import wire
S = 10**9
now = time.time_ns()
first = now // (10 * S) * (10 * S) - 20 * S
entries = [
    {"t": "timed", "mtype": int(MetricType.COUNTER), "id": b"ha.count",
     "time": first + i * 10 * S + 5 * S, "value": float(100 + i),
     "policy": "10s:2d"}
    for i in range(5)  # windows closing from ~now to ~now+25s
]
for ep in sys.argv[1:3]:
    host, _, port = ep.rpartition(":")
    with socket.create_connection((host, int(port)), timeout=5) as s:
        wire.write_frame(s, {"t": "batch", "entries": entries})
print("dual-wrote 5 windows to both HA aggregators")
EOF

# The election may legitimately land on EITHER instance (observed: ha-b
# wins ~half the time despite ha-a's head start) — detect the leader as
# whichever flush log goes non-empty first. Up to 60s: election + first
# flush normally lands in ~5-10s but CPU contention can stretch it.
LEADER=""
for i in $(seq 1 120); do
  if [ -s "$WORKDIR/ha-a.flush.log" ]; then LEADER=ha-a; break; fi
  if [ -s "$WORKDIR/ha-b.flush.log" ]; then LEADER=ha-b; break; fi
  sleep 0.5
done
[ -n "$LEADER" ] || { echo "no leader ever flushed"; cat "$WORKDIR/ha-a.log" "$WORKDIR/ha-b.log"; exit 1; }
if [ "$LEADER" = ha-a ]; then LEADER_PID=$HA_A_PID; else LEADER_PID=$HA_B_PID; fi
# The flush loop emits (durable log line) THEN commits flush times to KV —
# an at-least-once window of a few ms. Killing right on the observed line
# could land inside it and legitimately double-flush; a 1s grace puts the
# SIGKILL well past the commit (the next window is ~10s away).
sleep 1
kill -9 "$LEADER_PID"
echo "leader $LEADER SIGKILLed after $(wc -l < "$WORKDIR/$LEADER.flush.log") flushed window(s)"

# Wait until the promoted follower has drained every remaining window
# (the last one only closes ~30s after the writes).
for i in $(seq 1 120); do
  TOTAL=$(cat "$WORKDIR/ha-a.flush.log" "$WORKDIR/ha-b.flush.log" 2>/dev/null | wc -l)
  [ "$TOTAL" -ge 5 ] && break
  sleep 0.5
done
python - "$WORKDIR/ha-a.flush.log" "$WORKDIR/ha-b.flush.log" <<'EOF'
import sys
S = 10**9
windows = {}
for who, path in (("ha-a", sys.argv[1]), ("ha-b", sys.argv[2])):
    for line in open(path, "rb").read().splitlines():
        mid, t, v, pol = line.split(b"\t")
        assert mid == b"ha.count", line
        windows.setdefault(int(t), []).append((who, float(v)))
assert windows, "nothing flushed"
ends = sorted(windows)
dupes = {t: w for t, w in windows.items() if len(w) > 1}
assert not dupes, f"double-flushed windows: {dupes}"
span = [ends[0] + i * 10 * S for i in range(len(ends))]
assert ends == span, f"lost windows (gaps): {[e // S for e in ends]}"
assert len(ends) == 5, f"expected 5 windows, got {len(ends)}"
by_who = {w for t in windows for (w, _) in windows[t]}
assert by_who == {"ha-a", "ha-b"}, f"failover not exercised: {by_who}"
vals = [windows[t][0][1] for t in ends]
assert vals == [100.0, 101.0, 102.0, 103.0, 104.0], vals
print(f"failover OK: {len(ends)} windows flushed exactly once "
      f"({sum(1 for t in ends if windows[t][0][0]=='ha-a')} by ha-a, "
      f"{sum(1 for t in ends if windows[t][0][0]=='ha-b')} by ha-b)")
EOF

echo "SMOKE PASS"
