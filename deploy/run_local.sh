#!/usr/bin/env bash
# One-command local bring-up of the deployed topology: ONE shared TPU solver
# (snapshot channel + lease plane) and KC_REPLICAS leader-elected operator
# replicas, as separate processes — the in-cluster equivalent is
# deploy/manifests/deployment.yaml.  With --check, probes everything and
# exits; with --failover-check, also kills the leader and waits for the
# standby to take over (the two-process HA proof, also run as
# tests/test_ha_failover.py::TestTwoProcessFailover).
set -euo pipefail

REPO="$(cd "$(dirname "$0")/.." && pwd)"
export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"
export KC_SOLVER_LISTEN="${KC_SOLVER_LISTEN:-127.0.0.1:8980}"
export KC_LEASE_ENDPOINT="${KC_LEASE_ENDPOINT:-$KC_SOLVER_LISTEN}"
# per-run lease state: a stale lease from a killed previous run would make
# every bring-up wait out the 15 s staleness window
export KC_LEASE_STATE="${KC_LEASE_STATE:-$(mktemp -d)/leases.json}"
export LEADER_ELECT="${LEADER_ELECT:-true}"
KC_REPLICAS="${KC_REPLICAS:-2}"
BASE_METRICS_PORT="${BASE_METRICS_PORT:-8080}"

PIDS=()
cleanup() { kill "${PIDS[@]}" 2>/dev/null || true; }
trap cleanup EXIT

python -m karpenter_core_tpu.cmd.solver &
PIDS+=($!)

# one process per chip: the operators ship their device solves to the sidecar
# (deploy/manifests/deployment.yaml does the same), so the sidecar is the only
# process that initializes the accelerator backend
export KC_SOLVER_ADDRESS="${KC_SOLVER_ADDRESS:-$KC_SOLVER_LISTEN}"

METRICS_PORTS=()
for i in $(seq 0 $((KC_REPLICAS - 1))); do
  metrics_port=$((BASE_METRICS_PORT + 2 * i))
  health_port=$((BASE_METRICS_PORT + 2 * i + 1))
  METRICS_PORT="$metrics_port" HEALTH_PROBE_PORT="$health_port" \
    python -m karpenter_core_tpu.cmd.operator &
  PIDS+=($!)
  METRICS_PORTS+=("$metrics_port")
done

leader_count() {
  local count=0
  for port in "${METRICS_PORTS[@]}"; do
    v=$(curl -fsS "http://127.0.0.1:${port}/metrics" 2>/dev/null |
        awk '/^karpenter_leader_election_leader/ {print $2}')
    [[ "$v" == 1* ]] && count=$((count + 1))
  done
  echo "$count"
}

echo "waiting for the replicas to come up..."
for _ in $(seq 1 120); do
  up=0
  for port in "${METRICS_PORTS[@]}"; do
    curl -fsS "http://127.0.0.1:$((port + 1))/healthz" >/dev/null 2>&1 && up=$((up + 1))
  done
  [[ "$up" -eq "$KC_REPLICAS" ]] && break
  sleep 0.5
done

python - <<EOF
from karpenter_core_tpu.service.snapshot_channel import SnapshotSolverClient
client = SnapshotSolverClient("${KC_SOLVER_LISTEN}")
assert client.health() == {"status": "ok"}
client.close()
print("solver healthy ${KC_SOLVER_LISTEN} (gRPC /Health + lease plane)")
EOF

echo "waiting for exactly one leader across ${KC_REPLICAS} replicas..."
for _ in $(seq 1 120); do
  [[ "$(leader_count)" == "1" ]] && break
  sleep 0.5
done
[[ "$(leader_count)" == "1" ]] || { echo "FAIL: expected exactly 1 leader"; exit 1; }
echo "one leader elected through the shared lease plane"

if [[ "${1:-}" == "--failover-check" ]]; then
  # find and kill the leader process, then wait for the standby takeover
  for i in "${!METRICS_PORTS[@]}"; do
    port="${METRICS_PORTS[$i]}"
    v=$(curl -fsS "http://127.0.0.1:${port}/metrics" 2>/dev/null |
        awk '/^karpenter_leader_election_leader/ {print $2}')
    if [[ "$v" == 1* ]]; then
      leader_pid="${PIDS[$((i + 1))]}"  # PIDS[0] is the solver
      echo "killing leader (pid ${leader_pid}, metrics :${port})"
      kill -9 "$leader_pid"
      break
    fi
  done
  echo "waiting for standby promotion (lease staleness ~15 s)..."
  for _ in $(seq 1 120); do
    [[ "$(leader_count)" == "1" ]] && { echo "standby took over"; exit 0; }
    sleep 0.5
  done
  echo "FAIL: standby never took over"
  exit 1
fi

if [[ "${1:-}" == "--check" ]]; then
  echo "topology is up; --check done"
  exit 0
fi

echo "topology running (ctrl-c to stop)"
wait
