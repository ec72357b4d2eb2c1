#!/bin/bash
# Fleet utilization / cost report — the reference's cost-monitor Lambda
# analog (fraud-detection-additional-resources.yaml: Lambda + schedule that
# emailed a cost summary; the README's "40% cost optimization" claim,
# README.md:205, had no mechanism behind it).
#
# This one has a mechanism: scrape every scorer replica's Prometheus
# endpoint, compute per-replica throughput against the configured per-chip
# capacity, and flag replicas the HPA should be allowed to reclaim. Run it
# as the rtfd-cost-monitor CronJob (deploy/k8s/cost-monitor.yaml) or ad hoc.
set -uo pipefail
HOSTS="${RTFD_SCORER_HOSTS:-127.0.0.1:8080}"   # comma-separated host:port
# measured per-chip capacity: txn/s of one v5e in the benchmark's device-bound
# cell s512-fulltext-saturated (PERF_LEDGER.jsonl, PR 27; 6094 in
# s512-longtail-saturated, 469 with the OLMoE encoder); override per fleet
CAPACITY="${RTFD_CHIP_CAPACITY_TPS:-2407}"
python - "$HOSTS" "$CAPACITY" <<'EOF'
import json, socket, sys, urllib.request
raw, capacity = sys.argv[1].split(","), float(sys.argv[2])
# A headless-service name resolves to EVERY pod IP — expand each entry to
# all its A records so the report covers the fleet, not one sampled pod
hosts = []
for h in raw:
    h = h.strip()
    name, _, port = h.partition(":")
    try:
        ips = sorted({ai[4][0] for ai in socket.getaddrinfo(
            name, None, family=socket.AF_INET)})
    except OSError:
        ips = [name]
    hosts.extend(f"{ip}:{port or 8080}" for ip in ips)
rows, total_tps = [], 0.0
for h in hosts:
    try:
        with urllib.request.urlopen(f"http://{h}/metrics", timeout=5) as r:
            m = json.loads(r.read())
        # obs/metrics.py summary(): 60s-window prediction throughput
        tps = float(m.get("throughput_tps_60s") or 0.0)
    except Exception as e:
        rows.append({"replica": h, "error": str(e)[:120]})
        continue
    util = tps / capacity if capacity else 0.0
    rows.append({"replica": h, "txn_per_s": round(tps, 1),
                 "utilization": round(util, 4),
                 "reclaimable": util < 0.15})
    total_tps += tps
n_ok = sum(1 for r in rows if "error" not in r)
report = {
    "replicas": rows,
    "fleet_txn_per_s": round(total_tps, 1),
    "fleet_capacity_txn_per_s": capacity * max(n_ok, 1),
    "fleet_utilization": round(total_tps / (capacity * max(n_ok, 1)), 4),
    "recommendation": (
        "scale down: >1 replica under 15% utilization"
        if sum(1 for r in rows if r.get("reclaimable")) > 1
        else "sized correctly for current load"),
}
print(json.dumps(report))
EOF
