#!/usr/bin/env bash
# Integration gate (reference parity: dev/integration-tests.sh builds
# images, generates data, runs the compose cluster + query subset; here:
# native build, the full suite INCLUDING the SF0.2 scale tier (all 22
# TPC-H queries through standalone AND the cluster — the scale-dependent
# paths: overflow, compaction, partitioned joins, recovery), then the
# chip smoke rehearsed on the CPU. Budget: ~6min on a 1-core box (~2min
# fast tier + ~160s SF0.2 + smoke). Skip the scale tier for quick iteration with
#   FAST_ONLY=1 dev/integration_test.sh
set -euo pipefail
cd "$(dirname "$0")/.."

make -C ballista_tpu/native

# Real-etcd tier: when an etcd binary (or BALLISTA_ETCD_URL) is present —
# e.g. inside deploy/docker-compose.etcd.yaml — tests/test_real_etcd.py
# runs the etcd v3 wire implementation against the real server instead of
# only the in-repo fake (protocol-skew guard). It self-skips otherwise.
if command -v etcd >/dev/null 2>&1 || [[ -n "${BALLISTA_ETCD_URL:-}" ]]; then
  echo "real etcd detected: running protocol-skew tier"
  python -m pytest tests/test_real_etcd.py -q
fi

if [[ "${FAST_ONLY:-0}" == "1" ]]; then
  python -m pytest tests/ -q -m "not sf02"
else
  python -m pytest tests/ -q
fi
python chip_smoke.py --rehearse --scale 0.01
