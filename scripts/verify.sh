#!/usr/bin/env bash
# Tier-1 verification: the fast deterministic suite + a dry-run smoke.
#
# The default pytest run excludes the `slow` / `multidevice` markers
# (full multi-device subprocess equivalence runs, ~10 min) so that the
# everyday gate stays fast; run `pytest -m slow` explicitly before
# touching shard_map/collective code.
#
#   scripts/verify.sh               # tests + dry-run smoke
#   scripts/verify.sh --fast        # tests only
#   scripts/verify.sh --smoke       # smoke benchmarks + BENCH schema check
#                                   # (the CI benchmark job; no test run)
#   scripts/verify.sh --multidevice # the multidevice-marked subprocess
#                                   # suite on forced host devices (the
#                                   # CI multidevice job)
#   scripts/verify.sh --chaos       # fault-injection recovery suite:
#                                   # elastic scale-down/up on forced
#                                   # devices (the CI chaos-smoke job)
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

mode="${1:-}"

if [[ "$mode" == "--smoke" ]]; then
  echo "== smoke benchmarks (BENCH_*.json + schema check) =="
  python benchmarks/run.py --smoke
  python scripts/check_bench_schema.py
  echo "== traced smoke loop (trace_smoke.json artifact) =="
  # exercises the live trace path end to end: adaptive drop -> replan ->
  # hot-swap, exported as a Perfetto-loadable Chrome trace (§11)
  python -m repro.launch.train --smoke --scheduler deft --steps 56 \
    --adapt --adapt-repartition --adapt-drop-step 12 \
    --adapt-drop-scale 6.0 --trace trace_smoke.json
  python - <<'PY'
import json
kinds = {e.get("cat") for e in json.load(open("trace_smoke.json"))["traceEvents"]}
need = {"step", "phase", "place", "launch", "swap-compile",
        "swap-install", "replan", "repack"}
missing = need - kinds
assert not missing, f"trace_smoke.json missing span kinds: {missing}"
print(f"trace_smoke.json OK ({sorted(k for k in kinds if k)})")
PY
  echo "== precision smoke loop (wire quantization end to end, §13) =="
  # forced int8 wire + bf16sr master -> quantized layout -> traced
  # quantized collectives ('auto' would keep f32 here: the smoke
  # model's us-scale comm sits under the collective latency floor, so
  # the ladder rightly finds no gain).  The phase spans must carry
  # wire_bytes/precision attrs so the wire-bytes attribution
  # (obs.wire_bytes_report) can close the loop
  python -m repro.launch.train --smoke --scheduler deft --steps 12 \
    --wire-precision int8 --master-dtype bf16sr \
    --trace trace_precision.json
  python - <<'PY'
import json
evs = json.load(open("trace_precision.json"))["traceEvents"]
phases = [e for e in evs if e.get("cat") == "phase"]
assert phases, "trace_precision.json has no phase spans"
tagged = [e for e in phases if "wire_bytes" in e.get("args", {})]
assert tagged, "phase spans carry no wire_bytes attrs"
prec = {e["args"].get("precision") for e in tagged}
print(f"trace_precision.json OK ({len(tagged)} phase spans with "
      f"wire bytes, precisions={sorted(p for p in prec if p)})")
PY
  echo "verify.sh --smoke: OK"
  exit 0
fi

if [[ "$mode" == "--multidevice" ]]; then
  echo "== multi-device suite (forced host devices) =="
  # the tests spawn subprocesses that force their own device counts;
  # the outer XLA_FLAGS only covers any future in-process cases
  rc=0
  XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python -m pytest -q -m multidevice || rc=$?
  if [[ "$rc" -ne 0 ]]; then
    echo "verify.sh: multidevice tests FAILED (exit $rc)" >&2
    exit "$rc"
  fi
  echo "verify.sh --multidevice: OK"
  exit 0
fi

if [[ "$mode" == "--chaos" ]]; then
  echo "== chaos suite (fault injection -> elastic recovery) =="
  # the chaos tests spawn subprocesses that force their own device
  # counts, same pattern as --multidevice
  rc=0
  python -m pytest -q -m chaos || rc=$?
  if [[ "$rc" -ne 0 ]]; then
    echo "verify.sh: chaos tests FAILED (exit $rc)" >&2
    exit "$rc"
  fi
  echo "verify.sh --chaos: OK"
  exit 0
fi

echo "== lint: no legacy planner entry points outside core =="
python scripts/check_no_legacy_planner.py

echo "== tier-1 tests (excluding slow/multidevice) =="
# run under an if so `set -e` cannot short-circuit before we report,
# then propagate pytest's exit code verbatim (CI must see the status)
rc=0
python -m pytest -q -m "not slow and not multidevice" || rc=$?
if [[ "$rc" -ne 0 ]]; then
  echo "verify.sh: tier-1 tests FAILED (exit $rc)" >&2
  exit "$rc"
fi

if [[ "$mode" != "--fast" ]]; then
  echo "== dry-run smoke (compile-only, no model memory) =="
  # Output goes to a scratch dir: the checked-in experiments/dryrun
  # artifacts are updated deliberately, not by every verify run (CI
  # asserts the tree is clean afterwards).
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k \
    --out "$(mktemp -d)"
fi

echo "verify.sh: OK"
