#!/usr/bin/env bash
# Tier-1 verification, reproducible offline: force the host (CPU) backend
# so the suite behaves identically with or without accelerators attached.
# Mesh-heavy subprocess tests force their own device counts internally.
#
#   scripts/verify.sh                # full tier-1 run (docs check + API
#                                    # smoke + pytest)
#   scripts/verify.sh --fast         # fast lane: skip the mesh-heavy
#                                    # subprocess tests (-m 'not slow');
#                                    # docs check + smoke still run
#   scripts/verify.sh -m 'not slow'  # extra pytest args pass through
#   scripts/verify.sh --no-smoke ... # skip the API smoke stage
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

pytest_args=()
smoke=1
for arg in "$@"; do
  case "$arg" in
    --fast)     pytest_args+=(-m "not slow") ;;
    --no-smoke) smoke=0 ;;
    *)          pytest_args+=("$arg") ;;
  esac
done

echo "== docs check: python scripts/check_docs.py =="
# README/docs module paths, CLI flags, and local links must exist
python scripts/check_docs.py

if [[ "$smoke" == 1 ]]; then
  # runs in the --fast lane too: the example IS the API's executable doc
  echo "== API smoke: python -m examples.api_session --smoke =="
  # under JAX_PLATFORMS=cpu the example forces its own 8 host devices
  # via XLA_FLAGS, so this behaves identically with or without
  # accelerators attached
  python -m examples.api_session --smoke

  # controller smoke (fast lane too): a short --controller run on forced
  # host devices must emit at least one non-trivial ControlAction
  echo "== controller smoke: python scripts/controller_smoke.py =="
  python scripts/controller_smoke.py

  # dataplane smoke (fast lane too): prefetched run == sync run,
  # TrainState donation in effect, kernel router resolves the compiled
  # jnp reference on CPU (never interpret-mode Pallas on the hot path)
  echo "== dataplane smoke: python scripts/dataplane_smoke.py =="
  python scripts/dataplane_smoke.py

  # churn smoke (fast lane too): Poisson churn + coded redundancy on 8
  # forced host devices — finite losses, survivor-relayout fast path,
  # bit-exact save -> restore mid-churn, single-survivor identity
  echo "== churn smoke: python scripts/churn_smoke.py =="
  python scripts/churn_smoke.py

  # serve smoke (fast lane too): staggered continuous batching == static
  # reference token-for-token, background AMB fine-tune epoch absorbed
  # into the round budget, SLO JSONL flushed
  echo "== serve smoke: python scripts/serve_smoke.py =="
  python scripts/serve_smoke.py
fi

echo "== pytest ${pytest_args[*]:-} =="
# ${arr[@]+...} guard: empty-array expansion is an unbound-variable error
# under `set -u` on bash < 4.4 (stock macOS bash 3.2)
exec python -m pytest -x -q ${pytest_args[@]+"${pytest_args[@]}"}
