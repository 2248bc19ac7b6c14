#!/usr/bin/env bash
# THE blessed tier-1 entrypoint: builders and CI
# invoke this one script instead of hand-copying the ROADMAP command (one
# source of truth — a drifted copy silently weakens the gate).
#
#   scripts/check_tier1.sh            # static gate + the tier-1 suite
#   scripts/check_tier1.sh --static   # the fast static gate only
#
# Stage 1 (seconds): a static gate — python -m compileall over the
# package/tests/scripts plus pyflakes when available — so syntax errors
# and obvious undefined names fail in seconds, not after minutes of XLA
# compiles.  Stage 1.5 (jax-free, ~1s): `cli analyze` — encoding-
# soundness proofs over the shipped-model matrix, action lint, and the
# engine ownership/purity contracts (docs/analysis.md); any HIGH
# finding fails.  Stage 2: the ROADMAP "Tier-1 verify" command VERBATIM
# (keep the quoted block below byte-identical to ROADMAP.md when
# updating).

set -u
cd "$(dirname "$0")/.."

echo "[tier1] stage 1: static gate (compileall + pyflakes)"
python -m compileall -q kafka_specification_tpu tests scripts chip_smoke.py || {
    echo "[tier1] FAIL: compileall found syntax errors" >&2
    exit 1
}
if python -c "import pyflakes" 2>/dev/null; then
    # F821 undefined-name class of bugs; pyflakes is advisory-strict:
    # any finding fails the gate (the tree is kept pyflakes-clean)
    python -m pyflakes kafka_specification_tpu scripts chip_smoke.py || {
        echo "[tier1] FAIL: pyflakes findings (fix or # noqa them)" >&2
        exit 1
    }
else
    echo "[tier1] note: pyflakes not installed — skipping (compileall ran)"
fi

echo "[tier1] stage 1.5: kspec analyze (spec & engine static analysis)"
# jax-free: encoding-soundness over the shipped-model matrix, action
# lint, and the engine's concurrency-ownership/purity contracts
# (docs/analysis.md).  Any HIGH finding fails the gate in ~1s.
python -m kafka_specification_tpu.utils.cli analyze
rc_an=$?
if [ "$rc_an" -ne 0 ]; then
    # exit-code contract (utils/cli._run_analyze): 1 = HIGH findings,
    # 2 = a target could not even be analyzed (see stderr above)
    if [ "$rc_an" -eq 1 ]; then
        echo "[tier1] FAIL: kspec analyze found HIGH findings" >&2
    else
        echo "[tier1] FAIL: kspec analyze could not analyze a target (rc $rc_an)" >&2
    fi
    exit 1
fi

if [ "${1:-}" = "--static" ]; then
    echo "[tier1] static gate PASS (--static: skipping the pytest stage)"
    exit 0
fi

echo "[tier1] stage 2: ROADMAP tier-1 verify (verbatim)"
# --- ROADMAP.md "Tier-1 verify", byte-identical ---------------------------
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc
