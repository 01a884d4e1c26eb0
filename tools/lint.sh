#!/usr/bin/env bash
# pslint entry point: JAX/TPU-aware static analysis over the package.
#
#   tools/lint.sh                 # gate: package + tests/ + tools/ +
#                                 # analysis/ vs committed baseline
#   tools/lint.sh cli/foo.py      # lint other trees (ad hoc; the committed
#                                 # baseline still applies if entries match)
#   tools/lint.sh --write-baseline  # refresh lint_baseline.json over the
#                                   # gate's paths
#
# Exit 0 = clean (or fully baselined), 1 = new findings, 2 = usage error.
# The same check runs in tier-1 via tests/test_lint.py::test_package_is_
# clean_against_committed_baseline, so CI fails on any new finding.
set -euo pipefail
cd "$(dirname "$0")/.."
source tools/_gate_common.sh

# tests/ is in the gate on purpose: donated-buffer reuse (PSL005) and
# axis literals live there, and CPU-only CI cannot catch donation bugs
# at runtime (donation is a warning on CPU, a crash on TPU). tools/
# and analysis/ are gated because their host loops drive the
# TPU (PSL002 recompilation and PSL004 sync hazards live there too).
# The psdiverge pass (PSL006-008, multihost divergence) rides the same
# gate; run it alone with `tools/lint.sh --select PSL006,PSL007,PSL008`
# (smoke.sh's first leg).
GATE_PATHS=(ps_pytorch_tpu tests tools analysis)

REFUSE="tools/lint.sh: --write-baseline always refreshes over the gate's
paths (${GATE_PATHS[*]}); drop the explicit paths, or call
python -m ps_pytorch_tpu.lint directly with an explicit --baseline"

gate_dispatch --write-baseline "--baseline --select --format" "$REFUSE" \
    python -m ps_pytorch_tpu.lint "${GATE_PATHS[@]}" --baseline lint_baseline.json -- \
    python -m ps_pytorch_tpu.lint -- \
    "$@"
