"""graftlint — the repo-native static analyzer (docs/StaticAnalysis.md).

Five passes prove the hot-path invariants on a CPU-only runner, each
the static twin of a runtime audit:

  hostsync   implicit device->host syncs in ops//models/gbdt.py/serve/
             (runtime twin: the bench.py --dry fence-count assert)
  recompile  jit-cache hazards at decorator and call sites
             (runtime twin: obs recompiles --check)
  events     emit sites vs the obs/events.py field tables
             (runtime twin: validate_event, which sees only runs)
  config     Config reads vs utils/config.py vs docs/Parameters.md
  vmem       the Pallas tile planners evaluated over the benchmark
             shapes and the widths of the ladder against the VMEM
             budgets

Entry point: ``python -m lightgbm_tpu lint`` (analysis/cli.py).
"""
from .core import (Finding, LintInternalError, rule_catalog,  # noqa: F401
                   run_lint)
