"""graftlint — the JAX-law static analyzer (``sheeprl-tpu-lint``).

An AST-based pass enforcing the framework's performance and correctness
contracts at review time instead of runtime: buffer donation discipline
(the PR 7 / PR 14 use-after-donate bug class), trace purity / recompile
hazards, PRNG stream hygiene, and the config / fault-site / metric-family
registries.  See docs/static_analysis.md for the rule catalogue and
suppression etiquette.

Entry points:

* ``sheeprl-tpu-lint`` / ``python -m sheeprl_tpu.analysis`` — the CLI
* :func:`run_analysis` — in-process (the tier-1 test
  ``tests/test_analysis/test_repo_clean.py`` calls this)
* ``# graftlint: disable=<rule>`` — inline suppression;
  ``analysis/baseline.json`` — the accepted-findings ledger
"""

from sheeprl_tpu.analysis.baseline import DEFAULT_BASELINE, Baseline, BaselineError
from sheeprl_tpu.analysis.context import METRIC_FAMILIES, RepoContext
from sheeprl_tpu.analysis.core import RULE_IDS, Finding, Report, run_analysis

__all__ = [
    "Baseline",
    "BaselineError",
    "DEFAULT_BASELINE",
    "Finding",
    "METRIC_FAMILIES",
    "Report",
    "RepoContext",
    "RULE_IDS",
    "run_analysis",
]
