"""kf-lint — jaxpr-level static analysis for collective programs.

KungFu's adaptation story (swap the topology, the wire format, the cluster
size — mid-training) is only usable if every such change is cheap to trust:
on TPU a typo'd axis name, a cond whose branches disagree about their
collectives, a non-bijective ppermute or a raw fp32 psum on an axis the
deployment quantizes all compile fine and then hang or silently corrupt a
multi-minute SPMD launch.  GC3 (arXiv:2201.11840) showed collective
programs are tractable targets for compile-time reasoning; EQuARX
(arXiv:2506.17615) showed quantized-collective correctness rests on
statically checkable dtype-flow invariants.  This package enforces both
classes of invariant on traced jaxprs — before anything touches hardware.

Three surfaces:

  library     `analysis.check(fn, *args, mesh=..., compression=...)`
              traces fn (no devices, no compile) and returns structured
              `Finding`s with jaxpr provenance.
  hooks       `Session(..., analyze=True)`, `synchronous_sgd(...,
              analyze=True)`, `pair_averaging(..., analyze=True)` — or
              `KUNGFU_ANALYZE=1` — run the checker at trace time and
              raise `AnalysisError` on error-severity findings before
              dispatch.
  CLI         `python -m kungfu_tpu.analysis` lints the built-in program
              corpus (optimizers, examples, benchmark programs, every
              registered strategy implementation); `--module pkg.mod`
              lints a module's declared PROGRAMS.

Layout: findings.py (Finding/AnalysisError), extract.py (jaxpr walker +
replication tracking), rules.py (the rule engine), check.py (entry
points), programs.py (the built-in corpus the CLI checks).

kf-verify (docs/analysis.md) extends the same Finding machinery below
the jaxpr and above it:

  schedules   schedule.py + deadlock.py — a chunk-level IR for collective
              schedules with verifiers for dataflow correctness (symbolic
              chunk-set simulation), slot-race freedom, deadlock freedom
              (wait-for cycles over slots/credits) and per-round cost
              annotation matching planner/cost.py.  CLI: `--schedules`.
  host code   hostlint.py — AST lint of the control plane (conditional
              PUTs, journal-kind registry, lock order, thread lifecycle,
              wall-clock durations) + envaudit.py, the KFT_* env drift
              audit.  CLI: `--hostlint`, `--env`, and `--all` for the
              whole battery.
"""
from .findings import (  # noqa: F401
    ALL_RULES,
    ERROR,
    EVERY_RULE,
    HOST_RULES,
    INFO,
    SCHEDULE_RULES,
    WARNING,
    RULE_AXIS,
    RULE_BARE_PUT,
    RULE_CONFIG_SINGLE_URL,
    RULE_DEADLOCK,
    RULE_ENV_DRIFT,
    RULE_JOURNAL_KIND,
    RULE_LOCK_ORDER,
    RULE_PERMUTATION,
    RULE_REPLICATION,
    RULE_SCHED_DATAFLOW,
    RULE_SCHED_DEADLOCK,
    RULE_SCHED_SLOT,
    RULE_THREAD_LIFECYCLE,
    RULE_WALL_CLOCK,
    RULE_WIRE_DTYPE,
    AnalysisError,
    Finding,
    errors,
    format_findings,
)
from .extract import Collective, CondSite, Extraction, OutputLeak, extract  # noqa: F401
from .rules import RULES, RuleContext, run_rules  # noqa: F401
from .check import (  # noqa: F401
    abstractify,
    assert_clean,
    check,
    check_and_raise,
    check_axes_in_scope,
    check_collective_plan,
    check_elastic_permutations,
)

from .schedule import (  # noqa: F401
    Schedule,
    Transfer,
    builtin_schedules,
    schedule_cost,
    schedule_for_plan,
    verify_schedule,
)
from .deadlock import verify_deadlock_free  # noqa: F401

__all__ = [
    "ALL_RULES", "SCHEDULE_RULES", "HOST_RULES", "EVERY_RULE",
    "ERROR", "WARNING", "INFO",
    "RULE_AXIS", "RULE_DEADLOCK", "RULE_PERMUTATION", "RULE_REPLICATION",
    "RULE_WIRE_DTYPE",
    "RULE_SCHED_DATAFLOW", "RULE_SCHED_DEADLOCK", "RULE_SCHED_SLOT",
    "RULE_BARE_PUT", "RULE_JOURNAL_KIND", "RULE_LOCK_ORDER",
    "RULE_THREAD_LIFECYCLE", "RULE_WALL_CLOCK", "RULE_ENV_DRIFT",
    "RULE_CONFIG_SINGLE_URL",
    "AnalysisError", "Finding", "errors", "format_findings",
    "Collective", "CondSite", "Extraction", "OutputLeak", "extract",
    "RULES", "RuleContext", "run_rules",
    "abstractify", "assert_clean", "check", "check_and_raise",
    "check_axes_in_scope", "check_collective_plan",
    "check_elastic_permutations",
    "Schedule", "Transfer", "builtin_schedules", "schedule_cost",
    "schedule_for_plan", "verify_schedule", "verify_deadlock_free",
]
