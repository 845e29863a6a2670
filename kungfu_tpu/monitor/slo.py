"""Declarative SLO rule engine over the fleet time-series store.

The fleet can *measure* everything (PR 4/8) and *remember* it
(monitor.timeseries); nothing declares "this is out of spec".  The MPI
characterization lesson applies directly: the headline health signal for
hand-scheduled collectives is scaling efficiency vs ideal, and a
regression there must FAIL something — not scroll past in a dashboard.

A rule is (metric expr, predicate, sustain window, severity):

    {"name": "step_latency_p99", "metric": "hist:step_latency_ms:p99",
     "op": "<=", "threshold": 2000.0, "sustain_s": 15.0,
     "severity": "page", "description": "..."}

`metric` names a series in the time-series store (see the naming scheme in
monitor/timeseries.py) or a ratio of two (`"a/b"`).  The predicate states
the HEALTHY condition — the rule breaches when it is violated continuously
for `sustain_s` (arm) and clears after `clear_s` of continuous health
(PR-8-style arm/clear hysteresis, so a boundary-hugging metric cannot
flap).  Transitions journal `slo_breach` / `slo_cleared`, set the
`slo_active_<rule>` gauge, and count `slo_breaches` — and the launcher's
`-slo-exit-code` mode turns any sustained breach into exit
`SLO_EXIT_CODE` for drills and CI.

Rules load from `KFT_SLO_FILE` (JSON `{"rules": [...]}`, optional
`"include_defaults": true`) or fall back to the shipped defaults below.
The fleet aggregator serves the evaluated state at `/slo`
(docs/observability.md).
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..utils import get_logger
from ..utils.trace import job_now
from .journal import journal_event
from .timeseries import TimeSeriesStore

log = get_logger("kungfu.slo")

SLO_FILE_ENV = "KFT_SLO_FILE"
#: launcher exit code under -slo-exit-code when any rule sustained a breach
SLO_EXIT_CODE = 92

_OPS: Dict[str, Callable[[float, float], bool]] = {
    "<": lambda v, t: v < t,
    "<=": lambda v, t: v <= t,
    ">": lambda v, t: v > t,
    ">=": lambda v, t: v >= t,
}


@dataclasses.dataclass(frozen=True)
class SLORule:
    """One declarative service-level objective.

    `op`/`threshold` state the HEALTHY predicate (`value op threshold`);
    the rule breaches when the predicate is violated continuously for
    `sustain_s` and clears after `clear_s` (default = sustain_s, floored
    at one evaluation) of continuous health."""

    name: str
    metric: str
    op: str
    threshold: float
    sustain_s: float = 15.0
    clear_s: Optional[float] = None
    severity: str = "warn"
    description: str = ""
    # tenant selector: scope a histogram rule to one tenant's labeled
    # series (hist:<m>[<tenant>]:<pct>) — per-tenant SLOs on the shared
    # rule schema, no new rule type
    tenant: str = ""

    def __post_init__(self):
        if self.op not in _OPS:
            raise ValueError(f"SLO rule {self.name!r}: unknown op {self.op!r}")

    def healthy(self, value: float) -> bool:
        return _OPS[self.op](float(value), float(self.threshold))

    @property
    def effective_clear_s(self) -> float:
        return self.sustain_s if self.clear_s is None else self.clear_s

    @property
    def series_expr(self) -> str:
        """The store series this rule actually watches: `metric` with the
        tenant label spliced into each hist side (a `tenant=` on a gauge
        or rate expr is a no-op — only histograms carry labels)."""
        if not self.tenant:
            return self.metric

        def splice(expr: str) -> str:
            expr = expr.strip()
            if expr.startswith("hist:"):
                head, _, pct = expr.rpartition(":")
                return f"{head}[{self.tenant}]:{pct}"
            return expr

        if "/" in self.metric:
            a, _, b = self.metric.partition("/")
            return f"{splice(a)}/{splice(b)}"
        return splice(self.metric)

    def to_json(self) -> Dict[str, Any]:
        return {
            "name": self.name, "metric": self.metric, "op": self.op,
            "threshold": self.threshold, "sustain_s": self.sustain_s,
            "clear_s": self.effective_clear_s, "severity": self.severity,
            "description": self.description, "tenant": self.tenant,
        }

    @classmethod
    def from_json(cls, obj: Dict[str, Any]) -> "SLORule":
        return cls(
            name=str(obj["name"]), metric=str(obj["metric"]),
            op=str(obj.get("op", "<=")), threshold=float(obj["threshold"]),
            sustain_s=float(obj.get("sustain_s", 15.0)),
            clear_s=(float(obj["clear_s"]) if obj.get("clear_s") is not None
                     else None),
            severity=str(obj.get("severity", "warn")),
            description=str(obj.get("description", "")),
            tenant=str(obj.get("tenant", "")),
        )


#: shipped defaults — generous enough not to false-fire on healthy CPU
#: drills, tight enough that the chaos/scaling regressions the check.sh
#: drills induce trip them.  Operators override via KFT_SLO_FILE.
DEFAULT_RULES: List[SLORule] = [
    SLORule("step_latency_p99", "hist:step_latency_ms:p99", "<=", 2000.0,
            sustain_s=15.0, severity="page",
            description="windowed fleet step-latency p99 stays under 2 s"),
    SLORule("collective_wait_frac", "gauge:collective_wait_frac", "<=", 0.5,
            sustain_s=30.0, severity="warn",
            description="median fraction of each step spent waiting in "
                        "collectives stays under half the step"),
    SLORule("queue_depth", "gauge:queue_depth", "<=", 64.0,
            sustain_s=30.0, severity="page",
            description="serving admission-queue depth stays bounded "
                        "(sustained depth = the autoscaler lost the race)"),
    SLORule("request_latency_p99", "hist:request_latency_ms:p99", "<=",
            30000.0, sustain_s=15.0, severity="page",
            description="windowed serving request-latency p99 stays under "
                        "30 s; a breach journals the tail sampler's "
                        "per-phase attribution (dominant_phase)"),
    SLORule("heal_mttr", "gauge:heal_mttr_s", "<=", 30.0,
            sustain_s=0.0, severity="warn",
            description="worker-death-to-first-post-heal-step stays under "
                        "30 s (the recovery ladder's contract)"),
    SLORule("scaling_efficiency", "gauge:allreduce_scaling_efficiency",
            ">=", 0.4, sustain_s=0.0, severity="page",
            description="allreduce scaling efficiency vs ideal stays above "
                        "the floor — a scaling regression fails the bench, "
                        "not just single-chip speed"),
    SLORule("recompile_storm", "rate:recompile_storm", "<=", 0.0,
            sustain_s=0.0, severity="page",
            description="no recompile storms: a tracked program burning "
                        "through new XLA signatures re-pays full compiles "
                        "on its hot path (monitor/programs.py; the rule "
                        "stays no_data on fleets that never storm)"),
    SLORule("coordinator_flapping", "rate:leader_elected", "<=", 0.1,
            sustain_s=10.0, severity="page",
            description="config-plane leader elections stay rare (< ~1 per "
                        "10 s sustained): repeated failovers mean the "
                        "ensemble is flapping — lease/heartbeat tuning or a "
                        "sick replica — not healing (elastic/ensemble.py "
                        "feeds rate:leader_elected; stays no_data on "
                        "single-server fleets)"),
]


def load_rules(path: Optional[str] = None) -> List[SLORule]:
    """Rules from `path` / KFT_SLO_FILE, else the shipped defaults.

    A rule file takes full control (its rules replace the defaults) unless
    it sets `"include_defaults": true`, in which case defaults not named in
    the file are appended."""
    path = path or os.environ.get(SLO_FILE_ENV, "")
    if not path:
        return list(DEFAULT_RULES)
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError) as e:
        log.warning("SLO file %s unreadable (%s); using shipped defaults",
                    path, e)
        return list(DEFAULT_RULES)
    rules = [SLORule.from_json(r) for r in obj.get("rules", [])]
    if obj.get("include_defaults"):
        named = {r.name for r in rules}
        rules.extend(r for r in DEFAULT_RULES if r.name not in named)
    return rules


class _RuleState:
    __slots__ = ("breached", "viol_since", "pass_since", "last_value",
                 "last_t", "breaches", "breached_at")

    def __init__(self):
        self.breached = False
        self.viol_since: Optional[float] = None
        self.pass_since: Optional[float] = None
        self.last_value: Optional[float] = None
        self.last_t: Optional[float] = None
        self.breaches = 0
        self.breached_at: Optional[float] = None


class SLOEngine:
    """Evaluate rules against a TimeSeriesStore with arm/clear hysteresis.

    `evaluate()` is idempotent per sample: a rule only advances its streak
    when a NEW sample (fresh timestamp) lands, so polling `/slo` faster
    than the sampler tick cannot fake a sustained violation.  Rules whose
    series has no samples report `no_data` and never transition — the
    scaling-efficiency rule stays dormant in live training fleets and only
    fires where the series exists (the scaling bench)."""

    def __init__(self, store, rules: Optional[List[SLORule]] = None,
                 counters=None, journal: Callable[..., None] = journal_event,
                 clock: Callable[[], float] = job_now,
                 attribution_fn: Optional[
                     Callable[[SLORule, Optional[float]],
                              Optional[Dict[str, Any]]]] = None):
        self.store = store
        self.rules = list(rules) if rules is not None else load_rules()
        self.counters = counters
        self.journal = journal
        self.clock = clock
        # extra journal fields for breach transitions (e.g. the request
        # assembler's per-phase tail attribution: dominant_phase=kv_ship)
        self.attribution_fn = attribution_fn
        self._states: Dict[str, _RuleState] = {r.name: _RuleState()
                                               for r in self.rules}
        self.evaluations = 0

    # -- metric resolution ------------------------------------------------------------

    def _resolve(self, expr: str) -> Optional[tuple]:
        """Latest (t, value) for a series name or an `a/b` ratio of two."""
        if "/" in expr:
            num_name, _, den_name = expr.partition("/")
            num = self.store.latest(num_name.strip())
            den = self.store.latest(den_name.strip())
            if num is None or den is None or den[1] == 0:
                return None
            return (min(num[0], den[0]), num[1] / den[1])
        return self.store.latest(expr)

    # -- evaluation -------------------------------------------------------------------

    def evaluate(self, now: Optional[float] = None) -> Dict[str, Any]:
        self.evaluations += 1
        for rule in self.rules:
            st = self._states[rule.name]
            got = self._resolve(rule.series_expr)
            if got is None:
                continue  # no_data: hold state, never transition on silence
            t, value = got
            if st.last_t is not None and t <= st.last_t:
                continue  # same sample: streaks advance on new data only
            st.last_t, st.last_value = t, value
            if rule.healthy(value):
                st.viol_since = None
                if st.breached:
                    st.pass_since = t if st.pass_since is None else st.pass_since
                    if t - st.pass_since >= rule.effective_clear_s:
                        st.breached = False
                        st.pass_since = None
                        self._transition("slo_cleared", rule, st)
            else:
                st.pass_since = None
                st.viol_since = t if st.viol_since is None else st.viol_since
                if not st.breached and t - st.viol_since >= rule.sustain_s:
                    st.breached = True
                    st.breaches += 1
                    st.breached_at = t
                    self._transition("slo_breach", rule, st)
        return self.report()

    def _transition(self, event: str, rule: SLORule, st: _RuleState) -> None:
        log.warning("%s: %s (%s = %s, want %s %s)", event, rule.name,
                    rule.metric, st.last_value, rule.op, rule.threshold)
        extra: Dict[str, Any] = {}
        if event == "slo_breach" and self.attribution_fn is not None:
            try:
                # viol_since anchors the attribution window: the requests
                # since THIS violation began are the ones that caused it
                extra = self.attribution_fn(rule, st.viol_since) or {}
            except Exception as e:  # noqa: BLE001 - never block the breach
                log.debug("SLO attribution skipped: %s", e)
                extra = {}
        if rule.tenant:
            extra.setdefault("tenant", rule.tenant)
        self.journal(event, rule=rule.name, metric=rule.metric,
                     value=st.last_value, op=rule.op,
                     threshold=rule.threshold, severity=rule.severity,
                     sustain_s=rule.sustain_s, **extra)
        if self.counters is not None:
            self.counters.inc_event("slo_breaches" if event == "slo_breach"
                                    else "slo_clears")
            self.counters.set_gauge(f"slo_active_{rule.name}",
                                    1.0 if st.breached else 0.0)

    # -- reporting --------------------------------------------------------------------

    @property
    def breach_total(self) -> int:
        """Sustained breaches over the engine's lifetime — the
        -slo-exit-code signal (a breach that later cleared still counts:
        the SLO was violated on this run)."""
        return sum(st.breaches for st in self._states.values())

    def active(self) -> List[str]:
        return sorted(name for name, st in self._states.items() if st.breached)

    def report(self) -> Dict[str, Any]:
        rules: Dict[str, Any] = {}
        for rule in self.rules:
            st = self._states[rule.name]
            rules[rule.name] = {
                **rule.to_json(),
                "breached": st.breached,
                "breaches": st.breaches,
                "no_data": st.last_t is None,
                "last_value": st.last_value,
                "last_t": st.last_t,
            }
        return {
            "rules": rules,
            "active": self.active(),
            "breach_total": self.breach_total,
            "evaluations": self.evaluations,
            "t_job": round(self.clock(), 3),
        }


def resolve_exit_code(rc: int, breach_total: int) -> int:
    """The -slo-exit-code contract: a clean run keeps its exit code; any
    sustained breach turns a would-be-zero exit into SLO_EXIT_CODE (a
    real failure's nonzero code is never masked)."""
    if rc == 0 and breach_total > 0:
        return SLO_EXIT_CODE
    return rc


# -- scaling-efficiency gate (the `scaling_efficiency` rule's caller) ------------------


def evaluate_scaling_slo(efficiency_samples: Sequence[float],
                         rules=None, journal=None):
    """Feed an efficiency sequence through the SLO engine and return
    (engine, breached).  The shipped `scaling_efficiency` floor rule
    (sustain 0) is the gate; synthetic timestamps one second apart make
    each sample its own evaluation window."""
    if rules is None:
        rules = [r for r in load_rules()
                 if r.metric == "gauge:allreduce_scaling_efficiency"]
        if not rules:  # an operator file without the rule keeps the gate
            rules = [r for r in DEFAULT_RULES
                     if r.name == "scaling_efficiency"]
    store = TimeSeriesStore()
    kw = {"journal": journal} if journal is not None else {}
    engine = SLOEngine(store, rules=rules, clock=lambda: 0.0, **kw)
    for i, eff in enumerate(efficiency_samples):
        t = float(i + 1)
        store.record("gauge:allreduce_scaling_efficiency", t, eff)
        engine.evaluate(now=t)
    return engine, engine.breach_total > 0
