"""The plan compiler: enumerate -> validate -> cost -> measure -> install.

One `Planner` binds a live Session to the search machinery:

  1. enumerate   candidate (algorithm × topology × per-hop wire) plans per
                 tensor-size bucket (candidates.py);
  2. validate    every candidate through kf-lint (validate.py); rejected
                 candidates are journaled (`plan_rejected`) and can never
                 win;
  3. cost        the survivors against the α-β model fitted from measured
                 telemetry, probe-seeded where history is missing
                 (model.py / probe.py / cost.py);
  4. measure     the top predicted finalists — plus the hand-tuned default
                 as a control — with a short real A/B on the live session
                 (the model prunes 16-64 candidates down to ~3 runoffs;
                 GC3's shape: model for breadth, measurement for truth);
  5. install     the winner through Session.set_strategy + per-axis
                 CompressionConfig (`plan_selected` journaled), and
                 persist it to the JSON plan cache so tuning survives
                 restarts (cache.py).

`replan(reason)` re-runs the pipeline online — the ReplanPolicy calls it
when the interference vote or GNS monitor fires or the cluster resizes.
"""
from __future__ import annotations

import statistics
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..monitor.journal import journal_event
from ..utils import get_logger
from . import cost as cost_mod
from .cache import PlanCache
from .candidates import (
    Bucket,
    Plan,
    SCHEMES,
    bucket_for,
    default_buckets,
    enumerate_plans,
    hosts_for,
    topology_digest,
)
from .model import CostModel, fit_cost_model
from .probe import probe_links
from .validate import validate_plan

log = get_logger("kungfu.planner")


class Planner:
    """Cost-model autotuner over one Session's collective configuration.

    Args:
      session: the live Session plans are measured on and installed into.
      hosts: explicit host grouping (list of per-host rank lists); default
        derives it from session.size/host_count the way HostList fills.
      buckets: tensor-size bands to tune (candidates.default_buckets()).
      schemes: wire schemes the per-hop search considers.
      cache: a PlanCache, a path, or None (no persistence).
      counters: the Counters telemetry is harvested from (default: the
        process-global monitor counters).
    """

    def __init__(self, session, hosts=None, buckets=None,
                 schemes: Sequence[str] = SCHEMES, cache=None,
                 counters=None):
        from ..monitor.counters import global_counters

        self.session = session
        self.hosts = ([list(h) for h in hosts] if hosts is not None
                      else hosts_for(session.size, session.host_count))
        self.buckets: Sequence[Bucket] = tuple(buckets or default_buckets())
        self.schemes = tuple(schemes)
        if isinstance(cache, str):
            cache = PlanCache(cache)
        self.cache: Optional[PlanCache] = cache
        self.counters = counters if counters is not None else global_counters()
        self.model: Optional[CostModel] = None

    # -- identity ---------------------------------------------------------------------

    @property
    def world(self) -> int:
        return self.session.size

    def digest(self) -> str:
        return topology_digest(self.hosts, self.session.mesh.axis_names)

    def default_link(self) -> str:
        return "dcn" if self.session.host_count > 1 else "ici"

    def bucket(self, nbytes: int) -> Bucket:
        return bucket_for(nbytes, self.buckets)

    def default_plan(self, bucket: Bucket) -> Plan:
        """The hand-tuned baseline: one-shot tree allreduce, full
        precision — what a Session runs before any planning."""
        leg = self.default_link()
        return Plan(algorithm="binary_tree", strategy_name="BINARY_TREE",
                    wire=((leg, "none"),), bucket=bucket.id,
                    world=self.world)

    # -- model ------------------------------------------------------------------------

    def ensure_model(self, probe: bool = True, refit: bool = False) -> CostModel:
        """Fit (or refit) the cost model from the current telemetry.

        When `probe` is set, links/schemes with no measured history are
        seeded by the probe microbenchmark first — a fresh fleet fits from
        probes alone, a long-running one mostly from its own traffic.
        """
        if self.model is not None and not refit:
            return self.model
        if probe:
            from .model import harvest_points

            link = self.default_link()
            have = harvest_points(self.counters, self.world,
                                  default_link=link)
            missing = [s for s in self.schemes if (link, s) not in have]
            if missing:
                n = probe_links(self.session, self.counters,
                                schemes=missing, link=link)
                log.info("probe seeded %d points for %s", n, missing)
        self.model = fit_cost_model(self.counters, self.world,
                                    default_link=self.default_link())
        return self.model

    def fit_offline(self, snapshot: Dict) -> CostModel:
        """Fit from a dumped Counters.snapshot_json (no probes, no session
        traffic) — the offline path for a scraped fleet /metrics dump."""
        from ..monitor.counters import Counters

        self.model = fit_cost_model(
            Counters.load_snapshot(snapshot), self.world,
            default_link=self.default_link(),
        )
        return self.model

    # -- search -----------------------------------------------------------------------

    def candidates(self, bucket: Bucket) -> List[Plan]:
        return enumerate_plans(self.world, self.hosts, bucket,
                               schemes=self.schemes)

    def search(self, bucket: Bucket,
               candidates: Optional[Sequence[Plan]] = None) -> Dict:
        """Validate + cost every candidate; returns {"ranked": [(plan,
        predicted_ms)...best-first], "rejected": [(plan, reason)...]}.

        Every rejection is journaled — an illegal candidate must leave a
        trace, not just disappear from the ranking.
        """
        model = self.ensure_model()
        cands = list(candidates if candidates is not None
                     else self.candidates(bucket))
        ranked, rejected = [], []
        for plan in cands:
            problems = validate_plan(plan, self.hosts)
            if problems:
                reason = "; ".join(problems)
                rejected.append((plan, reason))
                log.warning("plan rejected: %s: %s", plan.describe(), reason)
                journal_event("plan_rejected", plan=plan.describe(),
                              bucket=bucket.id, reason=reason)
                continue
            ranked.append(
                (plan, cost_mod.predict_ms(plan, bucket.rep_bytes, model,
                                           self.hosts)))
        ranked.sort(key=lambda t: t[1])
        return {"ranked": ranked, "rejected": rejected}

    def _measure(self, plan: Plan, nbytes: int, reps: int = 3) -> float:
        """Median wall ms of the plan's allreduce at `nbytes` payload on
        the live session (one unmeasured warmup per compiled program)."""
        from .candidates import FUSED_MATMUL_ALGORITHMS

        if plan.algorithm in FUSED_MATMUL_ALGORITHMS:
            ms = self._measure_fused_matmul(plan, nbytes, reps=reps)
            if ms is not None:
                return ms
        elems = max(int(nbytes) // 4, 1)
        x = self.session.lift(
            np.random.RandomState(7).randn(elems).astype(np.float32))
        comp = plan.compression()
        kw = dict(strategy=plan.strategy,
                  compression=comp if comp is not None else "none")
        name = f"plan-measure:{plan.describe()}"
        self.session.all_reduce(x, name=f"{name}:warm", **kw)
        times = []
        for _ in range(max(reps, 1)):
            t0 = time.perf_counter()
            self.session.all_reduce(x, name=name, **kw)
            times.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(times)

    def _measure_fused_matmul(self, plan: Plan, nbytes: int,
                              reps: int = 3) -> Optional[float]:
        """Median EXPOSED-communication ms of a fused matmul plan: the
        fused kernel's wall time minus the pure-compute (no-collective)
        matmul at the same shape — the quantity comparable to an
        allreduce latency in the runoff (it is what the step actually
        pays for this tensor band's gather/scatter under the fused
        schedule).  The weight payload totals `nbytes` across ranks.
        Returns None when the session mesh has no single flat axis (the
        caller falls back to the allreduce measurement)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from ..ops import fused_matmul as FM

        mesh = self.session.mesh
        if len(mesh.axis_names) != 1:
            return None
        axis = mesh.axis_names[0]
        n = self.world
        cols = 128
        rows = max((max(int(nbytes) // 4, 1) // cols // n) * n, n)
        dtype = (jnp.bfloat16 if plan.wire_scheme(plan.legs[0]) == "bf16"
                 else jnp.float32)
        rng = np.random.RandomState(7)
        m = 128
        w = jnp.asarray(rng.randn(n, rows // n, cols), dtype)

        if plan.algorithm == "ag_matmul":
            x = jnp.asarray(rng.randn(n, m, rows), dtype)
            fused = jax.jit(shard_map(
                lambda xx, ww: FM.all_gather_matmul(xx[0], ww[0], axis),
                mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(axis),
                check_vma=False))
            compute = jax.jit(shard_map(
                lambda xx, ww: jnp.dot(
                    xx[0], jnp.concatenate([ww[0]] * n, axis=0),
                    preferred_element_type=jnp.float32).astype(dtype),
                mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(axis),
                check_vma=False))
        else:  # matmul_rs
            x = jnp.asarray(rng.randn(n, m * n, rows // n), dtype)
            fused = jax.jit(shard_map(
                lambda xx, ww: FM.matmul_reduce_scatter(xx[0], ww[0], axis),
                mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(axis),
                check_vma=False))
            compute = jax.jit(shard_map(
                lambda xx, ww: jnp.dot(
                    xx[0], ww[0],
                    preferred_element_type=jnp.float32).astype(dtype),
                mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(axis),
                check_vma=False))

        def timed(fn):
            jax.block_until_ready(fn(x, w))  # compile + warm
            ts = []
            for _ in range(max(reps, 1)):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x, w))
                ts.append((time.perf_counter() - t0) * 1e3)
            return statistics.median(ts)

        fused_ms = timed(fused)
        compute_ms = timed(compute)
        # exposed communication; floor at a measurable epsilon so a fully
        # hidden schedule still records a positive latency
        return max(fused_ms - compute_ms, 1e-3)

    def tune(self, bucket: Bucket, reps: int = 3, measure_top: int = 2,
             use_cache: bool = True, install: bool = False,
             source: str = "search") -> Dict:
        """Full pipeline for one bucket; returns the tuning record.

        A cache hit (same world/topology/bucket) skips probing and the
        measured runoff entirely and reuses the persisted winner.  A miss
        runs search, then measures the `measure_top` best-predicted plans
        plus the hand-tuned default as a control, and the measured winner
        — never the merely-predicted one — becomes the plan of record.
        """
        key = (self.world, self.digest(), bucket.id)
        if use_cache and self.cache is not None:
            entry = self.cache.get(*key)
            plan = self.cache.get_plan(*key)
            if plan is not None and not validate_plan(plan, self.hosts,
                                                      session=self.session):
                if install:
                    self.install(plan, predicted_ms=entry.get("predicted_ms"),
                                 measured_ms=entry.get("measured_ms"),
                                 source="cache")
                return {
                    "bucket": bucket.id, "cache_hit": True,
                    "plan": plan.to_json(), "describe": plan.describe(),
                    "predicted_ms": entry.get("predicted_ms"),
                    "measured_ms": entry.get("measured_ms"),
                    "default_ms": entry.get("default_ms"),
                    "rejected": 0, "measured": 0,
                }
        result = self.search(bucket)
        ranked = result["ranked"]
        if not ranked:
            raise RuntimeError(
                f"every candidate for bucket {bucket.id} was rejected")
        default = self.default_plan(bucket)
        finalists = [p for p, _ in ranked[:max(measure_top, 1)]]
        if default not in finalists:
            finalists.append(default)
        predicted = dict((p, ms) for p, ms in ranked)
        model = self.ensure_model()
        if default not in predicted:
            predicted[default] = cost_mod.predict_ms(
                default, bucket.rep_bytes, model, self.hosts)
        measured: Dict[Plan, float] = {}
        for p in finalists:
            problems = validate_plan(p, self.hosts, session=self.session)
            if problems:
                journal_event("plan_rejected", plan=p.describe(),
                              bucket=bucket.id, stage="program-lint",
                              reason="; ".join(problems))
                continue
            measured[p] = self._measure(p, bucket.rep_bytes, reps=reps)
        if not measured:
            raise RuntimeError(
                f"no finalist for bucket {bucket.id} survived program lint")
        winner = min(measured, key=lambda p: measured[p])
        pred = predicted.get(winner)
        meas = measured[winner]
        rel_err = (abs(pred - meas) / meas) if (pred is not None and meas > 0) else None
        record = {
            "bucket": bucket.id, "cache_hit": False,
            "plan": winner.to_json(), "describe": winner.describe(),
            "predicted_ms": round(pred, 4) if pred is not None else None,
            "measured_ms": round(meas, 4),
            "rel_err": round(rel_err, 4) if rel_err is not None else None,
            "default_ms": round(measured.get(default, float("nan")), 4)
            if default in measured else None,
            "finalists": [
                {"plan": p.describe(),
                 "predicted_ms": round(predicted.get(p, float("nan")), 4),
                 "measured_ms": round(measured[p], 4)}
                for p in measured
            ],
            "rejected": len(result["rejected"]),
            "measured": len(measured),
        }
        if self.cache is not None:
            self.cache.put(self.world, self.digest(), bucket.id, winner,
                           predicted_ms=record["predicted_ms"],
                           measured_ms=record["measured_ms"], model=model)
            # keep the control measurement so a later cache read still
            # shows predicted-vs-default context
            e = self.cache.get(self.world, self.digest(), bucket.id)
            if e is not None and record["default_ms"] is not None:
                e["default_ms"] = record["default_ms"]
                self.cache.save()
        if install:
            self.install(winner, predicted_ms=record["predicted_ms"],
                         measured_ms=record["measured_ms"], source=source)
        return record

    def tune_all(self, reps: int = 3, use_cache: bool = True,
                 install_for_bytes: Optional[int] = None,
                 source: str = "search") -> List[Dict]:
        """Tune every bucket; optionally install the winner of the bucket
        `install_for_bytes` falls into (installing per-bucket winners
        sequentially would just thrash the session default)."""
        records = []
        target = (self.bucket(install_for_bytes)
                  if install_for_bytes is not None else None)
        for b in self.buckets:
            records.append(self.tune(
                b, reps=reps, use_cache=use_cache,
                install=(target is not None and b.id == target.id),
                source=source,
            ))
        return records

    # -- install / replan -------------------------------------------------------------

    def install(self, plan: Plan, predicted_ms: Optional[float] = None,
                measured_ms: Optional[float] = None,
                source: str = "search") -> None:
        """Land a winning plan on the live session: strategy + per-axis
        wire dtype, with the decision journaled (`plan_selected`)."""
        self.session.set_strategy(plan.strategy)
        self.session.set_compression(plan.compression())
        journal_event(
            "plan_selected", plan=plan.describe(), bucket=plan.bucket,
            algorithm=plan.algorithm, strategy=plan.strategy_name,
            wire=dict(plan.wire), predicted_ms=predicted_ms,
            measured_ms=measured_ms, world=self.world,
            topology_digest=self.digest(), source=source,
        )
        log.info("installed plan %s (predicted %.4g ms, measured %.4g ms)",
                 plan.describe(), predicted_ms or float("nan"),
                 measured_ms or float("nan"))

    def on_resize(self) -> int:
        """Cluster shape changed: recompute hosts, drop stale cache keys.
        Returns how many cache entries were invalidated."""
        self.hosts = hosts_for(self.session.size, self.session.host_count)
        self.model = None  # old fit described another world
        if self.cache is None:
            return 0
        return self.cache.invalidate_stale(self.world, self.digest())

    def replan(self, reason: str, install_for_bytes: int = 4 << 20,
               reps: int = 3) -> List[Dict]:
        """Online re-plan: refit from the latest telemetry and re-run the
        search, bypassing the cache (the trigger means conditions changed
        — a cached winner is stale by definition)."""
        journal_event("replan", reason=reason, world=self.world,
                      topology_digest=self.digest())
        if reason == "resize":
            dropped = self.on_resize()
            if dropped:
                log.info("resize invalidated %d cached plans", dropped)
        self.ensure_model(refit=True)
        return self.tune_all(reps=reps, use_cache=False,
                             install_for_bytes=install_for_bytes,
                             source=f"replan:{reason}")
