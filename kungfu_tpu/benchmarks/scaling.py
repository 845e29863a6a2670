"""Scaling-efficiency observatory — efficiency vs ideal across world sizes.

The MPI characterization lesson (arXiv 1810.11112) is that the headline
health metric for hand-scheduled collectives is *scaling efficiency vs
ideal*, and the TPU-pod MLPerf work (arXiv 1909.09756) shows the failure
modes that matter (DCN hotspots, stragglers, input starvation) only
surface as trends across world sizes — point samples at one size can look
perfectly healthy while the curve collapses.  This module is the curve
harness:

  * a fixed collective microbench swept across world sizes AND algorithms
    (ring / hierarchical / pallas_ring) per payload bucket — bus-bandwidth
    efficiency vs the smallest multi-rank size (busbw already normalizes
    the 2(n-1)/n algorithmic factor, so flat = perfect);
  * a train-step microbench (per-peer grads + bucketed gradient sync, the
    data-parallel step shape) whose per-size efficiency is
    compute_ms/step_ms — "ideal" = a step with zero communication — and
    whose lost fraction decomposes in the PR-8 style into
    compute / data-wait / collective-wait fractions;
  * an SLO gate: every efficiency point feeds a time-series store
    (monitor.timeseries) and the `scaling_efficiency` floor rule
    (monitor.slo) — a sustained dip below the floor journals `slo_breach`
    and FAILS the bench with a nonzero exit, so a scaling regression is a
    first-class failure, not a dashboard footnote.

CPU hosts force a virtual multi-device platform (the repo's standard
multi-chip stand-in; sizes 1/2/4 by default), and the curve machinery is
world-size-agnostic — the netns 64–256-rank drill from ROADMAP item 1
plugs straight in.  `--chaos-collective-ms N` injects a per-dispatch delay
at the LARGEST world size only (a DCN hotspot that appears at scale), the
induced regression that must trip the floor.

    python -m kungfu_tpu.benchmarks --bench scaling [--sizes 1,2,4] \
        [--chaos-collective-ms 50] [--out SCALING.json]

`bench.py` records the result as the BENCH json's `scaling` section
through the probed runner.  The legacy `python -m
kungfu_tpu.benchmarks.scaling` weak-scaling sweep (`run`/`main` below) is
kept for the v5e multi-chip harness.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional, Sequence


def _ensure_devices(min_devices: int) -> None:
    """Force a virtual multi-device CPU platform when no TPU is asked for.

    Backend selection is lazy: `import jax` (already done by the package
    import that got us here) does NOT pick a backend, so flipping the env +
    jax.config BEFORE the first device use is still effective.
    """
    if _tpu_expected():
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={min_devices}"
        ).strip()
    # an inherited value must be OVERRIDDEN, not defaulted: this sweep is
    # written for the virtual CPU mesh
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_platforms", "cpu")


def _tpu_expected() -> bool:
    # KFT_SCALING_TPU=1 opts into probing the real chip; default is the
    # CPU mesh
    return os.environ.get("KFT_SCALING_TPU") == "1"


# -- pure curve math (unit-tested on synthetic throughput curves) ----------------------


def efficiency_curve(rows: Sequence[Dict]) -> List[Dict]:
    """Stamp `scaling_efficiency` onto multi-rank rows: busbw(n) relative
    to the smallest multi-rank size (n=1 rows report but never baseline —
    there is no wire traffic at n=1)."""
    out = [dict(r) for r in rows]
    multi = [r for r in out if r["np"] > 1 and r.get("busbw_gibps")]
    if not multi:
        return out
    base = multi[0]["busbw_gibps"]
    for r in multi:
        r["scaling_efficiency"] = round(r["busbw_gibps"] / base, 3) if base else None
    return out


def step_attribution(step_ms: float, compute_ms: float,
                     data_ms: float = 0.0) -> Dict[str, float]:
    """Decompose one measured step into the PR-8 fractions: compute /
    data-wait / collective-wait.  `efficiency` is compute/step — the
    fraction of the step that would survive on an ideal (zero-
    communication) fleet; the lost fraction IS the collective wait."""
    step_ms = max(float(step_ms), 1e-9)
    compute_ms = min(max(float(compute_ms), 0.0), step_ms)
    data_ms = min(max(float(data_ms), 0.0), step_ms - compute_ms)
    wait_ms = max(0.0, step_ms - compute_ms - data_ms)
    return {
        "step_ms": round(step_ms, 3),
        "compute_ms": round(compute_ms, 3),
        "compute_frac": round(compute_ms / step_ms, 4),
        "data_frac": round(data_ms / step_ms, 4),
        "collective_wait_frac": round(wait_ms / step_ms, 4),
        "efficiency": round(compute_ms / step_ms, 4),
    }


def evaluate_scaling_slo(efficiency_samples: Sequence[float],
                         rules=None, journal=None):
    """Feed an efficiency sequence through the SLO engine and return
    (engine, breached).  The shipped `scaling_efficiency` floor rule
    (sustain 0) is the gate; synthetic timestamps one second apart make
    each sample its own evaluation window."""
    from ..monitor.slo import DEFAULT_RULES, SLOEngine, load_rules
    from ..monitor.timeseries import TimeSeriesStore

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


# -- the observatory -------------------------------------------------------------------

ALGORITHMS = ("ring", "hierarchical", "pallas_ring")
DEFAULT_BUCKETS: Dict[str, int] = {
    # payload bucket -> float32 element count (planner-style small/large)
    "small": 1 << 14,   # 64 KiB
    "large": 1 << 20,   # 4 MiB
}


def _algo_strategy(name: str):
    from ..plan import Strategy

    return {
        "ring": Strategy.RING,
        "hierarchical": Strategy.BINARY_TREE_STAR,
        "pallas_ring": Strategy.PALLAS_RING,
    }[name]


def _time_collective(session, elems: int, strategy, steps: int, warmup: int,
                     chaos_ms: float = 0.0) -> float:
    """Seconds per all-reduce dispatch of `elems` float32 on the session,
    with an optional injected per-dispatch delay (the chaos hotspot)."""
    import numpy as np
    import jax

    rng = np.random.RandomState(0)
    x = session.lift(rng.randn(elems).astype(np.float32))
    name = f"scaling/{strategy.name}/{elems}"

    def one():
        r = session.all_reduce(x, name=name, strategy=strategy)
        jax.block_until_ready(r)
        if chaos_ms > 0:
            time.sleep(chaos_ms / 1e3)

    for _ in range(warmup):
        one()
    t0 = time.perf_counter()
    for _ in range(steps):
        one()
    return (time.perf_counter() - t0) / steps


def _time_train_step(session, steps: int, warmup: int, dim: int = 128,
                     per_chip_batch: int = 16,
                     chaos_ms: float = 0.0) -> Dict[str, float]:
    """One data-parallel train step's (step_ms, compute_ms): per-peer
    grads (vmapped over each peer's row of the lifted batch) plus the
    gradient all-reduce; compute-only omits the sync — the ideal step."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    params = (jnp.asarray(rng.randn(dim, dim) * 0.05, jnp.float32),
              jnp.asarray(rng.randn(dim, dim) * 0.05, jnp.float32))
    x = session.lift(rng.randn(per_chip_batch, dim).astype(np.float32))

    def loss_fn(p, xb):
        h = jnp.tanh(xb @ p[0])
        y = h @ p[1]
        return jnp.mean(y * y)

    grad_fn = jax.jit(jax.vmap(jax.grad(loss_fn), in_axes=(None, 0)))

    def compute_only():
        jax.block_until_ready(grad_fn(params, x))

    def full_step():
        grads = grad_fn(params, x)
        synced = session.group_all_reduce(list(grads), name="scaling/grad")
        jax.block_until_ready(synced)
        if chaos_ms > 0:
            time.sleep(chaos_ms / 1e3)

    for _ in range(warmup):
        compute_only()
    t0 = time.perf_counter()
    for _ in range(steps):
        compute_only()
    compute_ms = (time.perf_counter() - t0) / steps * 1e3
    for _ in range(warmup):
        full_step()
    t0 = time.perf_counter()
    for _ in range(steps):
        full_step()
    step_ms = (time.perf_counter() - t0) / steps * 1e3
    return {"step_ms": step_ms, "compute_ms": compute_ms}


def bench_scaling(sizes: Sequence[int] = (1, 2, 4),
                  algorithms: Sequence[str] = ALGORITHMS,
                  buckets: Optional[Dict[str, int]] = None,
                  steps: int = 4, warmup: int = 1,
                  chaos_collective_ms: float = 0.0,
                  out: Optional[str] = None, slo: bool = True) -> Dict:
    """Run the observatory; returns the BENCH-json `scaling` record with
    `slo_breached` set when the efficiency floor tripped (the CLI turns
    that into a nonzero exit)."""
    import numpy as np
    import jax
    from jax.sharding import Mesh

    from ..session import Session

    sizes = sorted({int(s) for s in sizes})
    buckets = dict(buckets or DEFAULT_BUCKETS)
    devices = jax.devices()
    usable = [n for n in sizes if n <= len(devices)]
    for n in sizes:
        if n not in usable:
            print(f"# skipping np={n}: only {len(devices)} devices",
                  file=sys.stderr)
    chaos_at = max(usable) if usable else 0
    GiB = float(1 << 30)

    collective_rows: List[Dict] = []
    train_rows: List[Dict] = []
    for n in usable:
        mesh = Mesh(np.asarray(devices[:n]), ("dp",))
        session = Session(mesh)
        chaos_ms = chaos_collective_ms if (chaos_collective_ms and n == chaos_at
                                           and n > 1) else 0.0
        for algo in algorithms:
            strategy = _algo_strategy(algo)
            for bucket, elems in sorted(buckets.items()):
                try:
                    sec = _time_collective(session, elems, strategy,
                                           steps, warmup, chaos_ms=chaos_ms)
                except Exception as e:  # noqa: BLE001 - one algo must not sink the curve
                    print(f"# {algo}/{bucket}@np={n} failed: "
                          f"{type(e).__name__}: {e}", file=sys.stderr)
                    continue
                nbytes = elems * 4
                data_gibps = nbytes / sec / GiB
                busbw = data_gibps * (2.0 * (n - 1) / n if n > 1 else 1.0)
                collective_rows.append({
                    "np": n, "algorithm": algo, "bucket": bucket,
                    "payload_bytes": nbytes,
                    "dispatch_ms": round(sec * 1e3, 3),
                    "busbw_gibps": round(busbw, 4),
                    "chaos_ms": chaos_ms,
                })
        tt = _time_train_step(session, steps, warmup, chaos_ms=chaos_ms)
        att = step_attribution(tt["step_ms"], tt["compute_ms"])
        att["np"] = n
        train_rows.append(att)
        print(f"RESULT: bench=scaling np={n} train_step_ms="
              f"{att['step_ms']} efficiency={att['efficiency']} "
              f"collective_wait_frac={att['collective_wait_frac']}",
              flush=True)

    # efficiency per (algorithm, bucket) curve + the fleet headline
    by_algo: Dict[str, Dict[str, Optional[float]]] = {}
    eff_samples: List[float] = []
    stamped_rows: List[Dict] = []
    for algo in algorithms:
        for bucket in sorted(buckets):
            curve = efficiency_curve([
                r for r in collective_rows
                if r["algorithm"] == algo and r["bucket"] == bucket])
            stamped_rows.extend(curve)
            tail = [r for r in curve if r.get("scaling_efficiency") is not None]
            if tail:
                eff = tail[-1]["scaling_efficiency"]
                by_algo.setdefault(algo, {})[bucket] = eff
                eff_samples.append(eff)
                print(f"RESULT: bench=scaling algo={algo} bucket={bucket} "
                      f"np={tail[-1]['np']} efficiency={eff}", flush=True)

    headline = min(eff_samples) if eff_samples else None
    max_train = train_rows[-1] if train_rows else None

    slo_report = None
    breached = False
    if slo and eff_samples:
        from ..monitor.journal import journal_event

        engine, breached = evaluate_scaling_slo(eff_samples,
                                                journal=journal_event)
        slo_report = engine.report()
        if breached:
            print(f"RESULT: bench=scaling SLO BREACH: efficiency floor "
                  f"tripped (worst={headline})", flush=True)

    record = {
        "bench": "scaling",
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind,
        "sizes": usable,
        "chaos_collective_ms": chaos_collective_ms,
        "collective": stamped_rows,
        "train": train_rows,
        "efficiency_by_algorithm": by_algo,
        "allreduce_scaling_efficiency": headline,
        "loss_attribution": max_train,
        "slo": slo_report,
        "slo_breached": breached,
    }
    print(json.dumps(record), flush=True)
    if out:
        with open(out, "w") as f:
            json.dump(record, f, indent=2)
    return record


# -- netns pod arm (the 64-256-rank shaped-link fleet) ---------------------------------


def attach_pod_record(record: Dict, hosts: int, workers_per_host: int = 2,
                      steps_per_rank: int = 30,
                      timeout_s: float = 900.0) -> Dict:
    """Run the netns pod weak-scaling drill (scripts/pod_drill.py --bench)
    and attach its record as `record["pod"]` — the 64-256-rank shaped-link
    fleet feeding the SAME `scaling` BENCH section and SLO floor as the
    in-process curve.  Needs root + netns; unavailable environments get an
    honest `{"skipped": reason}` stamp instead of a silent omission."""
    import subprocess
    import tempfile

    from ..testing.pod import pod_available

    if not pod_available():
        record["pod"] = {"skipped": "netns unavailable (need root + ip/veth)"}
        return record
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sizes = sorted({1, max(2, hosts // 2), hosts})
    with tempfile.NamedTemporaryFile(suffix=".json") as out:
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "scripts", "pod_drill.py"),
             "--bench", "--sizes", ",".join(str(s) for s in sizes),
             "--workers-per-host", str(workers_per_host),
             "--steps-per-rank", str(steps_per_rank),
             "--timeout", str(timeout_s), "--json-out", out.name],
            capture_output=True, text=True, timeout=timeout_s + 120)
        try:
            record["pod"] = json.load(open(out.name))
        except (OSError, ValueError):
            record["pod"] = {"skipped": f"pod bench failed (rc={r.returncode})",
                             "stderr_tail": r.stderr[-1000:]}
            return record
    if record["pod"].get("slo_breached"):
        record["slo_breached"] = True  # the pod curve gates the bench too
    return record


# -- legacy weak-scaling sweep (kungfu-bench-allreduce analog) -------------------------


def run(sizes, model: str, steps: int, warmup: int, fuse: bool):
    import numpy as np
    import jax
    from jax.sharding import Mesh

    from . import bench_all_reduce
    from ..session import Session

    devices = jax.devices()
    rows = []
    for n in sizes:
        if n > len(devices):
            print(f"# skipping np={n}: only {len(devices)} devices", file=sys.stderr)
            continue
        mesh = Mesh(np.asarray(devices[:n]), ("dp",))
        session = Session(mesh)
        r = bench_all_reduce(
            session, model=model, method="auto", fuse=fuse,
            steps=steps, warmup=warmup,
        )
        print(r.line(n), flush=True)
        rows.append(
            {
                "np": n,
                "payload_bytes": r.payload_bytes,
                "step_ms": round(r.seconds_per_step * 1e3, 3),
                "data_gibps": round(r.data_gibps, 3),
                "busbw_gibps": round(r.busbw_gibps(n), 3),
            }
        )
    rows = efficiency_curve(rows)
    multi = [row for row in rows if row.get("scaling_efficiency") is not None]
    if multi:
        print(
            f"RESULT: bench=allreduce-scaling model={model} fuse={int(fuse)} "
            f"np={multi[0]['np']}->{multi[-1]['np']} "
            f"efficiency={multi[-1]['scaling_efficiency']:.3f}",
            flush=True,
        )
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kungfu_tpu.benchmarks.scaling")
    ap.add_argument("--sizes", default="1,2,4,8")
    ap.add_argument("--model", default="resnet50-imagenet")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--no-fuse", action="store_true")
    ap.add_argument("--out", default="", help="write rows as JSON to this file")
    args = ap.parse_args(argv)

    sizes = sorted({int(s) for s in args.sizes.split(",") if s})
    _ensure_devices(max(sizes))

    import jax

    rows = run(sizes, args.model, args.steps, args.warmup, fuse=not args.no_fuse)
    out = {
        "bench": "allreduce-scaling",
        "model": args.model,
        "fuse": not args.no_fuse,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "rows": rows,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
