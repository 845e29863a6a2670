"""Probed, requeueing bench runner.

Probe the backend with a short-timeout, tree-killable subprocess; run jobs
only while the probe passes; requeue failures to the back of the queue with
a bounded budget:

  probe_backend   the PROBE_OK sentinel probe: a trivial jit dispatch in a
                  throwaway subprocess that must prove a TPU answered (CPU
                  counts only when explicitly requested), so a backend
                  silently falling back to CPU can never drain a queue of
                  on-chip benchmarks on the host.
  Section         one bench section: a callable returning its record, or an
                  argv whose JSON record is read from `out_json` (or the
                  last JSON line of stdout).
  run_sections    the queue loop: probe before EVERY section, journal
                  `bench_probe_failed` on a dead backend, requeue failures
                  to the back (`bench_requeued`) under a per-section
                  attempt budget, and stamp `measured_this_run` honestly
                  into every record — True only when the section actually
                  ran to completion THIS invocation.

`python -m kungfu_tpu.benchmarks.runner --queue jobs.txt --out results.json`
is the unattended entrypoint (journaled events and a machine-readable
result file); `bench.py` uses `run_section` for its drill-backed BENCH
sections.
"""
from __future__ import annotations

import dataclasses
import json
import os
import signal
import subprocess
import sys
import time
from collections import deque
from typing import Callable, Dict, Optional, Sequence

from ..monitor.journal import journal_event
from ..utils import get_logger

log = get_logger("kungfu.bench.runner")

# The child decides platform health and prints a sentinel: tpu => OK; CPU =>
# OK only when the operator EXPLICITLY requested cpu
# (KFT_PLATFORM/JAX_PLATFORMS=cpu).
PROBE_SRC = (
    "import os, jax, jax.numpy as jnp; "
    "want_cpu = (os.environ.get('KFT_PLATFORM') == 'cpu' "
    "or os.environ.get('JAX_PLATFORMS') == 'cpu'); "
    "want_cpu and jax.config.update('jax_platforms', 'cpu'); "
    "plat = jax.devices()[0].platform; "
    "x = float(jnp.sum(jnp.ones((8, 8)) * 31.0).block_until_ready()); "
    "ok = x == 1984.0 and (plat == 'tpu' or "
    "(plat == 'cpu' and want_cpu)); "
    "print('PROBE_OK' if ok else f'PROBE_FALLBACK {plat}')"
)


def _kill_tree(p: subprocess.Popen) -> None:
    """SIGKILL the probe/section session; never block past a short reap —
    an unkillable D-state child is abandoned rather than freezing the
    queue (run()'s post-kill communicate() once stalled the whole loop for
    18 minutes)."""
    try:
        os.killpg(p.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError, OSError):
        p.kill()
    try:
        p.wait(timeout=10)
    except subprocess.TimeoutExpired:  # pragma: no cover - unkillable child
        log.warning("child %d unkillable (abandoned)", p.pid)


PROBE_TIMEOUT_ENV = "KFT_BENCH_PROBE_TIMEOUT_S"
DEFAULT_PROBE_TIMEOUT_S = 90.0


def probe_timeout_s(default: float = DEFAULT_PROBE_TIMEOUT_S) -> float:
    """The probe's subprocess deadline: KFT_BENCH_PROBE_TIMEOUT_S, else
    `default`."""
    try:
        v = os.environ.get(PROBE_TIMEOUT_ENV, "")
        return max(1.0, float(v)) if v else default
    except ValueError:
        return default


def probe_backend_ex(timeout_s: Optional[float] = None,
                     env: Optional[Dict[str, str]] = None) -> Optional[Dict[str, object]]:
    """None when a trivial dispatch completes on an acceptable platform
    within `timeout_s` (None = KFT_BENCH_PROBE_TIMEOUT_S, default 90 s);
    else a diagnosis dict: `reason` (the headline), `cause` — "timeout"
    (deadline expired, whole process group SIGKILLed) vs "crash" vs
    "fallback" vs "no_sentinel", the distinction that makes a wedge
    diagnosable from the json alone — `exit` (returncode or "timeout"),
    and the probe's captured `stderr` tail: the detail the BENCH journal
    needs to say WHY `measured_this_run` went false instead of just that
    it did (ROADMAP item 6: two committed rounds shipped with a wedged
    probe and no recorded cause)."""
    if timeout_s is None:
        timeout_s = probe_timeout_s()
    full_env = dict(os.environ)
    if env:
        full_env.update(env)
    p = subprocess.Popen(
        [sys.executable, "-c", PROBE_SRC],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=full_env, start_new_session=True,
    )
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline and p.poll() is None:
        time.sleep(0.2)
    if p.poll() is None:
        # start_new_session above made the probe its own process group:
        # _kill_tree's killpg takes the whole tree down, grandchildren
        # (libtpu helpers) included, so the NEXT probe starts clean
        _kill_tree(p)
        return {"reason": f"probe timed out after {timeout_s:.0f}s "
                          "(backend wedged)",
                "cause": "timeout", "exit": "timeout", "stderr": ""}
    out = p.stdout.read() if p.stdout is not None else ""
    err = (p.stderr.read() if p.stderr is not None else "").strip()[-800:]
    if p.returncode != 0:
        return {"reason": f"probe exited {p.returncode}",
                "cause": "crash", "exit": p.returncode, "stderr": err}
    if "PROBE_OK" in out:
        return None
    if "PROBE_FALLBACK" in out:
        return {"reason": ("backend fell back to an unrequested platform "
                           f"({out.strip().split()[-1]})"),
                "cause": "fallback", "exit": p.returncode, "stderr": err}
    return {"reason": "probe printed no sentinel",
            "cause": "no_sentinel", "exit": p.returncode, "stderr": err}


def probe_backend(timeout_s: Optional[float] = None,
                  env: Optional[Dict[str, str]] = None) -> Optional[str]:
    """None when the backend answers; else the reason string (the
    compatibility wrapper over `probe_backend_ex`)."""
    diag = probe_backend_ex(timeout_s, env=env)
    return None if diag is None else str(diag["reason"])


# env vars a wedged attempt can leave poisoned; the fresh-env retry strips
# them so a stale XLA/libtpu override cannot wedge every later probe too
_PROBE_SCRUB_VARS = ("XLA_FLAGS", "LIBTPU_INIT_ARGS", "TPU_LIBRARY_PATH")


def fresh_probe_env(env: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """A scrubbed copy of the section env for the probe's second chance:
    XLA/libtpu overrides dropped (even section-provided ones — they are
    the usual poison), the section's requested platform kept."""
    out = dict(env or {})
    for k in _PROBE_SCRUB_VARS:
        out[k] = ""  # "" overrides any inherited value in the child env
    return out


@dataclasses.dataclass
class Section:
    """One bench section the runner can probe-gate and retry.

    Either `fn` (returns the record dict, or None = failed) or `argv` (a
    subprocess; its record is read from `out_json` after a zero exit, else
    parsed from the last JSON line of stdout)."""

    name: str
    argv: Optional[Sequence[str]] = None
    fn: Optional[Callable[[], Optional[dict]]] = None
    out_json: str = ""
    timeout_s: float = 600.0
    env: Optional[Dict[str, str]] = None  # extra env for argv AND its probe
    cwd: str = ""


def _execute(section: Section) -> Optional[dict]:
    """Run one section once; returns its record or raises on failure."""
    if section.fn is not None:
        return section.fn()
    assert section.argv is not None, f"section {section.name}: no fn or argv"
    env = dict(os.environ)
    if section.env:
        env.update(section.env)
    p = subprocess.Popen(
        list(section.argv), stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=env, cwd=section.cwd or None, start_new_session=True,
    )
    try:
        out, _ = p.communicate(timeout=section.timeout_s)
    except subprocess.TimeoutExpired:
        _kill_tree(p)
        raise RuntimeError(f"timed out after {section.timeout_s:.0f}s") from None
    if p.returncode != 0:
        tail = (out or "").strip()[-400:]
        raise RuntimeError(f"exited {p.returncode}: {tail}")
    if section.out_json:
        with open(section.out_json) as f:
            return json.load(f)
    for line in reversed((out or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    raise RuntimeError("no JSON record in section output")


def _normalize_probe(result) -> Optional[Dict[str, object]]:
    """None | reason-string | diagnosis-dict -> None | diagnosis-dict."""
    if result is None:
        return None
    return result if isinstance(result, dict) else {"reason": str(result)}


def run_sections(sections: Sequence[Section],
                 probe_timeout_s: Optional[float] = None,
                 retries: int = 2, interval_s: float = 5.0,
                 probe: Callable[..., object] = probe_backend_ex,
                 sleep: Callable[[float], None] = time.sleep) -> Dict[str, dict]:
    """Probe-gated queue over `sections`; every record is stamped with an
    honest `measured_this_run`.

    Each pop probes the backend first (with the section's env, so CPU-only
    drills never block on a wedged chip).  A failing probe gets ONE
    immediate second chance with a fresh subprocess env
    (`fresh_probe_env`: inherited XLA/libtpu overrides scrubbed) — a
    poisoned env from a wedged attempt must not fail every later probe
    too; recovery journals `bench_probe_recovered` and the section runs.
    A probe that fails both ways journals `bench_probe_failed` WITH the
    captured stderr tail and exit cause (the ROADMAP-6 diagnosis:
    `measured_this_run: false` now says why).  Failed probes/sections move
    to the BACK of the queue (`bench_requeued`) — the backend gets
    `interval_s` to recover while other sections take their turn — until
    the attempt budget (`retries` + 1) is spent, at which point the
    section records `measured_this_run: False` with the last error
    (`bench_section_failed`) instead of silently vanishing from the BENCH
    json."""
    queue = deque(sections)
    attempts: Dict[str, int] = {}
    results: Dict[str, dict] = {}
    while queue:
        s = queue.popleft()
        attempts[s.name] = attempts.get(s.name, 0) + 1
        fail: Optional[str] = None
        rec: Optional[dict] = None
        diag = _normalize_probe(probe(probe_timeout_s, env=s.env))
        if diag is not None:
            retry_diag = _normalize_probe(
                probe(probe_timeout_s, env=fresh_probe_env(s.env)))
            if retry_diag is None:
                journal_event("bench_probe_recovered", section=s.name,
                              attempt=attempts[s.name],
                              error=diag.get("reason"),
                              cause=diag.get("cause"),
                              exit=diag.get("exit"),
                              stderr=diag.get("stderr"))
                log.warning("section %s: probe recovered on a fresh env "
                            "(first failure: %s)", s.name, diag.get("reason"))
                diag = None
        if diag is not None:
            fail = f"probe: {diag.get('reason')}"
            journal_event("bench_probe_failed", section=s.name,
                          attempt=attempts[s.name], error=diag.get("reason"),
                          cause=diag.get("cause"),
                          exit=diag.get("exit"), stderr=diag.get("stderr"),
                          retried=True,
                          retry_error=retry_diag.get("reason"),
                          retry_cause=retry_diag.get("cause"))
            log.warning("section %s: %s", s.name, fail)
        else:
            try:
                rec = _execute(s)
                if rec is None:
                    fail = "section returned no record"
            except Exception as e:  # noqa: BLE001 - requeued, never fatal
                fail = f"{type(e).__name__}: {e}"
        if rec is not None and fail is None:
            rec = dict(rec)
            rec["measured_this_run"] = True
            results[s.name] = rec
            continue
        if attempts[s.name] <= retries:
            journal_event("bench_requeued", section=s.name,
                          attempt=attempts[s.name], error=fail)
            queue.append(s)
            sleep(interval_s)
        else:
            journal_event("bench_section_failed", section=s.name,
                          attempts=attempts[s.name], error=fail)
            log.error("section %s failed for good: %s", s.name, fail)
            results[s.name] = {"measured_this_run": False, "error": fail}
    return results


def run_section(section: Section, **kw) -> dict:
    """One-section convenience wrapper around `run_sections`."""
    return run_sections([section], **kw)[section.name]


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="kungfu_tpu.benchmarks.runner")
    ap.add_argument("--queue", required=True,
                    help="file with one shell command per line (#/blank "
                         "skipped); each must print a JSON record line")
    ap.add_argument("--out", default="", help="write {section: record} here")
    ap.add_argument("--probe-timeout", type=float, default=None,
                    help="probe subprocess deadline in seconds (default: "
                         "KFT_BENCH_PROBE_TIMEOUT_S, else 90)")
    ap.add_argument("--job-timeout", type=float, default=1800.0)
    ap.add_argument("--retries", type=int, default=3)
    ap.add_argument("--interval", type=float, default=120.0,
                    help="seconds between attempts while the backend is down")
    args = ap.parse_args(argv)

    with open(args.queue) as f:
        cmds = [ln.strip() for ln in f
                if ln.strip() and not ln.strip().startswith("#")]
    sections = [
        Section(name=f"job{i}: {cmd[:60]}", argv=["/bin/sh", "-c", cmd],
                timeout_s=args.job_timeout)
        for i, cmd in enumerate(cmds)
    ]
    results = run_sections(sections, probe_timeout_s=args.probe_timeout,
                           retries=args.retries, interval_s=args.interval)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
    measured = sum(1 for r in results.values() if r.get("measured_this_run"))
    print(f"# runner: {measured}/{len(results)} sections measured this run",
          flush=True)
    return 0 if measured == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
