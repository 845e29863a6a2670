"""kf-verify CLI: `python -m kungfu_tpu.analysis`.

Default run lints the built-in jaxpr corpus (shipped optimizers, session
strategies, parallel schedules, example/benchmark train steps) and exits
0 iff no error-severity finding fires.  The other stages:

  --schedules          verify the built-in chunk-level schedule corpus
                       (ring/tree/hierarchical/fused at several sizes):
                       dataflow, slot races, deadlock freedom.
  --hostlint [PATH..]  AST lint of the control plane (bare PUTs, journal
                       kinds, lock order, thread lifecycle, wall-clock
                       durations) + the EVENT_KINDS<->docs cross-check.
  --env                KFT_* env vars in code vs the docs tables.
  --all                everything above plus the jaxpr corpus — the CI
                       gate (scripts/check.sh runs it).
  --module pkg.mod     lint a module's declared `PROGRAMS` and verify its
                       `SCHEDULES` (kungfu_tpu.testing.bad_programs is
                       the canonical non-zero run).

Analysis is pure tracing, so the CLI pins the CPU backend with 8 virtual
devices (conftest-style) unless the caller already forced a platform.
"""
from __future__ import annotations

import argparse
import importlib
import os
import sys
import time
from typing import List


def _setup_backend() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def _load_module(dotted: str):
    mod = importlib.import_module(dotted)
    progs = getattr(mod, "PROGRAMS", None)
    scheds = getattr(mod, "SCHEDULES", None)
    if progs is None and scheds is None:
        raise SystemExit(
            f"module {dotted!r} declares neither PROGRAMS nor SCHEDULES"
        )
    return list(progs or []), list(scheds or [])


def _report(name: str, findings, ms: float, verbose: bool,
            fmt) -> int:
    from .findings import ERROR

    errs = [f for f in findings if f.severity == ERROR]
    rest = [f for f in findings if f.severity != ERROR]
    status = "FAIL" if errs else "ok"
    print(f"{status:5s} {name}  ({ms:.0f} ms, "
          f"{len(errs)} errors, {len(rest)} warnings)")
    shown = errs + (rest if verbose else [])
    if shown:
        for line in fmt(shown).splitlines():
            print(f"      {line}")
    return len(errs)


def _run_schedules(schedules, suppress, verbose, fmt) -> int:
    from .schedule import verify_schedule

    n_err = 0
    for s in schedules:
        t0 = time.perf_counter()
        findings = [f for f in verify_schedule(s)
                    if f.rule not in suppress]
        ms = (time.perf_counter() - t0) * 1e3
        label = f"{s.name} (n={s.world}, {len(s.rounds)} rounds)"
        n_err += _report(label, findings, ms, verbose, fmt)
    return n_err


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m kungfu_tpu.analysis",
        description="kf-verify: static analysis of collective programs, "
                    "schedules, and the control plane",
    )
    ap.add_argument("--module", default=None,
                    help="lint a module's PROGRAMS/SCHEDULES instead of "
                         "the built-in corpus")
    ap.add_argument("--program", action="append", default=None,
                    help="restrict to named program(s)")
    ap.add_argument("--tag", action="append", default=None,
                    help="restrict to programs carrying a tag "
                         "(optimizer, session, parallel, example, bench, "
                         "compression)")
    ap.add_argument("--schedules", action="store_true",
                    help="verify the built-in schedule corpus")
    ap.add_argument("--hostlint", nargs="*", metavar="PATH", default=None,
                    help="AST-lint host code (default: all of kungfu_tpu/)")
    ap.add_argument("--env", action="store_true",
                    help="audit KFT_* env vars against the docs tables")
    ap.add_argument("--all", action="store_true",
                    help="jaxpr corpus + schedules + hostlint + env audit")
    ap.add_argument("--suppress", action="append", default=[],
                    help="rule id(s) to skip")
    ap.add_argument("--list", action="store_true", help="list programs")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="print warnings/info findings too")
    args = ap.parse_args(argv)

    from .findings import EVERY_RULE

    unknown = [r for r in args.suppress if r not in EVERY_RULE]
    if unknown:
        raise SystemExit(f"unknown rule id(s): {unknown} "
                         f"(known: {list(EVERY_RULE)})")
    suppress = tuple(args.suppress)

    run_programs = bool(args.all or args.module
                        or not (args.schedules or args.env
                                or args.hostlint is not None))
    run_schedules = bool(args.all or args.schedules or args.module)
    run_hostlint = bool(args.all or args.hostlint is not None)
    run_env = bool(args.all or args.env)

    n_err = n_warn = n_skip = n_units = 0

    # host-side stages need no jax backend; run them first
    from . import format_findings
    from .findings import ERROR

    if run_hostlint:
        from .hostlint import hostlint_findings, lint_paths

        t0 = time.perf_counter()
        if args.hostlint:  # explicit path list: no docs cross-check
            findings = lint_paths(paths=args.hostlint,
                                  root=os.getcwd())
        else:
            findings = hostlint_findings()
        findings = [f for f in findings if f.rule not in suppress]
        ms = (time.perf_counter() - t0) * 1e3
        n_units += 1
        errs = _report("hostlint", findings, ms, args.verbose,
                       format_findings)
        n_err += errs
        n_warn += sum(1 for f in findings if f.severity != ERROR)

    if run_env:
        from .envaudit import code_env, env_findings

        t0 = time.perf_counter()
        findings = [f for f in env_findings() if f.rule not in suppress]
        ms = (time.perf_counter() - t0) * 1e3
        n_units += 1
        n_err += _report("env-audit", findings, ms, args.verbose,
                         format_findings)
        # ROADMAP D5: the count only falls (tests/unit/test_analysis.py
        # holds the ceiling)
        print(f"      {len(code_env())} KFT_* names read in code")

    programs: List = []
    schedules: List = []
    if args.module:
        programs, schedules = _load_module(args.module)
    else:
        if run_schedules:
            from .schedule import builtin_schedules

            schedules = builtin_schedules()

    if run_schedules:
        n_units += len(schedules)
        n_err += _run_schedules(schedules, suppress, args.verbose,
                                format_findings)

    if run_programs:
        _setup_backend()
        from .programs import (ProgramUnavailable, builtin_programs,
                               check_program)

        if not args.module:
            programs = builtin_programs()
        if args.program:
            wanted = set(args.program)
            programs = [p for p in programs if p.name in wanted]
            missing = wanted - {p.name for p in programs}
            if missing:
                raise SystemExit(f"unknown program(s): {sorted(missing)}")
        if args.tag:
            tags = set(args.tag)
            programs = [p for p in programs if tags & set(p.tags)]
        if args.list:
            for p in programs:
                print(f"{p.name:32s} [{','.join(p.tags)}] {p.description}")
            return 0
        if not programs and not (schedules or run_hostlint or run_env):
            raise SystemExit("no programs selected")

        for p in programs:
            t0 = time.perf_counter()
            try:
                findings = check_program(p, suppress=suppress)
            except ProgramUnavailable as e:
                n_skip += 1
                print(f"SKIP  {p.name}: {e}")
                continue
            ms = (time.perf_counter() - t0) * 1e3
            n_units += 1
            n_err += _report(p.name, findings, ms, args.verbose,
                             format_findings)
            n_warn += sum(1 for f in findings if f.severity != ERROR)

    print(f"kf-verify: {n_units} checks, {n_err} errors, "
          f"{n_warn} warnings, {n_skip} skipped")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
