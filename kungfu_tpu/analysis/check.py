"""kf-lint entry points: trace a collective program and run the rules.

`check(fn, *args, mesh=..., compression=...)` is the library API: it traces
`fn` to a ClosedJaxpr (pure tracing — `jax.make_jaxpr` on arrays or
ShapeDtypeStructs, no device execution, no compilation), walks it with
extract.py and runs rules.py, returning structured Findings.  Trace-time
failures that *are* the defect being hunted (an unbound axis name, a
replication check the newer shard_map performs itself) are converted into
the corresponding Finding instead of escaping as raw exceptions, so callers
get one uniform report either way.

`check_axes_in_scope` is the lightweight in-trace hook the optimizer
transforms use: called while an outer shard_map/pjit trace is live, it
verifies the transform's declared axes actually exist in the surrounding
mesh scope and that per-axis compression keys name real axes — the two
mistakes that otherwise surface as a hung TPU program minutes later.
"""
from __future__ import annotations

import re
from typing import Any, List, Optional, Sequence, Tuple

import jax

from ..compression.config import AxisCompression
from .extract import Extraction, extract
from .findings import (
    ERROR,
    AnalysisError,
    Finding,
    RULE_AXIS,
    RULE_REPLICATION,
    errors,
)
from .rules import run_rules

_UNBOUND = re.compile(r"unbound axis name: (.*)$")


def abstractify(tree: Any) -> Any:
    """Pytree of arrays/values -> pytree of ShapeDtypeStructs (trace inputs)."""
    import numpy as np

    def one(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        a = np.asarray(x) if not isinstance(x, jax.Array) else x
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    return jax.tree.map(one, tree)


def _known_axes(mesh, axis_sizes) -> Tuple[Tuple[str, ...], dict]:
    sizes = dict(axis_sizes or {})
    names: Tuple[str, ...] = tuple(sizes)
    if mesh is not None:
        names = tuple(dict.fromkeys(tuple(mesh.axis_names) + names))
        try:
            sizes.update({str(a): int(s) for a, s in dict(mesh.shape).items()})
        except Exception:  # pragma: no cover - exotic mesh stand-ins
            pass
    return names, sizes


def _trace_failure_finding(e: Exception, known: Sequence[str]) -> Optional[Finding]:
    """Map a known trace-time failure class onto its Finding."""
    msg = str(e)
    if isinstance(e, NameError):
        m = _UNBOUND.search(msg)
        bad = (m.group(1),) if m else ()
        shown = repr(bad[0]) if bad else repr(msg)
        return Finding(
            rule=RULE_AXIS, severity=ERROR, axes=bad,
            message=(f"collective references axis {shown} which is not "
                     f"bound by any mesh in scope; declared axes: "
                     f"{sorted(known)}"),
        )
    if isinstance(e, ValueError) and "replication" in msg:
        # newer shard_map's own check_rep/check_vma tripping during trace
        return Finding(
            rule=RULE_REPLICATION, severity=ERROR,
            message=f"shard_map replication check failed at trace time: {msg}",
        )
    return None


def check(
    fn,
    *args,
    mesh=None,
    compression: AxisCompression = None,
    axis_sizes: Optional[dict] = None,
    suppress: Sequence[str] = (),
    **kwargs,
) -> List[Finding]:
    """Statically analyze one collective program.

    Args:
      fn: the program — plain, jitted, or shard_map'd; traced, never run.
      *args / **kwargs: example inputs (arrays or ShapeDtypeStructs).
      mesh: the declared Mesh (axis names + sizes) the program must agree
        with; optional when fn contains its own shard_map (the walker reads
        the mesh off the equation), but explicit is stricter.
      compression: the CompressionConfig / registered name / {axis: config}
        dict the program is deployed with — drives the wire-dtype rule.
      axis_sizes: extra {axis: size} declarations (e.g. pmap axes).
      suppress: rule ids to skip (see findings.ALL_RULES).

    Returns structured Findings, worst first.  Never raises for defects the
    rules cover — use `assert_clean` (or the `analyze=` hooks) to escalate
    error findings into an AnalysisError.
    """
    known, sizes = _known_axes(mesh, axis_sizes)
    try:
        closed = jax.make_jaxpr(fn)(*args, **kwargs)
    except (NameError, ValueError) as e:
        f = _trace_failure_finding(e, known)
        if f is None:
            raise
        extraction = Extraction(axis_sizes=sizes)
        found = [] if f.rule in suppress else [f]
        found += run_rules(extraction, known, compression, suppress)
        return _sorted(found)
    extraction = extract(closed, axis_sizes=sizes)
    return _sorted(run_rules(extraction, known, compression, suppress))


_ORDER = {"error": 0, "warning": 1, "info": 2}


def _sorted(findings: List[Finding]) -> List[Finding]:
    return sorted(findings, key=lambda f: (_ORDER.get(f.severity, 3), f.rule))


def assert_clean(findings: Sequence[Finding], context: str = "") -> None:
    """Raise AnalysisError if any error-severity finding is present."""
    errs = errors(findings)
    if errs:
        raise AnalysisError(errs, context=context)


def check_and_raise(fn, *args, context: str = "", **kwargs) -> List[Finding]:
    """check() + assert_clean() — the shape every trace-time hook wants."""
    findings = check(fn, *args, **kwargs)
    assert_clean(findings, context=context)
    return findings


def _axis_env_sizes() -> dict:
    """{axis: size} for the axes bound by the surrounding trace."""
    from jax._src import core as _core

    return dict(_core.get_axis_env().axis_sizes)


def check_axes_in_scope(
    axis_name,
    compression: AxisCompression = None,
    context: str = "",
) -> None:
    """In-trace hook: verify declared axes are bound and compression keys
    name bound axes.  Must be called during an outer shard_map/pjit trace
    (exactly like lax.axis_index); raises AnalysisError on violations."""
    axes = axis_name if isinstance(axis_name, (tuple, list)) else (axis_name,)
    env = _axis_env_sizes()
    findings: List[Finding] = []
    in_scope = sorted(env)
    for a in axes:
        if a not in env:
            findings.append(Finding(
                rule=RULE_AXIS, severity=ERROR, axes=(a,),
                message=(f"axis {a!r} is not bound by the surrounding "
                         f"mesh; axes in scope: {in_scope}"),
            ))
    if isinstance(compression, dict):
        for k in compression:
            if k not in env:
                findings.append(Finding(
                    rule=RULE_AXIS, severity=ERROR, axes=(k,),
                    message=(f"compression key {k!r} names no axis in "
                             f"scope ({in_scope}); it would silently "
                             "stay full precision"),
                ))
    assert_clean(findings, context=context)


def check_collective_plan(graph_pairs, n: int,
                          what: str = "plan") -> List[Finding]:
    """Graph-level kf-lint for a collective plan.

    `graph_pairs` is the planner's (reduce_graph, bcast_graph) list (the
    strategy_graphs shape).  Every pair must describe a legal program:
    chain/ring rounds must be valid (partial) permutations — the same
    injectivity XLA's ppermute needs (rule 3) — and trees must be
    single-rooted, acyclic, and cover every rank, or the lowered collective
    silently drops ranks.  This is the validity oracle the plan compiler
    runs on every candidate before it may be installed.
    """
    from ..plan.graph import permutation_errors
    from .findings import RULE_PERMUTATION

    findings: List[Finding] = []

    def err(msg: str) -> None:
        findings.append(Finding(rule=RULE_PERMUTATION, severity=ERROR,
                                message=msg))

    for i, (reduce_g, bcast_g) in enumerate(graph_pairs):
        tag = f"{what}[{i}]" if len(graph_pairs) > 1 else what
        sized = True
        for g, role in ((reduce_g, "reduce"), (bcast_g, "bcast")):
            if len(g) != n:
                err(f"{tag} {role} graph spans {len(g)} ranks, plan world "
                    f"is {n}")
                sized = False
        if not sized:
            continue
        # the bcast orientation must be a covering tree: single root,
        # acyclic, every rank reachable (chains count — fanout 1)
        for problem in bcast_g.tree_errors():
            err(f"{tag} bcast tree: {problem}; edges={bcast_g.edges()}")
        # chain-shaped rounds (out-degree AND in-degree <= 1 everywhere,
        # i.e. a genuine ring/pipeline hop) execute as ppermutes: the send
        # pairs must satisfy the same injectivity XLA's ppermute needs.
        # Tree rounds legitimately fan in (many children -> one father)
        # and are covered by the tree check above instead.
        for g, role in ((reduce_g, "reduce"), (bcast_g, "bcast")):
            chain = all(len(g.nexts(r)) <= 1 and len(g.prevs(r)) <= 1
                        for r in range(n))
            if chain:
                for problem in permutation_errors(g.edges(), n):
                    err(f"{tag} {role} round: {problem}; edges={g.edges()}")
        # the pair must agree: reducing along G and broadcasting along
        # reverse(G) is the contract every strategy builder follows —
        # a mismatched pair deadlocks (one side waits on an edge the
        # other never drives)
        rev = {(b, a) for a, b in reduce_g.edges()}
        fwd = set(bcast_g.edges())
        if rev != fwd:
            err(f"{tag} reduce/bcast graphs disagree: reversed reduce "
                f"edges {sorted(rev)} != bcast edges {sorted(fwd)}")
    return _sorted(findings)


def check_elastic_permutations(build_perm, sizes: Sequence[int],
                               what: str = "ppermute") -> List[Finding]:
    """Validate a size-parametric permutation builder over every cluster
    size an elastic strategy can resize to (rule 3's elastic companion)."""
    from ..plan.graph import permutation_errors
    from .findings import RULE_PERMUTATION

    findings: List[Finding] = []
    for n in sizes:
        for problem in permutation_errors(list(build_perm(n)), n):
            findings.append(Finding(
                rule=RULE_PERMUTATION, severity=ERROR,
                message=f"{what} at size {n}: {problem}",
            ))
    return findings
