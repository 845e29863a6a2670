"""Jaxpr walking: collective extraction + vma-style replication tracking.

The walker descends a ClosedJaxpr — through `shard_map`, `pjit`, `cond`,
`scan`, `while`, `remat`, and `custom_*` call sub-jaxprs — and produces a
flat `Extraction`:

  collectives   every collective equation (psum/pmin/pmax/ppermute/
                all_gather/all_to_all/reduce_scatter/axis_index) with its
                named axes, operand dtype/size, static permutation, nesting
                path and the replication state of its operand;
  cond_sites    every `lax.cond`/`lax.switch` with the replication of its
                predicate and each branch's ordered collective signature —
                the input of the divergent-collective deadlock rule;
  leaks         shard_map outputs whose computed value is device-varying
                over axes the out_specs claim replicated — the vma-style
                unreduced-gradient signal (the check the repo's
                `check_vma=False` call sites opt out of at trace time);
  axis_sizes    mesh axis sizes seen while walking (from shard_map eqns).

Replication tracking is the classic abstract interpretation: a value's
abstract state is the set of mesh axes it may *vary over*.  Sharded
shard_map inputs vary over their sharding axes; `psum`/`pmin`/`pmax`/
`all_gather` over an axis erase that axis; `axis_index`, `reduce_scatter`,
`all_to_all` (and partial `ppermute`s) introduce it; everything else unions
its inputs.  `scan`/`while` carries run to fixpoint.  The lattice is tiny
(subsets of mesh axes), so the fixpoint converges in at most |axes| passes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, FrozenSet, List, Optional, Sequence, Tuple

from jax._src import source_info_util as _src_info
from jax.extend import core

#: primitives that move bytes between devices (collective wire ops)
WIRE_PRIMS = ("psum", "pmin", "pmax", "ppermute", "all_gather", "all_to_all",
              "reduce_scatter")

#: reduction-class primitives: output no longer varies over the reduced axis
_ERASING = ("psum", "pmin", "pmax", "all_gather")


@dataclasses.dataclass(frozen=True)
class Collective:
    """One collective equation, flattened out of its nesting context."""

    prim: str
    axes: Tuple[str, ...]
    dtype: str
    size: int                       # operand element count (per-device view)
    path: Tuple[str, ...]
    varying: FrozenSet[str]         # vma of the operand
    perm: Optional[Tuple[Tuple[int, int], ...]] = None   # ppermute only
    source: str = ""

    def signature(self) -> Tuple[str, Tuple[str, ...]]:
        return (self.prim, self.axes)


@dataclasses.dataclass(frozen=True)
class CondSite:
    """A cond/switch: predicate replication + per-branch collective sigs."""

    path: Tuple[str, ...]
    pred_varying: FrozenSet[str]
    branch_signatures: Tuple[Tuple[Tuple[str, Tuple[str, ...]], ...], ...]
    source: str = ""

    @property
    def has_collectives(self) -> bool:
        return any(self.branch_signatures)

    @property
    def divergent(self) -> bool:
        return len(set(self.branch_signatures)) > 1


@dataclasses.dataclass(frozen=True)
class OutputLeak:
    """A shard_map output claimed replicated over axes it varies over."""

    out_index: int
    axes: Tuple[str, ...]           # the leaked (varying-but-claimed) axes
    path: Tuple[str, ...]
    source: str = ""


@dataclasses.dataclass
class Extraction:
    collectives: List[Collective] = dataclasses.field(default_factory=list)
    cond_sites: List[CondSite] = dataclasses.field(default_factory=list)
    leaks: List[OutputLeak] = dataclasses.field(default_factory=list)
    axis_sizes: Dict[str, int] = dataclasses.field(default_factory=dict)

    def reduced_axes(self) -> FrozenSet[str]:
        """Axes some reduction-class collective erases somewhere in the
        program (used to grade replication leaks: a leak over an axis the
        program never reduces over is a missing psum, not bookkeeping)."""
        out: set = set()
        for c in self.collectives:
            if c.prim in _ERASING:
                out.update(c.axes)
        return frozenset(out)


def _named(axes) -> Tuple[str, ...]:
    """Filter a primitive's axes param to named (string) mesh axes."""
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _spec_axes(spec) -> FrozenSet[str]:
    """The named mesh axes a PartitionSpec shards over."""
    axes: set = set()
    for entry in spec:
        axes.update(_named(entry))
    return frozenset(axes)


def _source_of(eqn) -> str:
    return _src_info.summarize(eqn.source_info)


def _is_total_permutation(perm, n: Optional[int]) -> bool:
    if n is None:
        return False
    src = {p[0] for p in perm}
    dst = {p[1] for p in perm}
    return len(perm) == n and len(src) == n and len(dst) == n


def _sub_jaxprs(params) -> List[Tuple[str, Any]]:
    """All (param_name, Jaxpr) sub-jaxprs of an equation's params."""
    out = []
    for k, v in params.items():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for item in items:
            if isinstance(item, core.ClosedJaxpr):
                out.append((k, item.jaxpr))
            elif isinstance(item, core.Jaxpr):
                out.append((k, item))
    return out


class _Walker:
    def __init__(self, extraction: Extraction, record: bool = True):
        self.x = extraction
        self.record = record

    # -- environment helpers ----------------------------------------------------------

    @staticmethod
    def _read(env, var) -> FrozenSet[str]:
        if isinstance(var, core.Literal):
            return frozenset()
        return env.get(var, frozenset())

    def _in_vma(self, env, eqn) -> FrozenSet[str]:
        out: FrozenSet[str] = frozenset()
        for v in eqn.invars:
            out |= self._read(env, v)
        return out

    # -- main propagation -------------------------------------------------------------

    def run(self, jaxpr: core.Jaxpr, in_vmas: Sequence[FrozenSet[str]],
            path: Tuple[str, ...]) -> List[FrozenSet[str]]:
        """Propagate vma through `jaxpr`; returns each output's vma."""
        env: Dict[Any, FrozenSet[str]] = {}
        for var in jaxpr.constvars:
            env[var] = frozenset()
        for var, vma in zip(jaxpr.invars, in_vmas):
            env[var] = vma
        for eqn in jaxpr.eqns:
            outs = self._eqn(env, eqn, path)
            for var, vma in zip(eqn.outvars, outs):
                env[var] = vma
        return [self._read(env, v) for v in jaxpr.outvars]

    def _eqn(self, env, eqn, path) -> List[FrozenSet[str]]:
        name = eqn.primitive.name
        handler = getattr(self, f"_h_{name.replace('-', '_')}", None)
        if handler is not None:
            return handler(env, eqn, path)
        if name in WIRE_PRIMS or name == "axis_index":
            return self._h_collective(env, eqn, path)
        subs = _sub_jaxprs(eqn.params)
        if subs:
            return self._h_generic_call(env, eqn, path, subs)
        vma = self._in_vma(env, eqn)
        return [vma for _ in eqn.outvars]

    # -- collectives ------------------------------------------------------------------

    def _record_collective(self, env, eqn, path, axes, perm=None, prim=None):
        if not self.record or not axes:
            return
        aval = eqn.invars[0].aval if eqn.invars else None
        shape = tuple(getattr(aval, "shape", ()) or ())
        size = 1
        for d in shape:
            size *= int(d)
        dtype = str(getattr(aval, "dtype", ""))
        self.x.collectives.append(Collective(
            prim=prim or eqn.primitive.name, axes=tuple(axes), dtype=dtype,
            size=size,
            path=path, varying=self._in_vma(env, eqn),
            perm=tuple(tuple(p) for p in perm) if perm is not None else None,
            source=_source_of(eqn),
        ))

    def _h_collective(self, env, eqn, path) -> List[FrozenSet[str]]:
        name = eqn.primitive.name
        p = eqn.params
        vma = self._in_vma(env, eqn)
        if name == "axis_index":
            axes = _named(p.get("axis_name"))
            return [vma | set(axes)]
        if name == "ppermute":
            axes = _named(p.get("axis_name"))
            perm = tuple(p.get("perm", ()))
            self._record_collective(env, eqn, path, axes, perm=perm)
            n = self.x.axis_sizes.get(axes[0]) if axes else None
            if _is_total_permutation(perm, n):
                return [vma]          # total rotation of a replicated value
            return [vma | set(axes)]  # partial perms leave holes per-device
        if name in ("psum", "pmin", "pmax"):
            axes = _named(p.get("axes"))
            self._record_collective(env, eqn, path, axes)
            out = vma - set(axes)
            return [out for _ in eqn.outvars]
        if name == "all_gather":
            axes = _named(p.get("axis_name"))
            self._record_collective(env, eqn, path, axes)
            return [vma - set(axes)]
        if name in ("reduce_scatter", "all_to_all"):
            axes = _named(p.get("axis_name"))
            self._record_collective(env, eqn, path, axes)
            return [vma | set(axes)]
        return [vma for _ in eqn.outvars]  # pragma: no cover - unreachable

    # shard_map's check-rep machinery (jax 0.4's check_rep=True default,
    # 0.6's check_vma) rewrites psum into a psum2/psum_invariant primitive
    # and inserts pbroadcast/pvary casts.  psum2 is still a wire reduction
    # (record it under the canonical "psum" name so rule signatures match
    # the unrewritten form); pbroadcast/pvary only re-tag a replicated
    # value as varying — the content is identical on every device, so for
    # content-variance tracking they are the identity and not collectives.

    def _h_psum2(self, env, eqn, path) -> List[FrozenSet[str]]:
        axes = _named(eqn.params.get("axes"))
        self._record_collective(env, eqn, path, axes, prim="psum")
        vma = self._in_vma(env, eqn)
        out = vma - set(axes)
        return [out for _ in eqn.outvars]

    _h_psum_invariant = _h_psum2

    def _h_pbroadcast(self, env, eqn, path) -> List[FrozenSet[str]]:
        vma = self._in_vma(env, eqn)
        return [vma for _ in eqn.outvars]

    _h_pvary = _h_pbroadcast

    # -- structured control flow ------------------------------------------------------

    def _h_shard_map(self, env, eqn, path) -> List[FrozenSet[str]]:
        p = eqn.params
        mesh = p.get("mesh")
        if mesh is not None:
            try:
                self.x.axis_sizes.update(
                    {str(a): int(s) for a, s in dict(mesh.shape).items()}
                )
            except Exception:  # pragma: no cover - abstract/mocked meshes
                pass
        inner = p["jaxpr"]
        inner = inner.jaxpr if isinstance(inner, core.ClosedJaxpr) else inner
        in_vmas = [_spec_axes(spec) for spec in p["in_specs"]]
        sub_path = path + ("shard_map",)
        out_vmas = self.run(inner, in_vmas, sub_path)
        if self.record:
            for i, (vma, spec) in enumerate(zip(out_vmas, p["out_specs"])):
                leaked = vma - _spec_axes(spec)
                if leaked:
                    self.x.leaks.append(OutputLeak(
                        out_index=i, axes=tuple(sorted(leaked)),
                        path=sub_path, source=_source_of(eqn),
                    ))
        # outside the shard_map the results are global arrays again
        return [frozenset() for _ in eqn.outvars]

    def _h_cond(self, env, eqn, path) -> List[FrozenSet[str]]:
        p = eqn.params
        branches = [b.jaxpr if isinstance(b, core.ClosedJaxpr) else b
                    for b in p.get("branches", ())]
        pred_vma = self._read(env, eqn.invars[0])
        op_vmas = [self._read(env, v) for v in eqn.invars[1:]]
        n_out = len(eqn.outvars)
        outs = [frozenset() for _ in range(n_out)]
        sigs = []
        for bi, branch in enumerate(branches):
            sub_path = path + (f"cond:branch{bi}",)
            mark = len(self.x.collectives)
            b_outs = self.run(branch, op_vmas[: len(branch.invars)], sub_path)
            sigs.append(tuple(
                c.signature() for c in self.x.collectives[mark:]
            ))
            outs = [o | b for o, b in zip(outs, b_outs)]
        outs = [o | pred_vma for o in outs]
        if self.record and branches:
            self.x.cond_sites.append(CondSite(
                path=path, pred_varying=pred_vma,
                branch_signatures=tuple(sigs), source=_source_of(eqn),
            ))
        return outs

    def _h_scan(self, env, eqn, path) -> List[FrozenSet[str]]:
        p = eqn.params
        body = p["jaxpr"]
        body = body.jaxpr if isinstance(body, core.ClosedJaxpr) else body
        n_consts = int(p.get("num_consts", 0))
        n_carry = int(p.get("num_carry", 0))
        in_vmas = [self._read(env, v) for v in eqn.invars]
        consts, carry, xs = (in_vmas[:n_consts],
                             in_vmas[n_consts:n_consts + n_carry],
                             in_vmas[n_consts + n_carry:])
        sub_path = path + ("scan:body",)
        carry, body_outs = self._fixpoint(body, consts, carry, xs, sub_path,
                                          n_carry)
        return carry + body_outs[n_carry:]

    def _h_while(self, env, eqn, path) -> List[FrozenSet[str]]:
        p = eqn.params
        cond_j = p["cond_jaxpr"]
        cond_j = cond_j.jaxpr if isinstance(cond_j, core.ClosedJaxpr) else cond_j
        body_j = p["body_jaxpr"]
        body_j = body_j.jaxpr if isinstance(body_j, core.ClosedJaxpr) else body_j
        cn = int(p.get("cond_nconsts", 0))
        bn = int(p.get("body_nconsts", 0))
        in_vmas = [self._read(env, v) for v in eqn.invars]
        cconsts, bconsts, carry = in_vmas[:cn], in_vmas[cn:cn + bn], in_vmas[cn + bn:]
        sub_path = path + ("while:body",)
        carry, _ = self._fixpoint(body_j, bconsts, carry, [], sub_path,
                                  len(carry))
        quiet = _Walker(self.x, record=self.record)
        quiet.run(cond_j, cconsts + carry, path + ("while:cond",))
        return carry

    def _fixpoint(self, body, consts, carry, xs, path, n_carry):
        """Run a loop body to vma fixpoint; record on the final pass only."""
        for _ in range(len(self.x.axis_sizes) + 2):
            warm = _Walker(self.x, record=False)
            outs = warm.run(body, list(consts) + list(carry) + list(xs), path)
            new_carry = [c | o for c, o in zip(carry, outs[:n_carry])]
            if new_carry == carry:
                break
            carry = new_carry
        outs = self.run(body, list(consts) + list(carry) + list(xs), path)
        return [c | o for c, o in zip(carry, outs[:n_carry])], outs

    def _h_pjit(self, env, eqn, path) -> List[FrozenSet[str]]:
        body = eqn.params["jaxpr"]
        body = body.jaxpr if isinstance(body, core.ClosedJaxpr) else body
        in_vmas = [self._read(env, v) for v in eqn.invars]
        label = eqn.params.get("name") or "pjit"
        return self.run(body, in_vmas, path + (f"pjit:{label}",))

    def _h_remat2(self, env, eqn, path) -> List[FrozenSet[str]]:
        body = eqn.params["jaxpr"]
        body = body.jaxpr if isinstance(body, core.ClosedJaxpr) else body
        in_vmas = [self._read(env, v) for v in eqn.invars]
        return self.run(body, in_vmas, path + ("remat",))

    def _h_closed_call(self, env, eqn, path) -> List[FrozenSet[str]]:
        body = eqn.params.get("call_jaxpr") or eqn.params.get("jaxpr")
        body = body.jaxpr if isinstance(body, core.ClosedJaxpr) else body
        in_vmas = [self._read(env, v) for v in eqn.invars]
        return self.run(body, in_vmas, path + ("call",))

    def _h_generic_call(self, env, eqn, path, subs) -> List[FrozenSet[str]]:
        """Unknown higher-order primitive (custom_vjp/jvp, future prims):
        walk every sub-jaxpr conservatively — positional vma mapping when
        arities line up (trailing-aligned to skip leading consts), else the
        union of all inputs for every sub-input."""
        in_vmas = [self._read(env, v) for v in eqn.invars]
        union = frozenset().union(*in_vmas) if in_vmas else frozenset()
        out_union: FrozenSet[str] = frozenset()
        n_out = len(eqn.outvars)
        outs: Optional[List[FrozenSet[str]]] = None
        for pname, sub in subs:
            k = len(sub.invars)
            if k and k <= len(in_vmas):
                sub_in = in_vmas[-k:]
            else:
                sub_in = [union] * k
            sub_outs = self.run(sub, sub_in, path + (f"{eqn.primitive.name}:{pname}",))
            out_union |= frozenset().union(*sub_outs) if sub_outs else frozenset()
            if len(sub_outs) == n_out:
                outs = (sub_outs if outs is None
                        else [a | b for a, b in zip(outs, sub_outs)])
        if outs is not None:
            return outs
        return [union | out_union for _ in eqn.outvars]


def extract(closed_jaxpr: core.ClosedJaxpr,
            axis_sizes: Optional[Dict[str, int]] = None) -> Extraction:
    """Walk a ClosedJaxpr and return the flat Extraction.

    `axis_sizes` seeds known mesh axes (e.g. from an explicit mesh) for
    programs whose collectives sit outside any shard_map equation; the
    walker adds every shard_map mesh it encounters.
    """
    x = Extraction(axis_sizes=dict(axis_sizes or {}))
    jaxpr = closed_jaxpr.jaxpr
    walker = _Walker(x)
    # top level is the global (non-manual) context: nothing varies yet
    walker.run(jaxpr, [frozenset() for _ in jaxpr.invars], ())
    return x
