"""kf-lint (kungfu_tpu.analysis): the five rules on their seeded-bad
programs, silence on the shipped corpus, the shared bijection/config
validators, and the trace-time hooks.

The contract under test is ISSUE 2's acceptance bar: every seeded-bad
program in kungfu_tpu.testing.bad_programs produces EXACTLY its expected
finding, every shipped optimizer/session-strategy/schedule/example/bench
program analyzes clean, and the CLI exits 0 on the corpus / non-zero on
the bad module.
"""
import numpy as np
import pytest

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import optax

from kungfu_tpu import analysis
from kungfu_tpu.analysis import __main__ as cli
from kungfu_tpu.analysis.programs import (
    ProgramUnavailable,
    builtin_programs,
    check_program,
)
from jax import shard_map
from kungfu_tpu.plan.graph import permutation_errors, validate_permutation
from kungfu_tpu.testing import bad_programs

pytestmark = pytest.mark.analysis


def _mesh_dp(n: int = 8) -> Mesh:
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


def _sds(shape, dtype="float32"):
    return jax.ShapeDtypeStruct(shape, dtype)


# -- the five rules on their seeded-bad programs --------------------------------------


class TestSeededBadPrograms:
    @pytest.mark.parametrize(
        "program", bad_programs.PROGRAMS, ids=lambda p: p.name
    )
    def test_fires_exactly_its_rule(self, program):
        findings = check_program(program)
        assert len(findings) == 1, analysis.format_findings(findings)
        (f,) = findings
        assert f.severity == analysis.ERROR
        assert f.rule == bad_programs.EXPECTED_RULE[program.name]

    def test_every_rule_is_covered(self):
        assert set(bad_programs.EXPECTED_RULE.values()) == set(analysis.ALL_RULES)


# -- the shipped corpus must analyze clean --------------------------------------------


class TestCorpusClean:
    @pytest.mark.parametrize(
        "program", builtin_programs(), ids=lambda p: p.name
    )
    def test_no_error_findings(self, program):
        try:
            findings = check_program(program)
        except ProgramUnavailable as e:
            pytest.skip(str(e))
        errs = analysis.errors(findings)
        assert not errs, analysis.format_findings(errs)


# -- rule mechanics on hand-built programs --------------------------------------------


class TestRuleMechanics:
    def test_replicated_predicate_cond_is_clean(self):
        """Divergent branch signatures are fine when the predicate is
        provably replicated — the uniform-branch invariant, not branch
        equality, is what prevents the hang."""
        mesh = _mesh_dp()

        def body(x):
            go = lax.pmax(x[0, 0] > 0, "dp")
            return lax.cond(go, lambda v: lax.psum(v, "dp"), lambda v: v, x)

        fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                       check_vma=False)
        findings = analysis.check(fn, _sds((8, 16)), mesh=mesh)
        assert not analysis.errors(findings), analysis.format_findings(findings)

    def test_total_rotation_ppermute_is_clean(self):
        mesh = _mesh_dp()
        perm = [(i, (i + 1) % 8) for i in range(8)]

        def body(x):
            return lax.ppermute(x, "dp", perm)

        fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                       check_vma=False)
        findings = analysis.check(fn, _sds((8, 16)), mesh=mesh)
        assert not analysis.errors(findings), analysis.format_findings(findings)

    def test_float64_wire_flagged_without_compression(self):
        mesh = _mesh_dp()

        def body(x):
            return lax.psum(x, "dp")

        fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P(),
                       check_vma=False)
        with jax.enable_x64(True):  # default config downcasts f64
            findings = analysis.check(fn, _sds((8, 64), "float64"), mesh=mesh)
        errs = analysis.errors(findings)
        assert [f.rule for f in errs] == [analysis.RULE_WIRE_DTYPE]

    def test_compressed_reduction_on_int8_axis_is_clean(self):
        """The compression subsystem's own allreduce must NOT trip the
        wire-dtype rule it motivates (codes + per-block scales only)."""
        import jax.numpy as jnp

        from kungfu_tpu import compression as comp

        mesh = _mesh_dp()
        cfg = comp.resolve("int8")

        def body(x):
            return comp.all_reduce(jnp.squeeze(x, 0), "dp", cfg, op="mean")[None]

        fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                       check_vma=False)
        findings = analysis.check(fn, _sds((8, 1, 4096)), mesh=mesh,
                                  compression={"dp": cfg})
        assert not analysis.errors(findings), analysis.format_findings(findings)

    def test_suppress_silences_a_rule(self):
        program = bad_programs.PROGRAMS[0]
        rule = bad_programs.EXPECTED_RULE[program.name]
        assert check_program(program, suppress=(rule,)) == []

    def test_findings_carry_provenance(self):
        findings = check_program(
            next(p for p in bad_programs.PROGRAMS
                 if p.name == "bad-cond-divergent-psum")
        )
        (f,) = findings
        assert "shard_map" in f.path
        assert "bad_programs.py" in f.source


# -- satellite: plan/graph bijection checker ------------------------------------------


class TestPermutationValidation:
    def test_valid_ring_accepted(self):
        perm = [(i, (i + 1) % 4) for i in range(4)]
        assert permutation_errors(perm, 4) == []
        validate_permutation(perm, 4)  # must not raise

    def test_partial_permutation_accepted(self):
        # uncovered receivers get zeros by ppermute semantics — legal
        assert permutation_errors([(0, 1)], 4) == []

    def test_duplicate_destination_rejected(self):
        problems = permutation_errors([(0, 1), (2, 1)], 4)
        assert any("destination 2 times" in p for p in problems)
        with pytest.raises(ValueError, match="destination"):
            validate_permutation([(0, 1), (2, 1)], 4)

    def test_duplicate_source_rejected(self):
        with pytest.raises(ValueError, match="source"):
            validate_permutation([(0, 1), (0, 2)], 4)

    def test_out_of_range_rejected(self):
        with pytest.raises(ValueError, match="out of range"):
            validate_permutation([(0, 4)], 4)

    def test_elastic_sizes_sweep(self):
        ring = lambda n: [(i, (i + 1) % n) for i in range(n)]  # noqa: E731
        assert analysis.check_elastic_permutations(ring, range(1, 9)) == []
        # a wiring hardcoded for n=8 breaks when the cluster shrinks
        fixed = lambda n: [(i, (i + 1) % 8) for i in range(8)]  # noqa: E731
        findings = analysis.check_elastic_permutations(fixed, [4])
        assert findings and all(
            f.rule == analysis.RULE_PERMUTATION for f in findings
        )


# -- satellite: eager CompressionConfig axis-key validation ---------------------------


class TestCompressionKeyValidation:
    def test_typo_key_raises_with_known_axes(self):
        from kungfu_tpu import compression as comp

        with pytest.raises(ValueError, match=r"dp '.*known axes.*dp"):
            comp.validate_axis_keys({"dp ": "int8"}, ("dp",))

    def test_valid_keys_pass(self):
        from kungfu_tpu import compression as comp

        comp.validate_axis_keys({"dcn": "int8"}, ("dcn", "ici"))
        comp.validate_axis_keys("int8", ("dp",))  # non-dicts are exempt

    def test_optimizer_rejects_typo_at_construction(self):
        from kungfu_tpu.optimizers import all_reduce_gradients

        with pytest.raises(ValueError, match="known axis"):
            all_reduce_gradients("dp", compression={"pd": "int8"})

    def test_resolve_for_axis_validates_when_axes_known(self):
        from kungfu_tpu.compression import resolve_for_axis

        with pytest.raises(ValueError):
            resolve_for_axis({"pd": "int8"}, "dp", known_axes=("dp",))
        cfg = resolve_for_axis({"dp": "int8"}, "dp", known_axes=("dp",))
        assert cfg.scheme == "int8"


# -- trace-time hooks -----------------------------------------------------------------


class TestTraceTimeHooks:
    def test_sync_sgd_axis_typo_raises_at_trace(self):
        from kungfu_tpu.optimizers import synchronous_sgd

        mesh = _mesh_dp()
        grads = {"w": _sds((16, 4))}
        tx = synchronous_sgd(optax.sgd(0.1), axis_name="pd", analyze=True)
        state = tx.init({"w": np.zeros((16, 4), np.float32)})

        def body(g):
            u, _ = tx.update(g, state, None)
            return u

        fn = shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
        with pytest.raises(analysis.AnalysisError, match="pd"):
            jax.eval_shape(fn, grads)

    def test_pair_averaging_axis_typo_raises_at_trace(self):
        from kungfu_tpu.optimizers import pair_averaging

        mesh = _mesh_dp()
        tx = pair_averaging(optax.sgd(0.1), axis_name="pd", axis_size=8,
                            analyze=True)
        params = {"w": np.zeros((4, 4), np.float32)}
        state = tx.init(params)

        def body(g, p):
            u, _ = tx.update(g, state, p)
            return u

        fn = shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                       check_vma=False)
        with pytest.raises(analysis.AnalysisError, match="pd"):
            jax.eval_shape(fn, params, params)

    def test_session_analyze_clean_allreduce(self):
        from kungfu_tpu.session import Session

        sess = Session(_mesh_dp(), analyze=True)
        out = sess.all_reduce(sess.lift(np.ones(4, np.float32)))
        np.testing.assert_allclose(Session.local_row(out),
                                   8 * np.ones(4, np.float32))

    def test_session_analyze_env_flag(self, monkeypatch):
        from kungfu_tpu.session import Session

        monkeypatch.setenv("KUNGFU_ANALYZE", "1")
        assert Session(_mesh_dp())._analyze
        monkeypatch.delenv("KUNGFU_ANALYZE")
        assert not Session(_mesh_dp())._analyze

    @pytest.mark.parametrize("name", ["fsdp-plain",
                                      "example-fsdp-transformer"])
    def test_mesh_trainer_fsdp_step_passes_the_trace_time_hook(self, name):
        """The step MeshTrainer builds over an fsdp (and dp x fsdp) mesh,
        through the hook a trainer calls before its first dispatch:
        `check_and_raise` raises on an error finding."""
        from kungfu_tpu.analysis.programs import get_program

        fn, args, kw = get_program(name).build()
        assert "fsdp" in kw["mesh"].axis_names
        analysis.check_and_raise(fn, *args, mesh=kw["mesh"],
                                 context="MeshTrainer.train_step")

    def test_pipeline_ring_validated(self):
        # the ring perm is built from the live axis size, so any bijection
        # break would raise here via plan.graph.validate_permutation
        from kungfu_tpu.analysis.programs import get_program

        findings = check_program(get_program("pipeline-gpipe"))
        assert not analysis.errors(findings)


# -- CLI ------------------------------------------------------------------------------


class TestCLI:
    def test_bad_module_exits_nonzero(self, capsys):
        rc = cli.main(["--module", "kungfu_tpu.testing.bad_programs"])
        assert rc == 1
        out = capsys.readouterr().out
        assert "FAIL" in out

    def test_selected_corpus_programs_exit_zero(self, capsys):
        rc = cli.main(["--program", "session-star",
                       "--program", "optimizer-ssgd",
                       "--program", "optimizer-gossip"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "0 errors" in out.splitlines()[-1]

    def test_list_mode(self, capsys):
        assert cli.main(["--list"]) == 0
        out = capsys.readouterr().out
        assert "optimizer-ssgd" in out and "session-ring" in out

    def test_unknown_program_rejected(self):
        with pytest.raises(SystemExit):
            cli.main(["--program", "no-such-program"])


# -- env audit: the count of KFT_* names only falls (ROADMAP D5) ------------------------

#: the number PR 27 left, less the flash backward's two switches (its length
#: threshold, PR 39; its arm, PR 45).  Lower it when you remove a name; raising
#: it needs the two callers at the parent commit that need different values.
KFT_NAMES_CEILING = 74


def test_env_audit_is_clean_and_prints_a_count_under_the_ceiling(capsys):
    from kungfu_tpu.analysis import envaudit

    assert cli.main(["--env"]) == 0
    names = envaudit.code_env()
    assert f"{len(names)} KFT_* names read in code" in capsys.readouterr().out
    assert len(names) <= KFT_NAMES_CEILING, sorted(names)
