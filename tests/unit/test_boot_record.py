"""Boot phases, the compile ledger and the start record
(docs/observability.md "Boot"): spans of category `boot` are kept with
tracing off, in a bounded list beside a ring that behaves as before; the
ledger's totals count a nested trace once and leave `compile_ms`,
`compiles` and `cache_hits` what they were; the record is written
atomically, pruned, and only by a process an entry point armed; and a
launched trainer and a serving worker each leave one whose phases make a
timeline.
"""
import json
import os
import subprocess
import sys
import time

import pytest

from kungfu_tpu.monitor import boot
from kungfu_tpu.monitor import programs as P
from kungfu_tpu.monitor.counters import Counters
from kungfu_tpu.utils import trace as T

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    """A process with no boot so far: empty boot list, ring and ledger,
    nobody armed, no backend phase taken."""
    monkeypatch.delenv(T.ENABLE_ENV, raising=False)
    monkeypatch.delenv(T.DUMP_DIR_ENV, raising=False)
    monkeypatch.setattr(P.plan_mesh, "first_asked", None)
    boot._reset_for_tests()
    P._reset_for_tests()
    T.global_trace_buffer().clear()
    yield
    boot._reset_for_tests()
    P._reset_for_tests()
    T.global_trace_buffer().clear()


def span(event, name, start, end):
    P._on_time_span(event, start, end, fun_name=name)


# -- the ledger's totals ---------------------------------------------------------------


def test_nested_traces_total_as_the_union_not_the_sum():
    # as JAX sends them: a nested trace ends, and arrives, before its caller
    span(P.TRACE_EVENT, "matmul", 10.10, 10.40)
    span(P.TRACE_EVENT, "tanh", 10.50, 10.60)
    span(P.TRACE_EVENT, "step", 10.00, 11.00)
    span(P.TRACE_EVENT, "other_program", 12.00, 12.25)
    w = P.compile_watch_state()
    assert w["trace_ms"] == pytest.approx(1250.0)  # the sum would be 1650
    rows = {r["program"]: r for r in w["programs"]}
    assert rows["jit(step)"]["trace_s"] == pytest.approx(1.0)  # JAX's own number
    assert rows["jit(matmul)"]["trace_s"] == pytest.approx(0.3)


def test_a_trace_inside_a_lowering_is_not_lowering_time():
    span(P.TRACE_EVENT, "step", 1.0, 2.0)
    span(P.TRACE_EVENT, "kernel_body", 2.2, 2.5)  # traced while step lowers
    span(P.LOWER_EVENT, "jit(step)", 2.0, 3.0)
    w = P.compile_watch_state()
    assert w["trace_ms"] == pytest.approx(1300.0)
    assert w["lower_ms"] == pytest.approx(700.0)
    assert {r["program"] for r in w["programs"]} == {"jit(step)", "jit(kernel_body)"}


@pytest.mark.parametrize("have,new,grew,left", [
    ([], (1.0, 2.0), 1.0, [(1.0, 2.0)]),                              # first
    ([(1.0, 2.0)], (3.0, 4.0), 1.0, [(1.0, 2.0), (3.0, 4.0)]),        # disjoint
    ([(1.0, 2.0), (3.0, 4.0)], (0.5, 5.0), 2.5, [(0.5, 5.0)]),        # covers both
    ([(1.0, 2.0)], (1.5, 3.0), 1.0, [(1.0, 3.0)]),                    # overlaps the tail
    ([(1.0, 4.0)], (2.0, 3.0), 0.0, [(1.0, 4.0)]),                    # inside the tail
])
def test_union_add(have, new, grew, left):
    iv = [(s, e, e - s) for s, e in have]
    assert P._union_add(iv, *new) == pytest.approx(grew)
    assert iv == [(s, e, e - s) for s, e in left]


def test_a_callers_trace_takes_up_every_jit_it_called():
    # a model's step calls tens of thousands of jitted jnp functions; each
    # is traced inside the step's own trace and arrives before it
    n = 20000
    for i in range(n):
        span(P.TRACE_EVENT, "matmul", 10.0 + i * 1e-3, 10.0 + i * 1e-3 + 5e-4)
    assert P.compile_watch_state()["trace_ms"] == pytest.approx(n * 0.5)
    span(P.TRACE_EVENT, "step", 9.0, 31.0)
    w = P.compile_watch_state()
    assert w["trace_ms"] == pytest.approx(22000.0)  # the step's own 22 s, once
    assert w["events"] == n + 1
    assert all(len(iv) == 1 for pair in P._unions.values() for iv in pair)


def test_the_unions_memory_is_bounded(monkeypatch):
    monkeypatch.setattr(P, "UNION_INTERVALS_MAX", 100)
    for i in range(1000):  # a thousand top-level programs, never taken up
        span(P.LOWER_EVENT, f"jit(p{i % 7})", float(i), i + 0.25)
    assert P.compile_watch_state()["lower_ms"] == pytest.approx(250e3)
    assert all(len(iv) <= 101 for pair in P._unions.values() for iv in pair)


def test_traces_of_two_threads_do_not_merge():
    import threading

    span(P.TRACE_EVENT, "a", 1.0, 2.0)
    t = threading.Thread(target=span, args=(P.TRACE_EVENT, "b", 1.0, 2.0))
    t.start()
    t.join()
    assert P.compile_watch_state()["trace_ms"] == pytest.approx(2000.0)


def parent_counts(events):
    """What the parent's CompileWatch counts for a stream of JAX events."""
    backend = [e for e in events if e[0] == P.BACKEND_COMPILE_EVENT]
    return {"compile_ms": sum(d for _, d, _ in backend) * 1e3,
            "compiles": len(backend),
            "cache_hits": sum(1 for e in events if e[0] == P.CACHE_HIT_EVENT)}


def test_a_cache_hit_adds_to_load_and_leaves_the_parents_counts():
    # JAX 0.9.0, a persistent-cache hit then a miss: the hit fires the
    # retrieval's duration AND the backend event that wraps the lookup
    events = [
        (P.CACHE_HIT_EVENT, None, {}),
        ("/jax/compilation_cache/compile_time_saved_sec", 2.1, {}),
        (P.CACHE_LOAD_EVENT, 0.40, {}),
        (P.BACKEND_COMPILE_EVENT, 0.45, {"fun_name": "jit(step)"}),
        (P.BACKEND_COMPILE_EVENT, 3.00, {"fun_name": "jit(_decode)"}),
    ]
    P.maybe_install()  # the watch's three counters count from here, as ever
    for event, secs, kw in events:
        if secs is None:
            P._on_event(event, **kw)
        else:
            P._on_duration_event(event, secs, **kw)
    w = P.compile_watch_state()
    for key, want in parent_counts(events).items():
        assert w[key] == pytest.approx(want), key
    assert w["cache_load_ms"] == pytest.approx(400.0)
    assert w["cache_misses"] == 1
    rows = {r["program"]: r for r in w["programs"]}
    assert (rows["jit(step)"]["hit"], rows["jit(step)"]["miss"]) == (1, 0)
    assert rows["jit(step)"]["load_s"] == pytest.approx(0.40)
    assert rows["jit(step)"]["compile_s"] == 0.0
    assert rows["jit(_decode)"]["compile_s"] == pytest.approx(3.0)
    assert (rows["jit(_decode)"]["hit"], rows["jit(_decode)"]["miss"]) == (0, 1)


def test_the_three_old_counters_start_at_maybe_install_the_ledger_at_listen(monkeypatch):
    monkeypatch.setitem(P._watch, "active", False)
    P._on_event(P.CACHE_HIT_EVENT)
    P._on_duration_event(P.CACHE_LOAD_EVENT, 0.2)
    P._on_duration_event(P.BACKEND_COMPILE_EVENT, 0.25, fun_name="jit(_normal)")
    w = P.compile_watch_state()
    assert (w["compiles"], w["cache_hits"], w["compile_ms"]) == (0, 0, 0.0)
    assert w["cache_load_ms"] == pytest.approx(200.0)
    assert w["programs"][0]["program"] == "jit(_normal)" and w["programs"][0]["hit"] == 1
    monkeypatch.setitem(P._watch, "active", True)
    P._on_event(P.CACHE_HIT_EVENT)
    P._on_duration_event(P.CACHE_LOAD_EVENT, 0.3)
    P._on_duration_event(P.BACKEND_COMPILE_EVENT, 0.35, fun_name="jit(_decode)")
    w = P.compile_watch_state()
    assert (w["compiles"], w["cache_hits"]) == (1, 1)
    assert w["compile_ms"] == pytest.approx(350.0)
    assert w["cache_load_ms"] == pytest.approx(500.0)


def test_the_ledger_reports_32_rows_and_one_other_row():
    n = P.LEDGER_ROWS + 8
    for i in range(n):
        span(P.LOWER_EVENT, f"jit(p{i})", 100.0 * i, 100.0 * i + 1.0 + i)
    led = P.compile_ledger()
    assert len(led["programs"]) == P.LEDGER_ROWS
    assert led["programs"][0]["program"] == f"jit(p{n - 1})"  # largest first
    assert led["other"]["programs"] == 8
    assert led["other"]["lower_s"] == pytest.approx(sum(1.0 + i for i in range(8)))
    total = sum(r["lower_s"] for r in led["programs"]) + led["other"]["lower_s"]
    assert total == pytest.approx(P.compile_watch_state()["lower_ms"] / 1e3)


def test_the_ledger_keeps_a_bounded_number_of_names(monkeypatch):
    monkeypatch.setattr(P, "LEDGER_NAMES_MAX", 4)
    for i in range(10):
        span(P.TRACE_EVENT, f"f{i}", float(i), i + 0.5)
    assert len(P._ledger) == 5  # four names and the shared row
    assert P._ledger["(more)"]["trace_s"] == pytest.approx(3.0)


# -- the boot list beside the ring -----------------------------------------------------


def test_boot_spans_are_kept_with_tracing_off_and_the_ring_stays_empty():
    assert not T.enabled()
    with T.trace_scope("boot:weights", cat=T.BOOT_CAT, args={"rung": "seed"}):
        with T.trace_scope("serve:admit", cat="serving"):
            pass
    T.record_span("boot:interpreter", time.monotonic() - 1.0, cat=T.BOOT_CAT)
    T.record_span("heal:restore", time.monotonic() - 1.0, cat="heal")
    assert [s.name for s in T.boot_spans()] == ["boot:weights", "boot:interpreter"]
    assert T.boot_spans()[0].args == {"rung": "seed"}
    assert len(T.global_trace_buffer()) == 0


def test_with_tracing_on_the_ring_takes_every_span_as_before(monkeypatch):
    monkeypatch.setenv(T.ENABLE_ENV, "1")
    with T.trace_scope("boot:engine", cat=T.BOOT_CAT):
        with T.trace_scope("serve:admit", cat="serving"):
            pass
    assert sorted(s.name for s in T.global_trace_buffer().spans()) == [
        "boot:engine", "serve:admit"]
    assert [s.name for s in T.boot_spans()] == ["boot:engine"]


def test_the_boot_list_is_bounded():
    for i in range(T.BOOT_CAPACITY + 40):
        T.record_span("boot:first_call", time.monotonic(), cat=T.BOOT_CAT,
                      args={"program": f"p{i}"})
    spans = T.boot_spans()
    assert len(spans) == T.BOOT_CAPACITY
    assert spans[0].args["program"] == "p0"  # a boot is the process's first spans


def test_only_the_first_call_of_a_signature_opens_a_span():
    import jax
    import jax.numpy as jnp

    f = P.track("boot.test", jax.jit(lambda x: x * 2))
    for _ in range(3):
        f(jnp.ones((4,)))
    f(jnp.ones((8,)))
    first = [s for s in T.boot_spans() if s.name == "boot:first_call"]
    assert [s.args["program"] for s in first] == ["boot.test", "boot.test"]


def test_the_backend_phase_is_the_first_question_only():
    T.backend_devices()
    T.backend_devices()
    P.plan_mesh.make_mesh(dp=-1)
    backend = [s for s in P.boot_phases() if s.name == "boot:backend"]
    assert len(backend) == 1 and backend[0].args["platform"] == "cpu"


def test_make_mesh_asking_first_is_the_backend_phase():
    P.plan_mesh.make_mesh(dp=-1)
    backend = [s for s in P.boot_phases() if s.name == "boot:backend"]
    assert len(backend) == 1 and backend[0].args["devices"] >= 1


def test_the_first_step_of_a_state_is_the_trainers_first_call():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kungfu_tpu.trainer import MeshTrainer

    tr = MeshTrainer(nn.Dense(2), lambda m, p, b: jnp.mean(m.apply({"params": p}, b) ** 2),
                     optax.sgd(0.1))
    batch = np.ones((8, 4), np.float32)
    state = tr.init(jax.random.PRNGKey(0), batch)
    placed = tr.shard_batch(batch)
    tr.lower_step(state, placed)
    for _ in range(3):
        state, _ = tr.train_step(state, placed)
    names = [s.name for s in P.boot_phases()]
    assert names.count("train:init") == 1 and names.count("train:lower") == 1
    first = [s for s in P.boot_phases() if s.name == "boot:first_call"]
    assert [s.args for s in first] == [{"program": "train:step"}]
    rec = boot.record()
    assert rec["role"] == "trainer" and rec["boot_complete"] is not None
    assert rec["ledger"]["trace_ms"] > 0 and rec["ledger"]["cache_misses"] > 0
    assert any(r["program"] == "jit(step)" for r in rec["ledger"]["programs"])
    assert len(T.global_trace_buffer()) == 0  # train:step itself: the ring's gate


@pytest.mark.parametrize("axis, sharded", [("fsdp", 6), ("dp", 0)])
def test_train_init_says_where_it_placed_the_optimizer_state(axis, sharded):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kungfu_tpu.trainer import MeshTrainer

    class Net(nn.Module):  # two kernels with a logical "embed" dimension
        @nn.compact
        def __call__(self, x):
            for i, axes in enumerate((("embed", "mlp"), ("mlp", "embed"))):
                x = nn.Dense(8, kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), axes), name=f"d{i}")(x)
            return x

    mesh = P.plan_mesh.make_mesh(devices=jax.devices()[:4], **{axis: 4})
    tr = MeshTrainer(Net(), lambda m, p, b: jnp.mean(m.apply({"params": p}, b) ** 2),
                     optax.chain(optax.adamw(1e-3), optax.ema(0.9)), mesh=mesh)
    tr.init(jax.random.PRNGKey(0), np.ones((8, 8), np.float32))
    (phase,) = [p for p in boot.record()["phases"] if p["name"] == "train:init"]
    # bytes: adamw's mu and nu and the ema of two [8, 8] float32 kernels,
    # of which a chip holds a quarter under fsdp; of two biases, with the
    # two step counts, which it holds whole
    kernels, rest = 3 * 2 * 8 * 8 * 4, 3 * 2 * 8 * 4 + 2 * 4
    assert phase["args"] == {
        "opt_state_sharded_leaves": sharded,
        "opt_state_replicated_leaves": 14 - sharded,
        "opt_state_bytes_a_chip": (kernels // 4 if sharded else kernels) + rest,
    }


# -- the job clock ---------------------------------------------------------------------


def test_the_launcher_stamps_job_start_once_and_anchors_its_clock(monkeypatch):
    monkeypatch.setattr(T, "_JOB_START_MONO", T._JOB_START_MONO)
    monkeypatch.delenv("KFT_JOB_START", raising=False)
    T.stamp_job_start()
    stamped = os.environ["KFT_JOB_START"]
    monkeypatch.setenv("KFT_JOB_START", stamped)  # so that it goes again
    # this process's real start, which is before its import of the module
    assert float(stamped) <= T._PROC_START_WALL
    assert T.job_now(T.process_start_mono()) == pytest.approx(0.0, abs=1e-3)
    T.stamp_job_start()  # an outer launcher's stamp stays
    assert os.environ["KFT_JOB_START"] == stamped


def test_every_spawn_stamps_proc_start():
    from kungfu_tpu.plan import PeerID
    from kungfu_tpu.run.job import Proc
    from kungfu_tpu.run.launcher import ProcRunner

    stamps = []
    for _ in range(2):
        env = dict(os.environ)
        t0 = time.time()
        r = ProcRunner(Proc(name="0", args=[sys.executable, "-c", "pass"],
                            env=env, peer=PeerID("127.0.0.1", 1)), quiet=True)
        r.start()
        assert r.wait() == 0
        assert t0 <= float(env["KFT_PROC_START"]) <= time.time()
        stamps.append(env["KFT_PROC_START"])
    assert stamps[0] != stamps[1]  # a respawn reads its boot from its own spawn
    assert boot.record()["boot_complete"] is None  # pytest is no launcher


@pytest.mark.parametrize("age,taken", [(0.02, True), (30.0, False), (-1.0, False)])
def test_an_inherited_spawn_stamp_is_not_this_process(monkeypatch, age, taken):
    start = time.monotonic()
    spawn_wall = T._PROC_START_WALL - (T._PROC_START_MONO - (start - age))
    monkeypatch.setenv("KFT_PROC_START", repr(spawn_wall))
    got = boot._spawn_mono(start)
    assert got == pytest.approx(start - age if taken else start, abs=1e-6)


# -- the record ------------------------------------------------------------------------


def phases_of(*spans):
    for name, t, s in spans:
        T._keep(T.Span(name=name, t_start=t, dur=s, cat=T.BOOT_CAT), ring=False)
    return boot._phases()


def test_gaps_name_both_neighbours_and_nested_phases_open_none():
    phases = phases_of(("boot:interpreter", 5.0, 0.5), ("boot:imports", 5.5, 3.0),
                       ("train:init", 12.0, 4.0), ("boot:first_call", 13.0, 1.0))
    assert [p["depth"] for p in phases] == [0, 0, 0, 1]
    gaps = boot._gaps(phases, end=17.5)
    assert [(g["after"], g["before"], g["s"]) for g in gaps] == [
        ("job_start", "boot:interpreter", 5.0),
        ("boot:imports", "train:init", 3.5),
        ("train:init", "boot_complete", 1.5)]


def test_the_boot_line_names_every_share():
    phases_of(("boot:interpreter", 5.0, 0.5), ("boot:imports", 5.5, 3.0),
              ("boot:backend", 8.5, 6.0), ("boot:weights", 14.5, 2.0),
              ("boot:resident", 16.5, 0.5), ("boot:engine", 17.0, 1.5))
    span(P.TRACE_EVENT, "step", 1.0, 2.5)
    line = boot.boot_line()
    for part in ("BOOT: total=", " launcher=5.00", " interpreter=0.50",
                 " imports=3.00", " backend=6.00", " weights=4.00",
                 " first_call=0.00", "(trace=1.50 lower=0.00 load=0.00 compile=0.00)",
                 " programs=1 hits=0 misses=0"):
        assert part in line, (part, line)


def test_no_record_from_a_process_nobody_armed(tmp_path, monkeypatch):
    assert T.start_record_dir() == ""  # importing the package armed nothing
    monkeypatch.setenv(T.DUMP_DIR_ENV, str(tmp_path))  # a dump dir alone does not
    boot.complete()
    assert boot.write_record() is None
    assert os.listdir(tmp_path) == []


def test_the_record_is_written_atomically_and_pruned_to_32(tmp_path, monkeypatch):
    T.arm_start_record(str(tmp_path / "starts"))
    phases_of(("boot:imports", 0.1, 1.0))
    paths = []
    for i in range(boot.RECORDS_KEPT + 6):
        monkeypatch.setenv("KFT_SELF_SPEC", f"127.0.0.1:{10000 + i}")
        paths.append(boot.write_record())
        os.utime(paths[-1], (1000.0 + i, 1000.0 + i))
    left = sorted(os.listdir(tmp_path / "starts"))
    assert len(left) == boot.RECORDS_KEPT
    assert all(n.startswith("start-trainer-") and n.endswith(".json") for n in left)
    assert not os.path.exists(paths[0]) and os.path.exists(paths[-1])  # the newest stay
    with open(paths[-1]) as f:
        rec = json.load(f)
    assert rec["role"] == "trainer" and rec["pid"] == os.getpid()
    assert [p["name"] for p in rec["phases"]] == ["boot:imports"]
    assert {"trace_ms", "lower_ms", "cache_load_ms", "cache_misses", "programs",
            "other"} <= set(rec["ledger"])


def test_the_dump_directory_takes_the_record_when_it_is_set(tmp_path, monkeypatch):
    T.arm_start_record(str(tmp_path / "cache" / "starts"))
    monkeypatch.setenv(T.DUMP_DIR_ENV, str(tmp_path / "dump"))
    boot.enter("serve-worker")
    path = boot.write_record()
    assert os.path.dirname(path) == str(tmp_path / "dump")
    assert os.path.basename(path).startswith("start-serve-worker-")
    assert not os.path.exists(tmp_path / "cache")


def test_a_later_first_call_writes_the_record_again(tmp_path):
    import jax
    import jax.numpy as jnp

    T.arm_start_record(str(tmp_path))
    f = P.track("boot.later", jax.jit(lambda x: x + 1))
    f(jnp.ones((2,)))
    assert os.listdir(tmp_path) == []  # boot is not complete: nothing yet
    boot.complete()
    (name,) = os.listdir(tmp_path)
    with open(tmp_path / name) as fh:
        assert len(json.load(fh)["phases"]) == 3  # interpreter, imports, the call
    f(jnp.ones((3,)))  # a new signature, after boot complete
    with open(tmp_path / name) as fh:
        rec = json.load(fh)
    assert [p["name"] for p in rec["phases"]].count("boot:first_call") == 2
    assert rec["phases"][-1]["t"] > rec["boot_complete"]


# -- /metrics and /programs ------------------------------------------------------------


def check_exposition(text):
    """Text format 0.0.4: every sample's family has one # TYPE and a # HELP
    before it (tests/unit/test_timeseries.py holds the fleet to the same)."""
    typed, helped = {}, set()
    for line in text.splitlines():
        if line.startswith("# HELP "):
            helped.add(line.split()[2])
        elif line.startswith("# TYPE "):
            assert line.split()[2] not in typed, line
            typed[line.split()[2]] = line.split()[3]
        elif line.strip():
            family = line.split("{")[0].split(" ")[0]
            assert family in typed and family in helped, line
    return typed


def test_the_two_families_pass_the_exposition_checker():
    phases_of(("boot:imports", 0.1, 1.25), ("boot:first_call", 2.0, 0.5),
              ("boot:first_call", 3.0, 0.25))
    span(P.TRACE_EVENT, "step", 1.0, 2.5)
    P._on_duration_event(P.BACKEND_COMPILE_EVENT, 2.0, fun_name="jit(step)")
    c = Counters()
    c.inc_event("steps")
    c.add_source(boot.families)
    text = c.prometheus_text()
    typed = check_exposition(text)
    assert typed["kft_boot_seconds"] == typed["kft_program_setup_seconds"] == "gauge"
    assert 'kft_boot_seconds{phase="boot:first_call"} 0.75' in text
    assert 'kft_program_setup_seconds{stage="trace"} 1.5' in text
    assert 'kft_program_setup_seconds{stage="compile"} 2.0' in text
    assert "# HELP kft_boot_seconds Seconds of each boot phase" in text


def test_programs_endpoint_carries_totals_rows_and_boot_phases():
    phases_of(("boot:backend", 1.0, 2.0))
    span(P.LOWER_EVENT, "jit(_decode)", 1.0, 1.5)
    watch = P.global_registry().report()["watch"]
    assert watch["lower_ms"] == pytest.approx(500.0)
    assert watch["programs"][0]["program"] == "jit(_decode)"
    assert watch["boot"] == [{"name": "boot:backend", "t": 1.0, "s": 2.0, "args": {}}]
    json.dumps(watch)  # the endpoint's body


# -- end to end: a launched trainer, a serving worker ----------------------------------

TINY_TRAINER = """
import sys
sys.path.insert(0, {repo!r})
from kungfu_tpu.env import apply_platform_override, enable_compile_cache
apply_platform_override()
enable_compile_cache()
import jax, jax.numpy as jnp, numpy as np, optax
import flax.linen as nn
from kungfu_tpu.monitor import programs
from kungfu_tpu.plan import make_mesh
from kungfu_tpu.trainer import MeshTrainer

programs.maybe_install()  # as the benchmark's worker: the watch counts from here
jax.devices()  # the caller asks first, as the benchmark's worker does
tr = MeshTrainer(nn.Dense(4), lambda m, p, b: jnp.mean(m.apply({{"params": p}}, b) ** 2),
                 optax.sgd(0.1), mesh=make_mesh(dp=-1))
batch = np.ones((8, 8), np.float32)
state = tr.init(jax.random.PRNGKey(0), batch)
placed = tr.shard_batch(batch)
tr.lower_step(state, placed)
for _ in range(2):
    state, m = tr.train_step(state, placed)
print("RESULT: loss", float(m["loss"]))
"""


def child_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("KFT_JOB_START", "KFT_PROC_START", T.DUMP_DIR_ENV,
                        T.ENABLE_ENV, "KFT_CONFIG_ENABLE_MONITORING")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO + os.pathsep
               + env.get("PYTHONPATH", ""),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1", **extra)
    return env


def records_in(directory):
    """{role: its record of the newest job in `directory`}"""
    out = {}
    for name in os.listdir(directory):
        if name.startswith("start-") and name.endswith(".json"):
            with open(os.path.join(directory, name)) as f:
                rec = json.load(f)
            if rec["job_start_wall"] >= out.get(rec["role"], rec)["job_start_wall"]:
                out[rec["role"]] = rec
    return out


def check_timeline(rec):
    """Phases before boot complete lie inside [job start, boot complete],
    the top-level ones do not overlap, and every stretch between them is a
    gap that names both neighbours: together they tile the timeline."""
    done = rec["boot_complete"]
    before = [p for p in rec["phases"] if p["t"] < done]
    assert before and all(p["t"] >= -1e-3 and p["t"] + p["s"] <= done + 1e-2
                          for p in before), rec["phases"]
    top = [p for p in before if p["depth"] == 0]
    for a, b in zip(top, top[1:]):
        assert a["t"] + a["s"] <= b["t"] + 1e-3, (a, b)
    names = {p["name"] for p in top} | {"job_start", "boot_complete"}
    assert all(g["after"] in names and g["before"] in names for g in rec["gaps"])
    covered = sum(p["s"] for p in top) + sum(g["s"] for g in rec["gaps"])
    assert covered == pytest.approx(done, abs=0.05), (covered, done)


def test_a_launched_trainer_leaves_a_record_and_a_warm_start_loads(tmp_path):
    script = tmp_path / "tiny_trainer.py"
    script.write_text(TINY_TRAINER.format(repo=REPO))
    cache = tmp_path / "cache"
    runs = []
    for _ in range(2):  # an empty cache, then a warm one
        r = subprocess.run(
            [sys.executable, "-m", "kungfu_tpu.run", "-np", "1", "-platform", "cpu",
             sys.executable, str(script)], cwd=str(tmp_path), capture_output=True,
            text=True, timeout=300,
            env=child_env(JAX_COMPILATION_CACHE_DIR=str(cache)))
        assert r.returncode == 0 and "RESULT: loss" in r.stdout, r.stdout + r.stderr
        assert r.stderr.count("BOOT: total=") + r.stdout.count("BOOT: total=") == 2
        runs.append(records_in(cache / "starts"))
    cold, warm = runs
    launcher, trainer = warm["launcher"], warm["trainer"]
    assert launcher["job_start_wall"] == trainer["job_start_wall"]
    assert trainer["ppid"] == launcher["pid"]
    assert launcher["process_start"] == pytest.approx(0.0, abs=0.02)
    assert [p["name"] for p in launcher["phases"]] == [
        "boot:launcher", "boot:launcher.imports", "boot:launcher.config",
        "boot:launcher.spawn"]
    check_timeline(launcher)
    check_timeline(trainer)
    top = [p["name"] for p in trainer["phases"] if p["depth"] == 0]
    assert top == ["boot:interpreter", "boot:imports", "boot:backend", "train:init",
                   "train:lower", "boot:first_call"]
    # job start -> spawn -> process start -> entry, from the worker's record alone
    interp = trainer["phases"][0]
    spawn = next(p for p in launcher["phases"] if p["name"] == "boot:launcher.spawn")
    # (two processes' clocks meet through the wall clock: a few ms of slack)
    assert spawn["t"] - 0.05 <= interp["t"] <= spawn["t"] + spawn["s"] + 0.05
    assert interp["t"] - 0.05 <= interp["args"]["process_start"] \
        <= interp["t"] + interp["s"]
    # the caller's own imports and its jax.devices(): a gap, both sides named
    assert any(g["after"] == "boot:imports" and g["before"] == "boot:backend"
               for g in trainer["gaps"])
    led_cold, led_warm = cold["trainer"]["ledger"], warm["trainer"]["ledger"]
    # an empty cache compiles (two small programs of the init come twice,
    # and the second finds the first's entry)
    assert led_cold["cache_misses"] > led_cold["cache_hits"]
    assert led_cold["cache_misses"] + led_cold["cache_hits"] == led_cold["compiles"]
    # the same programs, loaded: both events fire on a hit, so `compiles`
    # counts them still, and what `compile_ms` now holds is retrieval
    assert led_warm["compiles"] == led_cold["compiles"] == led_warm["cache_hits"]
    assert led_warm["cache_misses"] == 0
    assert 0 < led_warm["cache_load_ms"] <= led_warm["compile_ms"]
    assert led_warm["trace_ms"] > 0 and led_warm["lower_ms"] > 0
    step = next(r for r in led_warm["programs"] if r["program"] == "jit(step)")
    assert step["hit"] == 1 and step["load_s"] > 0 and step["trace_s"] > 0


def test_a_serving_worker_leaves_a_record(tmp_path):
    dump = tmp_path / "dump"
    proc = subprocess.Popen(
        [sys.executable, "-m", "kungfu_tpu.serving", "-np", "1", "--platform", "cpu",
         "--preset", "tiny", "--slots", "2", "--timeout", "240", "--no-autoscale", "-q"],
        cwd=str(tmp_path), stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=child_env(KFT_TRACE_DUMP_DIR=str(dump),
                      JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")))
    try:
        deadline = time.monotonic() + 240
        recs = {}
        while time.monotonic() < deadline and "serve-worker" not in recs:
            assert proc.poll() is None, proc.stdout.read()
            time.sleep(0.25)
            recs = records_in(dump) if dump.exists() else {}
    finally:
        proc.terminate()
        out = proc.communicate(timeout=60)[0]
    assert "serve-worker" in recs, out
    sup, worker = recs["supervisor"], recs["serve-worker"]
    assert sup["job_start_wall"] == worker["job_start_wall"]
    assert worker["ppid"] == sup["pid"]
    assert [p["name"] for p in sup["phases"]] == [
        "boot:supervisor", "boot:supervisor.imports", "boot:supervisor.config_server",
        "boot:supervisor.router", "boot:supervisor.spawn"]
    check_timeline(sup)
    check_timeline(worker)
    top = [p for p in worker["phases"] if p["depth"] == 0 and p["t"] < worker["boot_complete"]]
    assert [p["name"] for p in top] == [
        "boot:interpreter", "boot:imports", "boot:backend", "boot:weights",
        "boot:resident", "boot:engine"]
    assert top[3]["args"] == {"rung": "seed"}
    led = worker["ledger"]
    assert led["cache_misses"] > 0 and led["trace_ms"] > 0 and led["events"] > 0
    # the ledger holds the boot; the watch's three counters start where they
    # always did, with the monitor, and this fleet has none
    assert (led["compiles"], led["cache_hits"], led["compile_ms"]) == (0, 0, 0.0)
    assert not os.path.exists(tmp_path / "cache" / "starts")  # the dump dir took both
