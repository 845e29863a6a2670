"""The one span system: every `trace_scope` is an annotation on the
profiler's timeline (flag or no flag) and a ring-buffer `Span` only with
KFT_CONFIG_ENABLE_TRACE; the engine, the worker loop, the observatory
wrapper and the trainer open their scopes where the work happens; the
worker's `/profile` leaves the Python tracer out unless asked.

Captures are taken here on the CPU with the Python tracer off, as the
worker's `/profile` takes them, and read back with `ProfileData`.  The
names and the nesting are the contract with `benchmark/lib/host_spans.py`.
"""
import argparse
import glob
import json
import os
import statistics
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

import jax
import jax.numpy as jnp

from kungfu_tpu.monitor import programs as P
from kungfu_tpu.monitor.counters import DEFAULT_BUCKETS_MS, Counters, Histogram
from kungfu_tpu.utils import trace as T

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# reading an event's stats warns about a builtin type of the profiler's own
pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


class Capture:
    """A profiler capture of a block without the Python tracer; afterwards
    `events` holds (name, start_ns, end_ns, stats, thread line) of every
    host event."""

    def __init__(self, tmp_dir, python=False):
        self.dir, self.python, self.events = str(tmp_dir), python, []

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if self.python else 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        self.events = read_host_events(self.dir)


def read_host_events(trace_dir):
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats), line.name))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def named(events, name):
    return [e for e in events if e[0] == name]


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2] and inner[4] == outer[4]


def children(events, parent, name):
    return [e for e in named(events, name) if inside(e, parent)]


# -- trace_scope -----------------------------------------------------------------------


def test_scope_annotates_with_the_flag_off_and_records_a_span_only_with_it_on(
        tmp_path, monkeypatch):
    buf = T.global_trace_buffer()
    buf.clear()
    with Capture(tmp_path) as cap:
        monkeypatch.delenv(T.ENABLE_ENV, raising=False)
        with T.trace_scope("spans:flag_off"):
            pass
        assert len(buf) == 0
        monkeypatch.setenv(T.ENABLE_ENV, "1")
        with T.trace_scope("spans:flag_on", cat="test"):
            pass
    assert [s.name for s in buf.spans()] == ["spans:flag_on"]
    assert len(named(cap.events, "spans:flag_off")) == 1
    assert len(named(cap.events, "spans:flag_on")) == 1
    buf.clear()


def test_annotation_carries_the_scalar_args_as_stats(tmp_path):
    args = {"active": 3, "bucket": "b64", "share": 0.5, "trace_ids": ["a", "b"]}
    with Capture(tmp_path) as cap:
        with T.trace_scope("spans:args", args=args):
            args["late"] = 1  # the ring serialises at scrape time, the profiler now
    (ev,) = named(cap.events, "spans:args")
    assert ev[3] == {"active": 3, "bucket": "b64", "share": 0.5}


def test_scope_nests_and_survives_an_exception(tmp_path):
    with Capture(tmp_path) as cap:
        with pytest.raises(ValueError):
            with T.trace_scope("spans:outer"):
                with T.trace_scope("spans:inner"):
                    raise ValueError("boom")
        with T.trace_scope("spans:after"):
            pass
    (outer,), (inner,) = named(cap.events, "spans:outer"), named(cap.events, "spans:inner")
    assert inside(inner, outer)
    (after,) = named(cap.events, "spans:after")
    assert after[1] >= outer[2]  # the failed scopes were closed, not left open


def test_trace_module_imports_no_jax_and_starts_no_backend():
    """A process that has not imported jax never does through utils.trace
    (the package's own __init__ is kept out of it here); one that has gets
    annotations without a backend being initialised: the launcher and the
    router parents must never hold the chip."""
    code = """
import sys, types
for name, path in (("kungfu_tpu", {root!r}), ("kungfu_tpu.utils", {root!r} + "/utils")):
    m = types.ModuleType(name); m.__path__ = [path]; sys.modules[name] = m
import kungfu_tpu.utils.trace as T
with T.trace_scope("x", args={{"a": 1}}):
    pass
assert "jax" not in sys.modules, "utils.trace imported jax"
import jax
from jax._src import xla_bridge
with T.trace_scope("y", args={{"a": 1}}):
    pass
assert not xla_bridge._backends, "an annotation initialised a backend"
print("ok")
""".format(root=os.path.join(ROOT, "kungfu_tpu"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr[-2000:]


def test_scope_with_the_flag_off_and_no_capture_costs_microseconds(monkeypatch):
    """2.7 us before the annotation, about 3 with it; the limit is loose
    because the hosts the tests run on are shared."""
    monkeypatch.delenv(T.ENABLE_ENV, raising=False)
    costs = []
    for _ in range(2000):
        t0 = time.perf_counter()
        with T.trace_scope("spans:cost", args={"active": 8}):
            pass
        costs.append(time.perf_counter() - t0)
    assert statistics.median(costs) < 50e-6


def test_trace_scope_has_no_device_parameter():
    with pytest.raises(TypeError):
        with T.trace_scope("spans:device", device=True):
            pass


# -- the engine under a capture --------------------------------------------------------


def _tiny_engine(counters, slots=2):
    from kungfu_tpu.serving import ServingEngine
    from kungfu_tpu.serving.worker import build_config, seed_params

    cfg = build_config("tiny")
    return ServingEngine(cfg, seed_params(cfg, 0), slots=slots, counters=counters)


@pytest.fixture(scope="module")
def engine_run(tmp_path_factory):
    """Four requests through a two-slot engine on the `tiny` preset, warmed
    up first so that the capture holds no compile."""
    from kungfu_tpu.serving import Request

    counters = Counters()
    eng = _tiny_engine(counters)
    reqs = lambda: [Request(prompt=tuple(range(3, 3 + n)), max_new_tokens=m)  # noqa: E731
                    for n, m in ((5, 4), (9, 6), (4, 3), (12, 5))]
    for r in reqs():
        eng.submit(r)
    eng.run_until_idle()
    count = lambda: counters.hist_summaries()["tok_latency_ms"][""]["count"]  # noqa: E731
    before = count()
    with Capture(tmp_path_factory.mktemp("engine")) as cap:
        for r in reqs():
            eng.submit(r)
        done = eng.run_until_idle()
        assert eng.step() == []  # an iteration with nothing to do: no serve:step
    assert len(done) == 4 and all(r.status == "ok" for r in done)
    decodes = count() - before
    return cap.events, decodes


def test_step_holds_decode_holds_dispatch_and_fetch(engine_run):
    """One `serve:decode` a step READ: it holds that step's fetch, and the
    dispatch of the step behind it (of both, entered with nothing in
    flight; of none, when nothing is to follow or the read is a drain
    before an admission)."""
    events, _ = engine_run
    steps, decodes = named(events, "serve:step"), named(events, "serve:decode")
    assert decodes and steps
    held = []
    for d in decodes:
        assert sum(inside(d, s) for s in steps) == 1
        dispatches = children(events, d, "serve:decode.dispatch")
        (fetch,) = children(events, d, "serve:decode.fetch")
        assert len(dispatches) <= 2
        assert all(disp[2] <= fetch[1] for disp in dispatches)
        assert d[3]["active"] in (1, 2)
        held.append(len(dispatches))
    # a step is dispatched once and read once, none is left in flight; the
    # loop ran ahead (a decode that dispatched the step behind the one it
    # read), started over after every admission (two dispatches) and read
    # without dispatching where a request's last token was in flight
    assert sum(held) == len(decodes) and {0, 1, 2} <= set(held)
    # no child outside a serve:decode, and every step did admit or decode
    assert len(named(events, "serve:decode.dispatch")) == len(decodes)
    assert len(named(events, "serve:decode.fetch")) == len(decodes)
    for s in steps:
        assert children(events, s, "serve:decode") or children(events, s, "serve:admit")


def test_upload_before_each_dispatch_and_sample_after_each_decode(engine_run):
    events, _ = engine_run
    order = [e for e in events if e[0] in ("serve:decode.upload",
                                           "serve:decode.dispatch")]
    names = [e[0] for e in order]
    assert names == ["serve:decode.upload",
                     "serve:decode.dispatch"] * (len(names) // 2)
    decodes = named(events, "serve:decode")
    for up, disp in zip(order[0::2], order[1::2]):
        assert up[2] <= disp[1]
        assert sum(inside(up, d) and inside(disp, d) for d in decodes) == 1
    order = [e for e in events if e[0] in ("serve:decode", "serve:decode.sample")]
    names = [e[0] for e in order]
    assert names == ["serve:decode", "serve:decode.sample"] * (len(names) // 2)
    for dec, samp in zip(order[0::2], order[1::2]):
        assert dec[2] <= samp[1]


def test_admit_holds_prefill_and_the_slot_write(engine_run):
    events, _ = engine_run
    admits = named(events, "serve:admit")
    assert len(admits) == 4
    assert sorted(a[3]["tokens"] for a in admits) == [4, 5, 9, 12]
    for a in admits:
        assert a[3]["slot"] in (0, 1)
        (pre,) = children(events, a, "serve:prefill")
        (disp,) = children(events, pre, "serve:prefill.dispatch")
        (fetch,) = children(events, pre, "serve:prefill.fetch")
        (write,) = children(events, a, "serve:slot_write")
        assert disp[2] <= fetch[1] <= pre[2] <= write[1]
    # a finished request's slot is reset inside the sampling of its last token
    resets = named(events, "serve:slot_reset")
    assert len(resets) == 4
    samples = named(events, "serve:decode.sample")
    assert all(any(inside(r, s) for s in samples) for r in resets)


def test_tok_latency_counts_what_serve_decode_spans(engine_run):
    events, decodes = engine_run
    assert decodes == len(named(events, "serve:decode")) > 0


def test_observatory_digest_is_a_span_inside_every_dispatch(engine_run):
    events, _ = engine_run
    digests = named(events, "programs:digest")
    dispatches = (named(events, "serve:decode.dispatch")
                  + named(events, "serve:prefill.dispatch"))
    for d in dispatches:
        assert len([g for g in digests if inside(g, d)]) == 1
    # the slot programs are not tracked: every digest lies in a dispatch
    assert len(digests) == len(dispatches)


def test_worker_loop_opens_one_idle_span_for_each_idle_stretch(tmp_path):
    from kungfu_tpu.serving import Request
    from kungfu_tpu.serving.worker import ServingWorker

    args = argparse.Namespace(
        host="127.0.0.1", port=0, launch_rank=0, incarnation=0,
        config_server="", preset="tiny", model_json="", tier="",
        prefix_cache="off", spec_draft="", spec_k=4, slots=2,
        queue_capacity=8, seed=0, weights_file="", warm_ship_s=0.15,
        buddy_timeout_s=3.0, request_timeout_s=30.0)
    worker = ServingWorker(args)
    eng = worker.engine

    def serve_one():
        pending = eng.submit(Request(prompt=(5, 17, 42), max_new_tokens=4))
        assert pending.wait(60).status == "ok"

    loop = threading.Thread(target=worker._engine_loop, daemon=True)
    loop.start()
    serve_one()  # warm-up: the programs compile outside the capture
    time.sleep(0.05)
    with Capture(tmp_path) as cap:
        time.sleep(0.05)
        serve_one()
        time.sleep(0.06)  # some thirty 2 ms sleeps: one stretch, one span
        serve_one()
        time.sleep(0.05)
        worker._stop.set()
        loop.join(timeout=10)
        assert not loop.is_alive()
    idles, steps = named(cap.events, "serve:idle"), named(cap.events, "serve:step")
    # the stretch open when the capture began is not recorded (its start was
    # not seen); the one between the requests and the one closed by the stop are
    assert len(idles) == 2
    assert steps and all(i[2] - i[1] > 0.03e9 for i in idles)
    for i in idles:  # closed before the work that ended it began
        assert not any(s[1] < i[2] and i[1] < s[2] for s in steps)


# -- the observatory's per-call cost ---------------------------------------------------


def _tree(n=6, width=8, dtype=jnp.float32):
    return ({f"layer{i}": {"w": jnp.zeros((width, 4), dtype), "b": jnp.zeros((4,), dtype)}
             for i in range(n)}, jnp.zeros((2, 1), jnp.int32), 3)


def test_tracked_memoises_the_digest_and_reports_the_same_strings(monkeypatch):
    reg = P.ProgramRegistry()
    calls = []
    real = P.signature_digest

    def counting(args, kwargs):
        calls.append(1)
        return real(args, kwargs)

    monkeypatch.setattr(P, "signature_digest", counting)
    tracked = P._Tracked("spans.fn", lambda *a, **k: None, reg)
    a, b = _tree(), _tree(width=16)
    for _ in range(100):
        tracked(*a, flag=True)
    assert len(calls) == 1
    tracked(*b, flag=True)  # a new shape: one more digest
    tracked(*_tree(dtype=jnp.bfloat16), flag=True)  # a new dtype: another
    tracked(*a, flag=7)  # a python leaf of another type: another
    for _ in range(50):
        tracked(*b, flag=True)
    assert len(calls) == 4
    rep = reg.report()["programs"]["spans.fn"]
    assert set(rep["digests"]) == {
        real(a, {"flag": True}), real(b, {"flag": True}),
        real(_tree(dtype=jnp.bfloat16), {"flag": True}), real(a, {"flag": 7})}
    assert rep["digests"][real(a, {"flag": True})]["calls"] == 100
    assert rep["digests"][real(b, {"flag": True})]["calls"] == 51
    assert rep["calls"] == 153


# -- /profile --------------------------------------------------------------------------

PYTHON_FRAME = r"^\$|\.py:\d+"


def _python_frames(trace_dir):
    import re

    return [e[0] for e in read_host_events(trace_dir)
            if re.search(PYTHON_FRAME, e[0])]


def test_capture_profile_leaves_the_python_tracer_out_unless_asked(tmp_path):
    def work():
        stop = time.monotonic() + 0.15
        while time.monotonic() < stop:
            with T.trace_scope("spans:work"):
                json.dumps({"a": [1, 2, 3]})

    outs = {}
    for python in (False, True):
        t = threading.Thread(target=work)
        t.start()
        outs[python] = P.capture_profile(0.1, out_dir=str(tmp_path / str(python)),
                                         python=python)
        t.join(timeout=10)
        assert outs[python]["ok"] is True and outs[python]["python"] is python
        # the XSpace alone: no trace-viewer conversion while the process serves
        files = [f for _, _, fs in os.walk(outs[python]["path"]) for f in fs]
        assert len(files) == 1 and files[0].endswith(".xplane.pb")
        assert 0 <= outs[python]["dump_s"] < 30
    quiet = read_host_events(outs[False]["path"])
    assert named(quiet, "spans:work") and not _python_frames(outs[False]["path"])
    assert named(read_host_events(outs[True]["path"]), "spans:work")
    assert _python_frames(outs[True]["path"])


def test_profile_endpoint_takes_python_1(tmp_path, monkeypatch):
    from kungfu_tpu.monitor.server import MonitorServer

    monkeypatch.setenv("KFT_TRACE_DUMP_DIR", str(tmp_path))
    srv = MonitorServer(Counters(), port=0, host="127.0.0.1").start()
    try:
        base = f"http://127.0.0.1:{srv.port}/profile?secs=0.1"
        got = {}
        for query in ("", "&python=1"):
            with urllib.request.urlopen(base + query, timeout=60) as r:
                got[query] = json.loads(r.read())
    finally:
        srv.close()
    assert got[""]["ok"] and got[""]["python"] is False
    assert got["&python=1"]["ok"] and got["&python=1"]["python"] is True
    assert os.path.isdir(got["&python=1"]["path"])


# -- the trainer -----------------------------------------------------------------------


def test_mesh_trainer_names_its_host_phases(tmp_path):
    import flax.linen as nn
    import optax

    from kungfu_tpu.plan import make_mesh
    from kungfu_tpu.trainer import MeshTrainer

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(x)

    trainer = MeshTrainer(
        Tiny(), lambda m, p, x: jnp.mean(m.apply({"params": p}, x) ** 2),
        optax.sgd(0.1), mesh=make_mesh(dp=-1))
    batch = jnp.ones((8, 4))
    state = trainer.init(jax.random.PRNGKey(0), batch)
    state, _ = trainer.train_step(state, trainer.shard_batch(batch))  # compiles
    first = state.step
    with Capture(tmp_path) as cap:
        for _ in range(3):
            state, m = trainer.train_step(state, trainer.shard_batch(batch))
        jax.block_until_ready(m["loss"])
    steps = named(cap.events, "train:step")
    assert [s[3]["step"] for s in steps] == [first, first + 1, first + 2]
    shards = named(cap.events, "train:shard_batch")
    assert len(shards) == 3
    assert all(b[2] <= s[1] for b, s in zip(shards, steps))


# -- satellites ------------------------------------------------------------------------


@pytest.mark.parametrize("ms,lo,hi", [(12.0, 10.0, 15.0), (17.0, 15.0, 20.0),
                                      (21.5, 20.0, 25.0), (27.0, 25.0, 30.0),
                                      (33.0, 30.0, 40.0), (46.0, 40.0, 50.0)])
def test_histogram_resolves_a_decode_step_of_tens_of_ms(ms, lo, hi):
    assert {15.0, 20.0, 30.0, 40.0} <= set(DEFAULT_BUCKETS_MS)
    assert list(DEFAULT_BUCKETS_MS) == sorted(DEFAULT_BUCKETS_MS)
    h = Histogram()
    for _ in range(100):
        h.observe(ms)
    assert lo <= h.percentile(0.5) <= hi and lo <= h.percentile(0.99) <= hi
    assert h.sum == pytest.approx(100 * ms) and h.count == 100


def test_flash_kernels_carry_stable_names():
    """The device trace names a Mosaic kernel after its `pallas_call`:
    `kft_flash_*`, not `attn.N` or `shard_map.N` after the enclosing scope."""
    from kungfu_tpu.ops.flash import flash_attention

    q = jnp.zeros((1, 256, 2, 128), jnp.bfloat16)
    kv = jnp.zeros((1, 256, 1, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False,
                               backward="pallas").astype(jnp.float32).sum()

    # MHA: the one-pass backward; GQA: the dq + dk/dv pair
    for k, names in ((q, ("kft_flash_fwd", "kft_flash_bwd")),
                     (kv, ("kft_flash_fwd", "kft_flash_bwd_dq",
                           "kft_flash_bwd_dkdv"))):
        text = jax.export.export(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                                 platforms=["tpu"])(q, k, k).mlir_module()
        for name in names:
            assert f'kernel_name = "{name}"' in text, name
