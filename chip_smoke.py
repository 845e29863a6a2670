#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once on a TPU through the entry points a user calls,
at the full width of the flagship LM (d_model 1024, 16 heads, d_ff 4096,
vocab 32000, seq 2048, bf16, 4 sequences per chip; random weights from a
seed), and checks what comes out by the repo's own means.  It fails — it
never falls back — when JAX finds no TPU.

    python chip_smoke.py             # phases A, B, E; C and D when 4 chips
    python chip_smoke.py --chips 4   # ... and demand the four-chip phases

  A  one chip, kernels     flash fwd+bwd (Pallas backward forced, and the
                           default arm) against full_attention
  B  one worker, trainer   kungfu_tpu.run -> examples/gpt_train.py, the
                           flagship LM under MeshTrainer (dp over every
                           chip the worker finds, 4 sequences per chip)
  C  four chips, 1 process the LM (depth cut) on dp=4 and dp=2 x fsdp=2,
                           shard placement, loss parity, Session.all_reduce
  D  four chips, 4 workers kungfu_tpu.run -np 4 -chips-per-host 4, S-SGD
  E  one chip, server      kungfu_tpu.serving boots a worker child, answers
                           four requests, replays the first

One process owns a chip at a time, so this parent never imports JAX: every
phase is a child process, run one after another, and killed with its whole
process group if it outlives its limit.  The last line of standard output
is the JSON verdict; it is printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the flagship's width (the bring-up LM of PR 21, no published model): never cut
WIDTH = dict(d_model=1024, n_heads=16, d_ff=4096, vocab=32000, seq_len=2048,
             batch_per_chip=4)
FLAGSHIP_LAYERS = 24
#: Phase C compiles the LM twice on four chips; depth is cut, width is not
PHASE_C_LAYERS = 4

RESULT_TAG = "SMOKE_PHASE "


class PhaseFailed(Exception):
    pass


# -- parent: run children, never touch JAX ---------------------------------------------


def _kill_group(popen: subprocess.Popen) -> None:
    """Stop a child and everything it started (it leads its own group)."""
    for sig in (signal.SIGTERM, signal.SIGKILL):
        if popen.poll() is not None:
            break
        try:
            os.killpg(popen.pid, sig)
        except ProcessLookupError:
            break
        try:
            popen.wait(timeout=10)
        except subprocess.TimeoutExpired:
            continue
    # stragglers of the group that outlived its leader
    try:
        os.killpg(popen.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


class Child:
    """One phase's child process: own process group, output echoed under
    the phase's name and kept for the checks."""

    def __init__(self, phase: str, cmd):
        self.phase = phase
        self.lines = []
        self._lock = threading.Lock()
        print(f"[{phase}] $ {' '.join(cmd)}", flush=True)
        self.popen = subprocess.Popen(
            cmd, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, bufsize=1,
            start_new_session=True,
        )
        self._pump = threading.Thread(target=self._read, daemon=True)
        self._pump.start()

    def _read(self) -> None:
        for line in self.popen.stdout:
            line = line.rstrip("\n")
            with self._lock:
                self.lines.append(line)
            print(f"[{self.phase}] {line}", flush=True)

    def snapshot(self):
        with self._lock:
            return list(self.lines)

    def wait(self, timeout_s: float) -> int:
        try:
            rc = self.popen.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            _kill_group(self.popen)
            raise PhaseFailed(f"phase {self.phase} ran past {timeout_s:.0f}s")
        self._pump.join(timeout=10)
        _kill_group(self.popen)  # whatever the child left behind
        return rc

    def wait_for_line(self, pattern: str, timeout_s: float) -> str:
        """First output line matching `pattern`; fails if the child exits
        or the limit passes first."""
        rx = re.compile(pattern)
        deadline = time.monotonic() + timeout_s
        seen = 0
        while time.monotonic() < deadline:
            lines = self.snapshot()
            for line in lines[seen:]:
                if rx.search(line):
                    return line
            seen = len(lines)
            if self.popen.poll() is not None:
                raise PhaseFailed(
                    f"phase {self.phase}: child exited "
                    f"({self.popen.returncode}) before printing /{pattern}/")
            time.sleep(0.1)
        raise PhaseFailed(
            f"phase {self.phase}: no /{pattern}/ within {timeout_s:.0f}s")

    def stop(self) -> None:
        _kill_group(self.popen)
        self._pump.join(timeout=10)


def _fields(line: str) -> dict:
    """key=value tokens of a DEVICE:/RESULT:/READY line (values may be
    quoted with repr)."""
    out = {}
    for m in re.finditer(r"(\w+)=('(?:[^']*)'|\S+)", line):
        v = m.group(2)
        out[m.group(1)] = v[1:-1] if v.startswith("'") else v
    return out


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def _report(phase: str, device: dict, setup_s, step_ms, extra: str = "") -> None:
    _require(device.get("platform") == "tpu",
             f"phase {phase} ran on platform={device.get('platform')!r}, not tpu")
    print(f"phase {phase}: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"setup_s={float(setup_s):.1f} step_ms={float(step_ms):.2f}"
          + (f" {extra}" if extra else "") + " ok", flush=True)


def _in_process_phase(phase: str, timeout_s: float) -> dict:
    """Phases A and C run in a child of this same script."""
    child = Child(phase, [sys.executable, os.path.abspath(__file__),
                          "--child", phase])
    rc = child.wait(timeout_s)
    _require(rc == 0, f"phase {phase}: child exited {rc}")
    tagged = [l for l in child.lines if l.startswith(RESULT_TAG)]
    _require(len(tagged) == 1, f"phase {phase}: no result line")
    res = json.loads(tagged[0][len(RESULT_TAG):])
    _report(phase, res["device"], res["setup_s"], res["step_ms"],
            res.get("note", ""))
    return res


def phase_b_parent(n_chips: int, timeout_s: float) -> dict:
    """One worker that owns every chip it finds (dp over all of them), at
    the flagship's 4 sequences per chip."""
    steps = 8
    child = Child("B", [
        sys.executable, "-m", "kungfu_tpu.run", "-np", "1",
        sys.executable, "examples/gpt_train.py",
        "--d-model", str(WIDTH["d_model"]), "--n-layers", str(FLAGSHIP_LAYERS),
        "--n-heads", str(WIDTH["n_heads"]), "--n-kv-heads", "0",
        "--vocab", str(WIDTH["vocab"]), "--seq-len", str(WIDTH["seq_len"]),
        "--batch", str(WIDTH["batch_per_chip"] * n_chips),
        "--steps", str(steps),
    ])
    rc = child.wait(timeout_s)
    _require(rc == 0, f"phase B: launcher exited {rc}")
    dev = [_fields(l) for l in child.lines if "DEVICE:" in l]
    res = [_fields(l) for l in child.lines if "RESULT:" in l]
    _require(len(dev) == 1 and len(res) == 1,
             "phase B: expected one DEVICE and one RESULT line from the worker")
    dev, res = dev[0], res[0]
    first, last = float(res["first_loss"]), float(res["loss"])
    _require(last == last and abs(last) != float("inf"), "phase B: loss not finite")
    _require(last < first, f"phase B: loss did not fall ({first} -> {last})")
    _require(res["attention"] == "flash",
             f"phase B: attention resolved to {res['attention']}, not flash")
    _require(res["dtype"] == "bfloat16" and res["param_dtypes"] == "float32",
             f"phase B: dtypes {res['dtype']}/{res['param_dtypes']}, "
             "configured bfloat16 compute over float32 params")
    _require(int(res["mosaic_calls"]) > 0,
             "phase B: no tpu_custom_call in the compiled step")
    _require(int(res["compiles_after_warmup"]) == 0,
             f"phase B: {res['compiles_after_warmup']} compilations after warm-up")
    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": int(dev["count"])}
    _report("B", device, res["setup_s"], res["step_ms"],
            f"loss={first}->{last} mosaic_calls={res['mosaic_calls']} "
            f"step_cache_hits={res['step_cache_hits']} "
            f"tokens_per_sec={res['tokens_per_sec']}")
    return {"device": device, "step_cache_hits": int(res["step_cache_hits"])}


def phase_d_parent(timeout_s: float) -> None:
    child = Child("D", [
        sys.executable, "-m", "kungfu_tpu.run", "-np", "4",
        "-chips-per-host", "4",
        sys.executable, "examples/mnist_slp.py", "--steps", "60",
    ])
    rc = child.wait(timeout_s)
    _require(rc == 0, f"phase D: launcher exited {rc}")
    res = [_fields(l) for l in child.lines if "RESULT:" in l]
    _require(sorted(r["rank"] for r in res) == [f"{i}/4" for i in range(4)],
             f"phase D: RESULT lines from ranks {[r.get('rank') for r in res]}")
    for r in res:
        # one slice of four chips, one chip in each process: not four
        # copies of a one-chip job
        _require(r["devices"] == "4" and r["local_devices"] == "1",
                 f"phase D: rank {r['rank']} sees {r['devices']} devices, "
                 f"{r['local_devices']} local; wanted 4 and 1")
        _require(float(r["acc"]) > 0.5, f"phase D: rank {r['rank']} acc {r['acc']}")
    # S-SGD keeps replicas identical: every rank reports the same model
    _require(len({r["acc"] for r in res}) == 1,
             f"phase D: ranks disagree on accuracy {[r['acc'] for r in res]}")
    r0 = res[0]
    _report("D", {"platform": r0["platform"], "kind": r0["device_kind"],
                  "count": int(r0["devices"])},
            r0["setup_s"], r0["step_ms"], f"acc={r0['acc']}")


def _post(url: str, doc: dict, timeout_s: float) -> bytes:
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout_s) as r:
        return r.read()


def phase_e_parent(n_chips: int, timeout_s: float) -> None:
    t0 = time.monotonic()
    child = Child("E", [
        sys.executable, "-m", "kungfu_tpu.serving", "-np", "1",
        "--preset", "small", "--slots", "4",
        "--chips-per-host", str(n_chips), "--timeout", str(int(timeout_s)),
    ])
    try:
        url = child.wait_for_line(r"SERVE_URL: ", 60).split("SERVE_URL: ")[1]
        ready = _fields(child.wait_for_line(r"SERVE_WORKER_READY:", timeout_s))
        prompts = [[5, 17, 42, 7], [9, 3, 200, 11, 64], [1, 2, 3], [250, 8]]
        answers = []
        for p in prompts:
            body = _post(url + "/v1/generate",
                         {"prompt": p, "max_new_tokens": 16}, timeout_s)
            doc = json.loads(body)
            # the answer carries the prompt and then the new tokens
            _require(doc.get("status") == "ok"
                     and doc["tokens"][:len(p)] == p
                     and len(doc["tokens"]) == len(p) + 16,
                     f"phase E: bad answer {doc}")
            answers.append(doc)
        setup_s = time.monotonic() - t0  # boot, compile, first answers
        replay = json.loads(_post(url + "/v1/generate",
                                  {"prompt": prompts[0], "max_new_tokens": 16},
                                  timeout_s))
        _require(json.dumps(replay["tokens"]) == json.dumps(answers[0]["tokens"]),
                 "phase E: the replayed request's tokens differ")
        # a warm request: worker-side latency past the first token, per
        # token, each decode step ended by the host fetch of its logits
        step_ms = (replay["latency_ms"] - replay["ttft_ms"]) / 15
        _report("E", {"platform": ready["platform"],
                      "kind": ready["device_kind"],
                      "count": int(ready["devices"])},
                setup_s, step_ms, "requests=4 replay=identical")
    finally:
        child.stop()


def parent(args) -> int:
    t_start = time.monotonic()
    a = _in_process_phase("A", 420)
    device = a["device"]
    n = int(device["count"])
    _require(args.chips in (0, n),
             f"--chips {args.chips} but JAX finds {n} device(s)")
    b = phase_b_parent(n, 720)
    _require(b["device"] == device, "phase B saw another device than phase A")
    if n == 4:
        _in_process_phase("C", 600)
        phase_d_parent(300)
    else:
        for phase in "CD":
            print(f"phase {phase}: not run ({n} devices)", flush=True)
    phase_e_parent(n, 300)
    print(f"chip_smoke: all phases passed in {time.monotonic() - t_start:.0f}s; "
          f"the LM step was {'served from' if b['step_cache_hits'] else 'not in'}"
          " the compile cache", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# -- children: the phases that run in a process of this script -------------------------


def _device() -> dict:
    """The device as JAX reports it; no TPU is a failure, never a fallback."""
    import jax

    devs = jax.devices()
    d = {"platform": devs[0].platform, "kind": devs[0].device_kind,
         "count": len(devs)}
    print(f"DEVICE: platform={d['platform']} device_kind={d['kind']!r} "
          f"count={d['count']}", flush=True)
    if d["platform"] != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {d['platform']}")
    return d


def _lm_config(n_layers: int, mesh=None, **kw):
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    # examples/gpt_train.py's model at the flagship's width
    return TransformerConfig(
        vocab_size=WIDTH["vocab"], d_model=WIDTH["d_model"], n_layers=n_layers,
        n_heads=WIDTH["n_heads"], n_kv_heads=0, rope=True, ffn="swiglu",
        tie_embeddings=True, d_ff=WIDTH["d_ff"], max_len=WIDTH["seq_len"],
        dtype=jnp.bfloat16, attention="auto", mesh=mesh, **kw)


def child_a() -> dict:
    """Flash kernels, compiled by Mosaic, against the plain-XLA reference."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from kungfu_tpu import native
    from kungfu_tpu.ops.flash import flash_attention, flash_blocks
    from kungfu_tpu.parallel.ring_attention import full_attention

    device = _device()
    print(f"native: {'built' if native.available() else 'numpy'}", flush=True)
    B, L = WIDTH["batch_per_chip"], WIDTH["seq_len"]
    setup_s, step_ms = 0.0, []
    for H in (WIDTH["n_heads"], WIDTH["n_heads"] // 2):  # head_dim 64, 128
        D = WIDTH["d_model"] // H
        bq, bk = flash_blocks(None, None, head_dim=D, seq_len=L,
                              dtype_bytes=2)  # the table, as a model's None
        ks = jax.random.split(jax.random.PRNGKey(H), 4)
        q, k, v, w = [jax.random.normal(kk, (B, L, H, D), jnp.bfloat16)
                      for kk in ks]

        def loss_of(attn, w):
            # a random cotangent, so every element of dq/dk/dv is exercised
            return lambda q, k, v: jnp.sum(
                attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

        # reference in f32 on the first sequence only: sequences are
        # independent, and one [H, L, L] f32 score tensor is enough to hold
        def ref_attn(q, k, v):
            return full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                                  v.astype(jnp.float32), causal=True)

        ref_o = jax.jit(ref_attn)(q[:1], k[:1], v[:1])
        ref_g = jax.jit(jax.grad(loss_of(ref_attn, w[:1]), argnums=(0, 1, 2)))(
            q[:1], k[:1], v[:1])

        for backward in ("pallas", None):
            arm = backward or "default"
            attn = lambda q, k, v: flash_attention(  # noqa: E731
                q, k, v, causal=True, block_q=bq, block_k=bk,
                backward=backward)
            fwd = jax.jit(attn)
            grad = jax.jit(jax.grad(loss_of(attn, w), argnums=(0, 1, 2)))
            text = grad.lower(q, k, v).as_text()
            n_calls = text.count("tpu_custom_call")
            # forward kernel always; forced, the backward too (MHA at this
            # length: the one-pass kernel, so one more call, not two)
            want = 2 if backward == "pallas" else 1
            assert n_calls >= want, (
                f"D{D} {arm}: {n_calls} tpu_custom_call in the lowered "
                f"grad, expected at least {want}")
            t0 = time.perf_counter()
            o = jax.block_until_ready(fwd(q, k, v))
            g = jax.block_until_ready(grad(q, k, v))
            setup_s += time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(5):
                g = grad(q, k, v)
            jax.block_until_ready(g)
            ms = (time.perf_counter() - t0) / 5 * 1e3
            step_ms.append(ms)

            def close(name, got, ref):
                got = np.asarray(got[:1], np.float32)
                ref = np.asarray(ref, np.float32)
                assert np.isfinite(got).all(), f"D{D} {arm} {name}: not finite"
                # tests/unit/test_flash.py's bf16 bound, scaled to the
                # tensor's magnitude (gradients here are not O(1))
                tol = 3e-2 * max(1.0, float(np.abs(ref).max()))
                err = float(np.abs(got - ref).max())
                assert err <= tol, f"D{D} {arm} {name}: |err| {err} > {tol}"
                return err

            assert o.shape == (B, L, H, D) and o.dtype == jnp.bfloat16
            errs = [close("o", o, ref_o)] + [
                close(n, a, b) for n, a, b in zip(("dq", "dk", "dv"), g, ref_g)]
            print(f"# flash B{B} H{H} D{D} L{L} tiles {bq}x{bk} backward={arm}: "
                  f"{n_calls} mosaic calls, fwd+bwd {ms:.2f} ms, max |err| "
                  f"o/dq/dk/dv {' '.join(f'{e:.3g}' for e in errs)}", flush=True)
    return {"device": device, "setup_s": setup_s, "step_ms": max(step_ms),
            "note": "flash fwd+bwd, slowest of 4 arms"}


def child_c() -> dict:
    """The LM on four chips in one process, under two layouts."""
    import numpy as np
    import jax

    from kungfu_tpu.env import enable_compile_cache
    from kungfu_tpu.models.transformer import TransformerLM, lm_loss
    from kungfu_tpu.optimizers import lm_adamw
    from kungfu_tpu.plan import make_mesh
    from kungfu_tpu.session import Session
    from kungfu_tpu.trainer import MeshTrainer

    enable_compile_cache()
    device = _device()
    devs = jax.devices()
    assert len(devs) == 4, f"phase C needs four devices, found {len(devs)}"
    batch = WIDTH["batch_per_chip"] * 4
    rng = np.random.RandomState(0)
    start = rng.randint(0, WIDTH["vocab"] // 2, size=(batch, 1))
    tokens = ((start + np.arange(WIDTH["seq_len"])[None, :])
              % WIDTH["vocab"]).astype(np.int32)

    def four_distinct(tree, what):
        for path, x in jax.tree_util.tree_leaves_with_path(tree):
            shards = x.addressable_shards
            where = {s.device for s in shards}
            assert where == set(devs), (
                f"{what}{jax.tree_util.keystr(path)}: shards on {where}")
            want = x.sharding.shard_shape(x.shape)
            assert all(s.data.shape == want for s in shards), (
                f"{what}{jax.tree_util.keystr(path)}: shard shapes "
                f"{[s.data.shape for s in shards]}, expected {want}")

    losses, setup_s, step_ms, shard_bytes = {}, 0.0, [], {}
    for name, axes in (("dp4", dict(dp=4)), ("dp2xfsdp2", dict(dp=2, fsdp=2))):
        mesh = make_mesh(**axes)  # topology-aware device order on a TPU
        model = TransformerLM(_lm_config(PHASE_C_LAYERS, mesh=mesh))
        trainer = MeshTrainer(
            model, lambda m, p, t: lm_loss(m.apply({"params": p}, t), t),
            lm_adamw(3e-4, warmup_steps=2, total_steps=10), mesh=mesh)
        t0 = time.perf_counter()
        state = trainer.init(jax.random.PRNGKey(0), tokens)
        placed = trainer.shard_batch(tokens)
        four_distinct(state.params, f"{name} params")
        four_distinct(placed, f"{name} batch")
        assert placed.sharding.shard_shape(placed.shape) == (
            WIDTH["batch_per_chip"], WIDTH["seq_len"]), placed.sharding
        shard_bytes[name] = sum(
            x.addressable_shards[0].data.nbytes
            for x in jax.tree.leaves(state.params))
        state, m = trainer.train_step(state, placed)
        run = [float(jax.block_until_ready(m["loss"]))]
        setup_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(3):
            state, m = trainer.train_step(state, placed)
            run.append(float(jax.block_until_ready(m["loss"])))
        step_ms.append((time.perf_counter() - t0) / 3 * 1e3)
        for d in devs:
            used = d.memory_stats()["bytes_in_use"]
            assert used > 0, f"{name}: device {d.id} holds nothing"
        four_distinct(state.params, f"{name} params after training")
        assert all(np.isfinite(run)) and run[-1] < run[0], (name, run)
        losses[name] = run
        print(f"# {name}: mesh {dict(mesh.shape)} losses "
              f"{' '.join(f'{x:.4f}' for x in run)} param bytes per chip "
              f"{shard_bytes[name] / 2**20:.0f} MiB step {step_ms[-1]:.1f} ms",
              flush=True)
        del state, trainer, model, placed, m
    # fsdp=2 halves the big kernels a chip holds
    assert shard_bytes["dp2xfsdp2"] < 0.6 * shard_bytes["dp4"], shard_bytes
    np.testing.assert_allclose(losses["dp4"], losses["dp2xfsdp2"], rtol=1e-2)

    sess = Session()  # the default: one dp axis over every device
    x = np.random.RandomState(1).randn(4, 1 << 16).astype(np.float32)
    out = sess.all_reduce(x)
    assert {s.device for s in out.addressable_shards} == set(devs)
    np.testing.assert_allclose(
        np.asarray(out), np.broadcast_to(x.sum(0), x.shape), rtol=1e-5,
        atol=1e-5)
    print(f"# Session.all_reduce[{sess.strategy.name}] over {sess.size} "
          "devices equals the numpy sum", flush=True)
    return {"device": device, "setup_s": setup_s, "step_ms": max(step_ms),
            "note": f"{PHASE_C_LAYERS}-layer LM, slower of two layouts; "
                    "layouts agree on the loss"}


def child(phase: str) -> int:
    sys.path.insert(0, ROOT)
    res = {"A": child_a, "C": child_c}[phase]()
    print(RESULT_TAG + json.dumps(res), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=0, choices=(0, 1, 4),
                    help="demand this many chips (4 turns 'not run' of the "
                         "four-chip phases into a failure)")
    ap.add_argument("--child", default="", choices=("", "A", "C"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return child(args.child)
    try:
        return parent(args)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
