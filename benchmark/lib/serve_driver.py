"""Serving cells: the fleet as a user starts it, a client beside it.

    python -m kungfu_tpu.serving -np 1 --chips-per-host 1 --model-json ...

(router parent -> one worker child -> ServingEngine).  This module boots it,
warms the shapes the mix reaches, runs the open or closed loop for the
window, optionally captures a profile through the worker's /profile, reads
the worker's counters, stops the fleet and then, with the chip free, has a
checker child compare served tokens with the plain reference.
"""
from __future__ import annotations

import json
import os
import re
import sys
import threading
import time
import urllib.request

from . import traffic as T
from .configs import ROOT, load_json, program_fields
from .procs import Child, ChildFailed, fields

MONITOR_PORT_OFFSET = 16000  # kungfu_tpu/monitor/server.py


def http_json(url: str, timeout_s: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout_s) as r:
        return json.loads(r.read().decode())


def http_text(url: str, timeout_s: float = 10.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout_s) as r:
        return r.read().decode()


def parse_prometheus(text: str) -> dict:
    """{"events": {...}, "gauges": {...}, "hist": {name: {"sum", "count"}}}"""
    out = {"events": {}, "gauges": {}, "hist": {}}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        m = re.match(r'^(\w+)(?:\{([^}]*)\})?\s+(\S+)$', line)
        if not m:
            continue
        name, labels, value = m.group(1), m.group(2) or "", float(m.group(3))
        lab = dict(re.findall(r'(\w+)="([^"]*)"', labels))
        if name == "kungfu_events_total":
            out["events"][lab.get("event", "")] = value
        elif name == "kungfu_gauge":
            out["gauges"][lab.get("name", "")] = value
        elif name.endswith("_sum") or name.endswith("_count"):
            base, _, part = name.rpartition("_")
            out["hist"].setdefault(base, {})[part] = value
    return out


class Fleet:
    """The serving fleet under test."""

    def __init__(self, config: dict, seed: int, out_dir: str, rehearse: str = ""):
        dep = config["deployment"]
        self.slots = int(dep["slots"])
        env = dict(os.environ)
        # the worker's monitoring endpoint: /metrics, /programs and /profile
        env["KFT_CONFIG_ENABLE_MONITORING"] = "1"
        env["KFT_TRACE_DUMP_DIR"] = out_dir
        cmd = [sys.executable, "-m", "kungfu_tpu.serving", "-np", "1",
               "--model-json", json.dumps(program_fields(config)),
               "--slots", str(self.slots), "--seed", str(seed),
               "--queue-capacity", str(int(dep.get("queue_capacity", 1024))),
               "--worker-queue-capacity",
               str(int(dep.get("worker_queue_capacity", 1024))),
               "--timeout", "3000"]
        if rehearse:
            cmd += ["--platform", rehearse]
        else:
            cmd += ["--chips-per-host", str(dep["chips"])]
        self.child = Child("serve", cmd, cwd=ROOT, env=env)
        self.url = self.worker_url = self.monitor_url = ""
        self.ready = {}
        self.boot_s = None

    def wait_ready(self, timeout_s: float) -> None:
        _, line = self.child.wait_for_line(r"SERVE_URL: ", 120)
        self.url = line.split("SERVE_URL: ")[1].strip()
        # the supervisor respawns a worker that dies for ever: a death
        # before the first READY line (out of memory at boot) ends the run
        self.boot_s, line = self.child.wait_for_line(
            r"SERVE_WORKER_READY:|serving worker \S+ died", timeout_s)
        if "SERVE_WORKER_READY:" not in line:
            tail = "\n".join(text for _, text in self.child.snapshot()[-40:])
            raise ChildFailed(f"the serving worker died at boot:\n{tail}")
        self.ready = fields(line)
        self.worker_url = self.ready["url"]
        host, port = self.worker_url.rsplit(":", 1)
        self.monitor_url = f"{host}:{int(port) + MONITOR_PORT_OFFSET}"
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:  # the router's probe has seen it
            stats = http_json(self.url + "/stats")
            if any(w["healthy"] for w in stats["workers"].values()):
                return
            time.sleep(0.05)
        raise ChildFailed("the router never saw a healthy worker")

    def device(self) -> dict:
        return {"platform": self.ready["platform"],
                "kind": self.ready["device_kind"],
                "count": int(self.ready["devices"])}

    def scrape(self) -> dict:
        out = parse_prometheus(http_text(self.monitor_url + "/metrics"))
        out["watch"] = http_json(self.monitor_url + "/programs")["watch"]
        out["engine"] = http_json(self.worker_url + "/healthz")
        return out

    def hbm_peak_sampled(self) -> int:
        """Largest `hbm_bytes_in_use` the worker's sampler saw (the worker
        does not report `peak_bytes_in_use`; see PERF.md section 7)."""
        snap = http_json(self.monitor_url + "/history?series=gauge:hbm_bytes_in_use")
        best = 0.0
        for series in (snap.get("series") or {}).values():
            best = max([best] + [float(v) for _, v in series.get("fine") or []]
                       + [float(row[3]) for row in series.get("coarse") or []])
        return int(best)

    def stop(self) -> None:
        self.child.stop()


def warm_up(fleet: Fleet, traffic: dict, vocab: int, seed: int) -> dict:
    """One request for each prefill shape the mix reaches (which also runs
    the decode program and the slot programs), and the fixed request twice:
    greedy decoding must answer it with the same tokens."""
    import numpy as np

    rng = np.random.default_rng([seed, 9])
    lens = T.warmup_prompt_lens(traffic)
    for n in lens:
        req = {"prompt": rng.integers(0, vocab, size=n).tolist(),
               "max_new_tokens": 2, "id": f"warm-{n}"}
        doc = T.post_generate(fleet.url, req, 900)
        if doc.get("status") != "ok":
            raise ChildFailed(f"warm-up request of {n} tokens: {doc}")
    fixed = {"prompt": rng.integers(0, vocab, size=lens[0]).tolist(),
             "max_new_tokens": 8}
    a = T.post_generate(fleet.url, dict(fixed, id="fixed-a"), 900)
    b = T.post_generate(fleet.url, dict(fixed, id="fixed-b"), 900)
    return {"warm_lens": lens,
            "replay_identical": a.get("tokens") == b.get("tokens")
            and len(a.get("tokens", [])) == lens[0] + 8}


def hist_delta(after: dict, before: dict, name: str):
    a, b = after["hist"].get(name), before["hist"].get(name, {})
    if not a:
        return None
    n = a.get("count", 0) - b.get("count", 0)
    if n <= 0:
        return None
    return {"count": n, "mean": (a.get("sum", 0) - b.get("sum", 0)) / n}


def check_served(config_path: str, seed: int, served, out_dir: str,
                 rehearse: str) -> dict:
    """With the fleet stopped and the chip free: the checker child
    (serve_check.py) on a few served requests."""
    if not served:
        return {"ok": False, "error": "no served request to check"}
    job = {"config": config_path, "seed": seed,
           "out": os.path.join(out_dir, "check.json"),
           "served": [{"id": r["id"], "prompt_len": r["prompt_len"],
                       "tokens": r["prompt"] + r["new"]} for r in served]}
    job_path = os.path.join(out_dir, "check_job.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    env = dict(os.environ)
    if rehearse:
        env["JAX_PLATFORMS"] = rehearse
    here = os.path.dirname(os.path.abspath(__file__))
    child = Child("check", [sys.executable, os.path.join(here, "serve_check.py"),
                            job_path], cwd=ROOT, env=env)
    rc = child.wait(600)
    if rc != 0 or not os.path.exists(job["out"]):
        return {"ok": False, "error": f"the checker exited {rc}"}
    return load_json(job["out"])


def run_serving_cell(cell: dict, config_path: str, traffic_path: str, seed: int,
                     seconds: float, trace: bool, out_dir: str, t0: float,
                     rehearse: str = "") -> dict:
    config, traffic = load_json(config_path), load_json(traffic_path)
    vocab, max_len = config["vocab_size"], config["max_position_embeddings"]
    fleet = Fleet(config, seed, out_dir, rehearse)
    try:
        fleet.wait_ready(1500)
        device = fleet.device()
        if device["platform"] != "tpu" and device["platform"] != rehearse:
            raise ChildFailed(f"the benchmark needs a TPU; the worker found "
                              f"{device['platform']}")
        if device["count"] != cell["chips"]:
            raise ChildFailed(f"the cell asks for {cell['chips']} chip(s); "
                              f"the worker found {device['count']}")
        t_ready = time.time()
        warm = warm_up(fleet, traffic, vocab, seed)
        before = fleet.scrape()
        setup_s = time.time() - t0
        drain_s = float(traffic.get("drain_s", 30.0))
        profile = {}
        if trace:
            secs = float(traffic.get("trace_seconds", 3.0))
            at = float(traffic.get("trace_at_share", 0.4)) * seconds

            def capture():
                time.sleep(at)
                try:
                    profile.update(http_json(
                        fleet.monitor_url + f"/profile?secs={secs}", secs + 120))
                except OSError as e:
                    profile.update(ok=False, error=str(e))

            prof_thread = threading.Thread(target=capture, daemon=True)
            prof_thread.start()
        if traffic["kind"] == "open":
            schedule = T.open_schedule(traffic, vocab, max_len, seed, seconds)
            records, _ = T.run_open_loop(fleet.url, schedule, seconds, drain_s,
                                         f"s{seed}")
        elif traffic["kind"] == "closed":
            clients = traffic["clients"]
            clients = fleet.slots if clients == "slots" else int(clients)
            records, _ = T.run_closed_loop(fleet.url, traffic, vocab, max_len,
                                           seed, clients, seconds, drain_s,
                                           f"s{seed}")
        else:
            raise ChildFailed(f"traffic kind {traffic['kind']!r} is not a "
                              "serving kind")
        if trace:
            prof_thread.join(timeout=180)
        after = fleet.scrape()
        hbm = fleet.hbm_peak_sampled()
    finally:
        fleet.stop()
    values = T.summarize(records, seconds)
    values.update(
        setup_s=setup_s, worker_boot_s=fleet.boot_s,
        warmup_s=setup_s - (t_ready - t0),
        compile_s=before["watch"]["compile_ms"] / 1e3,
        cache_hits=before["watch"]["cache_hits"],
        compiles_setup=before["watch"]["compiles"],
        compiles_in_window=after["watch"]["compiles"] - before["watch"]["compiles"],
        peak_hbm_bytes=hbm, slots=fleet.slots,
        replay_identical=warm["replay_identical"],
    )
    for name in ("tok_latency_ms", "prefill_ms"):
        d = hist_delta(after, before, name)
        if d:
            values[name + "_mean"] = d["mean"]
            values[name + "_count"] = d["count"]
    if trace and not profile.get("path"):
        raise ChildFailed(f"the worker's /profile gave no trace: {profile}")
    # the four shortest served requests: the reference holds them easily
    served = sorted((r for r in records if r.get("ok")),
                    key=lambda r: r["prompt_len"] + r["new_tokens"])[:4]
    check = check_served(config_path, seed, served, out_dir, rehearse)
    values["reference_max_deficit"] = check.get("max_deficit")
    correct = bool(values["failed"] == 0 and values["attempted"] > 0
                   and values["replay_identical"] and check.get("ok")
                   and values["compiles_in_window"] == 0)
    return {"device": dict(device, memory_peak_bytes=hbm), "values": values,
            "correct": correct, "attempted": values["attempted"],
            "failed": values["failed"], "rehearsal": bool(rehearse),
            "trace_dir": profile.get("path", ""),
            "detail": {"check": check, "profile": profile,
                       "warm_lens": warm["warm_lens"], "engine": after["engine"]}}
