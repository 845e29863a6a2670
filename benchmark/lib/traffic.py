"""The one general traffic generator, and the arithmetic on what comes back.

A traffic mix is a data file `benchmark/traffic/<name>.json`:

  {"kind": "train", "seq_len": 2048, ...}            token batches (train_worker.py)
  {"kind": "open",  "rate_per_s": 4.0, ...}          Poisson arrivals at a fixed rate
  {"kind": "closed", "clients": "slots", ...}        each client sends when answered

Serving mixes give `prompt_len` and `answer_len` as a distribution:
  {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32, "max": 1536}
  {"dist": "uniform", "min": 1024, "max": 1900}
and optionally `bursts: {"size_min": 8, "size_max": 16}` (arrivals come in
bursts at the same mean rate) and `shared_prefix: {"tokens": 1024, "pool": 4}`
(each prompt starts with one of `pool` fixed prefixes).  Every request comes
from the seed alone: same seed, same requests, same due times; and an open
mix offers every seed the same amount of work (see `open_schedule`).  An open
mix may fix its pattern: with `schedule_seed: N` the due times and the lengths
come from N, the same in every run, and `--seed` draws only the tokens.
"""
from __future__ import annotations

import http.client
import json
import math
import threading
import time
import urllib.parse
from statistics import NormalDist

import numpy as np


def length_quantile(spec: dict, u: float) -> int:
    """The length at quantile `u` of a mix's length distribution."""
    if spec["dist"] == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(u))
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return int(min(max(int(x), spec.get("min", 1)), spec.get("max", int(x))))


def draw_len(rng, spec: dict) -> int:
    return length_quantile(spec, float(rng.uniform(1e-9, 1 - 1e-9)))


class RequestStream:
    """Requests of one mix from one RNG stream: call `next()`.  With
    `count`, lengths are stratified: one from each of `count` equal slices of
    the distribution, in an order drawn from the seed, so that every seed
    asks for nearly the same number of tokens.  With `shape_key`, the
    lengths (and which shared prefix a prompt takes) come from a stream of
    their own, so that they can stay the same while the tokens change."""

    def __init__(self, traffic: dict, vocab: int, max_len: int, seed_key,
                 count: int = 0, shape_key=None):
        self.t, self.vocab, self.max_len = traffic, vocab, max_len
        self.rng = np.random.default_rng(seed_key)
        self.shape_rng = (self.rng if shape_key is None
                          else np.random.default_rng(shape_key))
        self.strata = None
        if count:
            self.strata = {k: iter((self.shape_rng.permutation(count)
                                    + self.shape_rng.uniform(size=count)) / count)
                           for k in ("prompt_len", "answer_len")}
        sp = traffic.get("shared_prefix")
        self.prefixes = None
        if sp:
            prng = np.random.default_rng([int(seed_key[0]), 0x5eed])
            self.prefixes = prng.integers(0, vocab, size=(sp["pool"], sp["tokens"]))

    def _len(self, key: str) -> int:
        if self.strata is None:
            return draw_len(self.shape_rng, self.t[key])
        return length_quantile(self.t[key], float(next(self.strata[key])))

    def next(self) -> dict:
        n_prompt = self._len("prompt_len")
        n_new = max(1, min(self._len("answer_len"), self.max_len - n_prompt))
        prompt = self.rng.integers(0, self.vocab, size=n_prompt)
        if self.prefixes is not None:
            pre = self.prefixes[self.shape_rng.integers(len(self.prefixes))]
            k = min(len(pre), n_prompt - 1)
            prompt[:k] = pre[:k]
        return {"prompt": prompt.tolist(), "max_new_tokens": int(n_new)}


def open_schedule(traffic: dict, vocab: int, max_len: int, seed: int,
                  seconds: float, rate_per_s=None):
    """[(due seconds, request)] over [0, seconds): Poisson arrivals at the
    mix's fixed rate, conditioned on their count.  Every seed offers the
    same amount of work: round(rate x seconds) requests (a Poisson process
    with its count given has independent uniform arrival times) with
    stratified lengths; what the seed draws is when each arrives and which
    length it has.  With `bursts`, arrivals come in groups at the same mean
    rate.  With `schedule_seed`, the mix fixes that pattern too: due times
    and lengths are drawn from it, the same in every run, and `seed` draws
    the tokens alone.  A request's time between tokens turns on how many
    prefills interrupt it, so the pattern a seed drew was part of what
    differed between two runs of one code (PERF.md section 6)."""
    rate = float(rate_per_s if rate_per_s is not None else traffic["rate_per_s"])
    fixed = traffic.get("schedule_seed")
    pattern = seed if fixed is None else int(fixed)
    rng = np.random.default_rng([pattern, 1])
    n = max(1, round(rate * seconds))
    stream = RequestStream(traffic, vocab, max_len, [seed, 2], count=n,
                           shape_key=None if fixed is None else [pattern, 4])
    bursts = traffic.get("bursts")
    sizes = []
    while sum(sizes) < n:
        size = (int(rng.integers(bursts["size_min"], bursts["size_max"] + 1))
                if bursts else 1)
        sizes.append(min(size, n - sum(sizes)))
    dues = np.sort(rng.uniform(0.0, seconds, size=len(sizes)))
    return [(float(t), stream.next()) for t, size in zip(dues, sizes)
            for _ in range(size)]


def warmup_prompt_lens(traffic: dict) -> list:
    """Prompt lengths that between them reach every prefill shape the mix
    can: its shortest and longest prompt and every power of two between
    (the program pads prompts to power-of-two buckets)."""
    lo = int(traffic["prompt_len"]["min"])
    hi = int(traffic["prompt_len"]["max"])
    lens = {lo, hi}
    p = 1
    while p <= hi:
        if lo <= p <= hi:
            lens.add(p)
        p *= 2
    return sorted(lens)


# -- arithmetic ------------------------------------------------------------------------


def percentile(values, q: float):
    """Linear-interpolated percentile of a list; None when it is empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def reply_times(rec: dict):
    """(ttft_ms, tpot_ms) a client saw for one answered request.

    The worker's `latency_ms` and `ttft_ms` start at its receipt of the
    request; the client's clock starts when the request was DUE.  Replies do
    not stream, so the first token's time is reconstructed: the round trip
    less what the worker spent after its first token."""
    rtt_ms = (rec["t_reply"] - rec["t_due"]) * 1e3
    after_first = rec["latency_ms"] - rec["ttft_ms"]
    ttft = rtt_ms - after_first
    tpot = after_first / (rec["new_tokens"] - 1) if rec["new_tokens"] > 1 else None
    return ttft, tpot


# -- the client ------------------------------------------------------------------------


def post_generate(url: str, body: dict, timeout_s: float) -> dict:
    """One POST /v1/generate; raises on anything but a 200 with JSON."""
    u = urllib.parse.urlsplit(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout_s)
    try:
        conn.request("POST", "/v1/generate", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        data = r.read()
        if r.status != 200:
            raise OSError(f"HTTP {r.status}: {data[:120]!r}")
        return json.loads(data)
    finally:
        conn.close()


def _send(url, rid, req, t_open, t_due, timeout_s, records, lock):
    t_sent = time.monotonic() - t_open
    rec = {"id": rid, "t_due": t_due, "t_sent": t_sent, "ok": False,
           "prompt_len": len(req["prompt"]), "asked": req["max_new_tokens"],
           "prompt": req["prompt"]}
    try:
        doc = post_generate(url, dict(req, id=rid), timeout_s)
        rec["t_reply"] = time.monotonic() - t_open
        toks = doc.get("tokens") or []
        n = len(req["prompt"])
        rec.update(
            status=doc.get("status"), ttft_ms=doc.get("ttft_ms"),
            latency_ms=doc.get("latency_ms"), new_tokens=len(toks) - n,
            echo=toks[:n] == req["prompt"], new=toks[n:])
        rec["ok"] = bool(doc.get("status") == "ok" and rec["echo"]
                         and rec["new_tokens"] == req["max_new_tokens"]
                         and rec["ttft_ms"] is not None)
    except (OSError, ValueError) as e:
        rec["error"] = str(e)[:200]
    with lock:
        records.append(rec)


def run_open_loop(url: str, schedule, seconds: float, drain_s: float, tag: str):
    """Send each request at its due time whatever the system does (one thread
    for each request in flight); a request unanswered `drain_s` after the
    window is failed.  Returns the records."""
    records, lock, threads = [], threading.Lock(), []
    t_open = time.monotonic()
    deadline = seconds + drain_s
    for k, (due, req) in enumerate(schedule):
        wait = due - (time.monotonic() - t_open)
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(
            target=_send, daemon=True,
            args=(url, f"{tag}-{k}", req, t_open, due,
                  max(1.0, deadline - due), records, lock))
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=max(0.0, deadline - (time.monotonic() - t_open)))
    with lock:
        done = {r["id"] for r in records}
        for k, (due, req) in enumerate(schedule):
            if f"{tag}-{k}" not in done:
                records.append({"id": f"{tag}-{k}", "t_due": due, "ok": False,
                                "error": "unanswered at the end of the drain",
                                "prompt_len": len(req["prompt"]),
                                "asked": req["max_new_tokens"]})
        return list(records), t_open


def run_closed_loop(url: str, traffic: dict, vocab: int, max_len: int, seed: int,
                    clients: int, seconds: float, drain_s: float, tag: str):
    """`clients` callers, each sending its next request when the last was
    answered, until the window ends; requests in flight then are waited for
    `drain_s`.  Each client draws from its own stream of the seed."""
    records, lock = [], threading.Lock()
    t_open = time.monotonic()
    deadline = seconds + drain_s

    def client(c: int) -> None:
        stream = RequestStream(traffic, vocab, max_len, [seed, 3, c])
        k = 0
        while True:
            now = time.monotonic() - t_open
            if now >= seconds:
                return
            _send(url, f"{tag}-{c}-{k}", stream.next(), t_open, now,
                  max(1.0, deadline - now), records, lock)
            k += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=max(0.0, deadline + 5.0 - (time.monotonic() - t_open)))
    with lock:
        return list(records), t_open


def summarize(records, seconds: float) -> dict:
    """Client-side numbers of one window, from the records of the requests
    due in it."""
    ok = [r for r in records if r.get("ok")]
    ttft, tpot, overhead = [], [], []
    for r in ok:
        a, b = reply_times(r)
        ttft.append(a)
        if b is not None:
            tpot.append(b)
        overhead.append((r["t_reply"] - r["t_sent"]) * 1e3 - r["latency_ms"])
    late = [(r["t_sent"] - r["t_due"]) * 1e3 for r in records if "t_sent" in r]
    in_window = [r for r in ok if r["t_reply"] <= seconds]
    backlog = sum(1 for r in records
                  if not (r.get("ok") and r["t_reply"] <= seconds))
    return {
        "attempted": len(records), "failed": len(records) - len(ok),
        "answered_in_window": len(in_window),
        "backlog_at_window_end": backlog,
        "out_tokens_in_window": sum(r["new_tokens"] for r in in_window),
        "prompt_tokens_in_window": sum(r["prompt_len"] for r in in_window),
        "serve_out_tokens_per_s": sum(r["new_tokens"] for r in in_window) / seconds,
        "requests_per_s": len(in_window) / seconds,
        "ttft_p50_ms": percentile(ttft, 50), "ttft_p90_ms": percentile(ttft, 90),
        "tpot_p50_ms": percentile(tpot, 50), "tpot_p90_ms": percentile(tpot, 90),
        "router_overhead_ms_p50": percentile(overhead, 50),
        "generator_late_ms_p50": percentile(late, 50),
        "generator_late_ms_max": max(late) if late else None,
        "errors": sorted({r.get("error", "") for r in records
                          if not r.get("ok")})[:5],
    }
