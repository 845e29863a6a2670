"""Generators are reproducible from the seed; the open loop times from due."""
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from benchmark.lib import traffic as T

CHAT = {"kind": "open", "rate_per_s": 50.0,
        "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 1.0,
                       "min": 32, "max": 1536},
        "answer_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                       "min": 16, "max": 384}}


def test_same_seed_same_requests_and_due_times():
    a = T.open_schedule(CHAT, 50304, 2048, seed=7, seconds=10)
    b = T.open_schedule(CHAT, 50304, 2048, seed=7, seconds=10)
    c = T.open_schedule(CHAT, 50304, 2048, seed=8, seconds=10)
    assert a == b and a != c
    assert len(a) == len(c) == 500  # the count is fixed: 50/s over 10 s
    # ... and so, nearly, is the work: lengths are stratified
    for key in ("prompt", "max_new_tokens"):
        tot = [sum(len(r[key]) if key == "prompt" else r[key] for _, r in s)
               for s in (a, c)]
        assert abs(tot[0] - tot[1]) < 0.01 * tot[0], (key, tot)
    med = sorted(len(r["prompt"]) for _, r in a)[250]
    assert 250 <= med <= 262  # the mix's median prompt
    dues = [d for d, _ in a]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 10
    for _, r in a:
        assert 32 <= len(r["prompt"]) <= 1536 and 16 <= r["max_new_tokens"] <= 384
        assert len(r["prompt"]) + r["max_new_tokens"] <= 2048
        assert all(0 <= t < 50304 for t in r["prompt"])


def test_a_mix_can_fix_its_pattern_and_leave_the_tokens_to_the_seed():
    mix = dict(CHAT, schedule_seed=22)
    a = T.open_schedule(mix, 50304, 2048, seed=7, seconds=10)
    b = T.open_schedule(mix, 50304, 2048, seed=8, seconds=10)
    assert a == T.open_schedule(mix, 50304, 2048, seed=7, seconds=10)
    shape = [[(d, len(r["prompt"]), r["max_new_tokens"]) for d, r in s]
             for s in (a, b)]
    assert shape[0] == shape[1]  # same due times, same lengths
    assert all(ra["prompt"] != rb["prompt"] for (_, ra), (_, rb) in zip(a, b))
    free = T.open_schedule(CHAT, 50304, 2048, seed=7, seconds=10)
    assert [d for d, _ in free] != [d for d, _ in a]  # 7's own pattern differs


def test_bursts_and_shared_prefixes_are_data():
    mix = dict(CHAT, bursts={"size_min": 8, "size_max": 16},
               shared_prefix={"tokens": 16, "pool": 2})
    s = T.open_schedule(mix, 1000, 2048, seed=1, seconds=5)
    dues = [d for d, _ in s]
    assert len(set(dues)) < len(dues) / 7  # arrivals come in bursts
    heads = {tuple(r["prompt"][:16]) for _, r in s}
    assert len(heads) <= 2


def test_closed_loop_clients_draw_their_own_streams():
    mix = {"prompt_len": {"dist": "uniform", "min": 1024, "max": 1900},
           "answer_len": {"dist": "uniform", "min": 16, "max": 48}}
    a = T.RequestStream(mix, 50304, 2048, [3, 3, 0])
    b = T.RequestStream(mix, 50304, 2048, [3, 3, 0])
    c = T.RequestStream(mix, 50304, 2048, [3, 3, 1])
    ra, rb, rc = a.next(), b.next(), c.next()
    assert ra == rb and ra != rc
    assert 1024 <= len(ra["prompt"]) <= 1900 and 16 <= ra["max_new_tokens"] <= 48


def test_warmup_lengths_reach_every_power_of_two_bucket():
    assert T.warmup_prompt_lens({"prompt_len": {"min": 32, "max": 1536}}) == [
        32, 64, 128, 256, 512, 1024, 1536]
    assert T.warmup_prompt_lens({"prompt_len": {"min": 1024, "max": 1900}}) == [
        1024, 1900]


def test_reply_times_count_from_due():
    rec = {"t_due": 1.0, "t_sent": 1.5, "t_reply": 3.0, "ttft_ms": 100.0,
           "latency_ms": 1100.0, "new_tokens": 11}
    ttft, tpot = T.reply_times(rec)
    # 2,000 ms from due to reply, 1,000 ms of it after the first token
    assert abs(ttft - 1000.0) < 1e-6 and abs(tpot - 100.0) < 1e-6


class _SlowOneAtATime(BaseHTTPRequestHandler):
    """Answers one request at a time, 0.2 s each: the second of two requests
    due together waits for the first."""
    lock = threading.Lock()

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        t0 = time.monotonic()
        with self.lock:
            time.sleep(0.2)
        ms = (time.monotonic() - t0) * 1e3
        out = json.dumps({"id": body["id"], "status": "ok",
                          "tokens": body["prompt"] + [1] * body["max_new_tokens"],
                          "ttft_ms": ms - 1.0, "latency_ms": ms}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(out)))
        self.end_headers()
        self.wfile.write(out)


def test_open_loop_sends_on_schedule_and_times_from_due():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _SlowOneAtATime)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    try:
        url = f"http://127.0.0.1:{srv.server_address[1]}"
        req = {"prompt": [5, 6, 7], "max_new_tokens": 2}
        schedule = [(0.05, req), (0.05, req), (0.05, req)]
        records, _ = T.run_open_loop(url, schedule, seconds=1.0, drain_s=5.0,
                                     tag="t")
    finally:
        srv.shutdown()
        srv.server_close()
    assert len(records) == 3 and all(r["ok"] for r in records)
    assert all(abs(r["t_sent"] - 0.05) < 0.05 for r in records)  # not serialised
    ttfts = sorted(T.reply_times(r)[0] for r in records)
    # the third waited for two others: its first token is ~0.6 s after due
    assert 150 < ttfts[0] < 320 and 350 < ttfts[1] < 520 and 550 < ttfts[2] < 750
    s = T.summarize(records, 1.0)
    assert s["attempted"] == 3 and s["failed"] == 0
    assert s["serve_out_tokens_per_s"] == 6.0
    assert s["generator_late_ms_p50"] < 50


def test_an_unanswered_request_fails_and_misses_every_latency():
    records, _ = T.run_open_loop("http://127.0.0.1:9", [(0.0, {
        "prompt": [1], "max_new_tokens": 2})], seconds=0.2, drain_s=0.5, tag="x")
    s = T.summarize(records, 0.2)
    assert s["attempted"] == 1 and s["failed"] == 1 and s["ttft_p50_ms"] is None
