"""One serving rank — `python -m kungfu_tpu.serving.worker`.

A worker owns one ServingEngine replica and exposes it over HTTP:

  POST /generate   one Request in, blocks until its Result (the router holds
                   one connection per in-flight request, so worker-side
                   concurrency == open connections == busy slots); 503 on
                   backpressure, 400 on a request that can never fit.  On a
                   PREFILL-tier worker this runs the prefill half only and
                   proxies the rest: finished KV ships to a decode rank
                   (ops/kv_ship packed blob -> POST /kv_ship) and the final
                   result comes back through GET /kv_result
  POST /kv_ship    shipped prefill KV in (decode tier): graft-admit into a
                   slot when one frees; acks {ok} immediately so the ship
                   latency (`kv_ship_ms`) measures transfer + admission,
                   not the decode.  503 on backpressure; re-ships of a
                   known request dedupe (double-serve guard)
  GET  /kv_result?id=R   blocks until request R's Result (the prefill
                   worker's proxy read)
  GET  /healthz    engine stats + tier — the router's health probe and the
                   prefill tier's decode-pool picker signal
  GET  /weights    this replica's params as a resilience.buddy snapshot blob
                   (the sub-second rejoin path: a respawned rank pulls
                   weights from a live peer instead of re-initializing).
                   It is the engine's RESIDENT tree (bf16 leaves where the
                   programs read bf16): exactly what this replica computes
                   with, and installing it elsewhere changes nothing more
  POST /warm       warm-state ship from a peer: its in-flight requests'
                   generated-so-far tokens, held here so the router can
                   resume them if that peer dies
  GET  /warm?origin=R   the warm set shipped by rank R (the router reads a
                   dead rank's buddy to resume its streams mid-output)

Serving v2 flags: `--tier prefill|decode` joins a disaggregated fleet (the
supervisor reads the document's tier map); `--prefix-cache on|off|auto`
arms the radix prefix KV cache (auto = the KFT_PREFIX_CACHE_MB budget,
prefill + monolithic tiers only); `--spec-draft PRESET --spec-k K` arms
speculative decoding with a draft model from the zoo presets ("same" =
self-draft with the target's own params — the mechanics A/B used by the
bench; "mtp" = the target's own multi-token-prediction module, `mtp_layers`
1 in the model JSON, verify width 2; decode + monolithic tiers only).

Weight resolution at boot climbs a serving flavor of the recovery ladder
(docs/serving.md): buddy (live peer fetch over HTTP, rejoins only) ->
file (--weights-file pickle, e.g. exported from a training checkpoint) ->
seed (deterministic init).  The rung lands in the `rank_rejoined` journal
event, the acceptance signal of the serve drill.  `seed_params` and the
weights file are the float32 checkpoint form; whatever the rung, the tree
is converted once to the resident form (models/transformer.py
`resident_params`) before the engine or a self-draft sees it, and
/healthz (`param_bytes`) and /metrics (`kft_serve_param_bytes{dtype}`) say
what the chip then holds.

Chaos: the decode loop calls ChaosInjector.on_serve_tokens after every
engine iteration — and the prefill handler after every prefill, with the
prefilled-token counter — so `crash_serve@tokens=N:rank=R[:tier=T]` kills
this process mid-stream with requests in flight on either tier.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pickle
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional

from ..utils import get_logger
from ..utils import trace as T

log = get_logger("kungfu.serving")

# compact model presets for drills/benches; --model-json overrides fields
PRESETS: Dict[str, dict] = {
    "tiny": dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
                 max_len=96, n_kv_heads=2),
    "small": dict(vocab_size=256, d_model=128, n_layers=4, n_heads=8,
                  d_ff=256, max_len=512, n_kv_heads=4),
}


def build_config(preset: str, overrides_json: str = ""):
    import jax.numpy as jnp

    from ..models.transformer import TransformerConfig

    kw = dict(PRESETS[preset])
    kw.update(rope=True, attention="full", dtype=jnp.float32, norm="rms",
              ffn="swiglu")
    if overrides_json:
        kw.update(json.loads(overrides_json))
    return TransformerConfig(**kw)


def seed_params(cfg, seed: int = 0):
    """Deterministic params — identical on every rank for a given seed, so
    data-parallel replicas agree without any weight exchange.  Float32, the
    checkpoint form (the benchmark's checker seeds its reference with it);
    the engine keeps its own resident form of them."""
    import jax
    import jax.numpy as jnp
    import flax.linen as nn

    from ..models.transformer import TransformerLM

    model = TransformerLM(cfg)
    probe = jnp.zeros((1, 4), jnp.int32)
    return nn.meta.unbox(model.init(jax.random.PRNGKey(seed), probe)["params"])


def _to_numpy(tree):
    import jax
    import numpy as np

    return jax.tree.map(lambda x: np.asarray(x), tree)


class WarmStore:
    """Warm-resume state held FOR peers: {origin_rank: {req_id: item}}.
    Bounded per origin — the shipping side only ever has `slots` requests in
    flight, so the bound is belt-and-braces against a looping shipper."""

    def __init__(self, per_origin_cap: int = 64):
        self._lock = threading.Lock()
        self._by_origin: Dict[int, Dict[str, dict]] = {}
        self._cap = per_origin_cap

    def put(self, origin: int, items: List[dict]) -> None:
        with self._lock:
            # full replacement: the ship is a snapshot of CURRENT in-flight
            # work; completed requests must drop out so a resume can't
            # resurrect them
            self._by_origin[origin] = {
                it["id"]: it for it in items[: self._cap]
            }

    def get(self, origin: int) -> List[dict]:
        with self._lock:
            return list(self._by_origin.get(origin, {}).values())


class ServingWorker:
    def __init__(self, args):
        from ..chaos.inject import injector_from_env
        from ..monitor.counters import counters_if_enabled
        from ..monitor.journal import journal_event, set_journal_context

        self.args = args
        self.rank = args.launch_rank
        self.incarnation = args.incarnation
        self.tier = getattr(args, "tier", "") or ""
        set_journal_context(rank=self.rank, identity=f"serve-{self.rank}")
        self.counters = counters_if_enabled()
        self.injector = injector_from_env()
        self.warm = WarmStore()
        self._stop = threading.Event()
        self._peer_cache: tuple = (0.0, [])  # (fetched_at, urls)
        self._ship_pending: Dict[str, Any] = {}  # req_id -> engine _Pending
        self._ship_lock = threading.Lock()

        from ..models.transformer import resident_params

        import jax

        cfg = build_config(args.preset, args.model_json)
        # a model with recurrent layers keeps a state a slot, which no
        # position cuts: nothing built on rows alone is armed for it, and
        # asking for it ends the boot here, before any weight is made
        # (serving/slots.py STATE_LEAVES; the engine refuses them too)
        stateful = cfg.keeps_state
        asked = [flag for flag, on in (
            ("--prefix-cache on", args.prefix_cache == "on"),
            ("--spec-draft", bool(getattr(args, "spec_draft", ""))),
            ("--tier", bool(self.tier))) if on]
        if stateful and asked:
            raise SystemExit(
                f"{', '.join(asked)}: not with a model that keeps recurrent "
                "state (state-space, lightning or block-selected layers): a "
                "prefix hit needs a snapshot of "
                "the state at the hit length, a rejected draft a way to roll "
                "it back, a shipped prefill rows to ship; none exists yet "
                "(ROADMAP R6)")
        t0 = time.monotonic()
        # boot phases end when the device has done their work, not when the
        # host has dispatched it: each then holds what it names
        ladder: Dict[str, Any] = {}
        with T.trace_scope("boot:weights", cat=T.BOOT_CAT, args=ladder):
            params, rung = self._resolve_weights(cfg)
            ladder["rung"] = rung
            jax.block_until_ready(params)
        # the rungs give the checkpoint form; the resident form is what
        # this process computes with, the engine and a self-draft alike.
        # Nothing else reads the tree a rung made, so each float32 leaf goes
        # as its narrower copy arrives and the chip never holds both
        with T.trace_scope("boot:resident", cat=T.BOOT_CAT):
            params = jax.block_until_ready(
                resident_params(cfg, params, donate=True))
        restore_s = time.monotonic() - t0
        self.weight_rung = rung
        if self.incarnation > 0:
            journal_event("rank_rejoined", rank=self.rank,
                          incarnation=self.incarnation, recovery_rung=rung,
                          tier=self.tier, restore_s=round(restore_s, 3))
            if self.counters is not None:
                self.counters.inc_event(f"serve_rejoin_{rung}")
                self.counters.set_gauge("serve_restore_s", restore_s)
        log.info("worker rank=%d incarnation=%d tier=%s weights=%s (%.2fs)",
                 self.rank, self.incarnation, self.tier or "-", rung,
                 restore_s)

        from .engine import ServingEngine

        prefix = None
        if not stateful and self.tier != "decode" \
                and getattr(args, "prefix_cache", "auto") != "off":
            from .prefix import PrefixCache, prefix_cache_if_enabled

            if args.prefix_cache == "on":
                prefix = PrefixCache(counters=self.counters)
            else:  # auto: the env budget decides
                prefix = prefix_cache_if_enabled(counters=self.counters)
        spec = None
        draft_name = getattr(args, "spec_draft", "") or ""
        if draft_name == "mtp" and self.tier != "prefill":
            # the target's own prediction module (`mtp_layers` 1 in the
            # model JSON): part of `params`, verify width 2 whatever
            # --spec-k says
            from .spec import MTPDrafter

            spec = MTPDrafter(cfg, params, slots=args.slots,
                              counters=self.counters)
        elif draft_name and self.tier != "prefill":
            from .spec import SpecDecoder, build_draft

            if draft_name == "same":
                draft_cfg, draft_params = cfg, params
            else:
                draft_cfg, draft_params = build_draft(draft_name,
                                                      seed=args.seed)
            assert draft_cfg.vocab_size == cfg.vocab_size, (
                "draft and target must share a vocab")
            spec = SpecDecoder(draft_cfg, draft_params, slots=args.slots,
                               k=args.spec_k, counters=self.counters)
        from .tenancy import TenantRegistry

        # workers inherit KFT_TENANTS_FILE through the environment; when
        # unset this is None and the engine keeps its v1 FIFO queue
        tenants = TenantRegistry.from_env()
        self.engine = ServingEngine(
            cfg, params, slots=args.slots,
            queue_capacity=args.queue_capacity, counters=self.counters,
            prefix_cache=prefix, spec=spec, tenants=tenants,
        )
        if self.counters is not None:
            from ..parallel.moe import stats_families

            self.counters.add_source(
                lambda: stats_families(self._moe_stats()))
            self.counters.add_source(lambda: {"kft_serve_param_bytes": {
                f'dtype="{name}"': n
                for name, n in self.engine.param_bytes.items()}})
            self.counters.add_source(lambda: {
                family: {f'kind="{kind}"': n for kind, n in rows().items()}
                for family, rows in (
                    ("kft_serve_decode_attn_rows_total", self._attn_rows),
                    ("kft_serve_decode_rows_total",
                     self.engine.decode_rows),
                    ("kft_serve_decode_steps_total",
                     self.engine.decode_steps),
                    ("kft_serve_cache_bytes",
                     lambda: self.engine.cache_bytes),
                    ("kft_serve_scan_tokens_total",
                     self.engine.scan_tokens),
                    ("kft_serve_sparse_rows_total",
                     self.engine.sparse_rows))})
        self.decode_pool = None
        if self.tier == "prefill" and args.config_server:
            from ..elastic.config_client import ConfigClient
            from .disagg import DecodePool

            self.decode_pool = DecodePool(
                ConfigClient(args.config_server, retries=2,
                             retry_deadline_s=3.0),
                self_spec=f"{args.host}:{args.port}",
            )
        # the blob served on /weights: packed at the first request, once
        # (params are immutable).  Packing at boot held three host copies
        # of the parameters, which a 10.9 GB model does not leave room for
        self._weights_blob: Optional[bytes] = None
        self._weights_lock = threading.Lock()

    def _weights(self) -> bytes:
        from ..resilience.buddy import pack_snapshot

        with self._weights_lock:
            if self._weights_blob is None:
                self._weights_blob = pack_snapshot(
                    step=self.incarnation, offset=0,
                    state={"params": _to_numpy(self.engine.params)},
                    origin_rank=self.rank, cluster_version=0,
                ).tobytes()
            return self._weights_blob

    def _attn_rows(self) -> Dict[str, int]:
        """The engine's five kinds of cache rows and, beside them, those a
        drafter counts for its own cache (spec.py `attn_rows`)."""
        rows = self.engine.decode_attn_rows()
        if self.engine.spec is not None:
            rows.update(self.engine.spec.attn_rows())
        return rows

    def _moe_stats(self, refresh: bool = True):
        """The experts' counts (parallel/moe.py's `stats_*` turn them into
        /metrics families and a /healthz block), read from the device now
        or, with `refresh=False`, as the last read left them; None for a
        dense model."""
        from ..parallel.moe import STATS

        return self.engine.device_counters(refresh).get(STATS)

    # -- weight ladder -------------------------------------------------------------

    def _resolve_weights(self, cfg):
        from ..resilience.buddy import buddy_enabled

        if self.incarnation > 0 and self.args.config_server and buddy_enabled():
            got = self._fetch_buddy_weights()
            if got is not None:
                return got, "buddy"
        if self.args.weights_file:
            try:
                with open(self.args.weights_file, "rb") as f:
                    return pickle.load(f), "file"
            except (OSError, pickle.PickleError) as e:
                log.warning("weights file unusable (%s); falling to seed", e)
        return seed_params(cfg, self.args.seed), "seed"

    def _peer_urls(self, max_age_s: float = 2.0) -> List[str]:
        """Live peers (not self) from the cluster document, ring-buddy
        first — the same ring-offset preference the training ladder uses.
        Cached for `max_age_s`: the warm shipper calls this several times a
        second and the document rarely moves."""
        from ..elastic.config_client import ConfigClient

        t, urls = self._peer_cache
        if time.monotonic() - t < max_age_s:
            return urls
        try:
            got = ConfigClient(self.args.config_server,
                               retries=2, retry_deadline_s=3.0).get_cluster()
        except OSError:
            return []
        if got is None:
            return []
        workers, _ = got[0].workers, got[1]
        self_spec = f"{self.args.host}:{self.args.port}"
        urls = [f"http://{p.host}:{p.port}" for p in workers
                if str(p) != self_spec]
        my_idx = next((i for i, p in enumerate(workers)
                       if str(p) == self_spec), None)
        if my_idx is not None and len(workers) > 1:
            buddies = workers.ring_buddies()
            b = workers[buddies[my_idx]]
            burl = f"http://{b.host}:{b.port}"
            if burl in urls:
                urls.remove(burl)
                urls.insert(0, burl)
        self._peer_cache = (time.monotonic(), urls)
        return urls

    def _fetch_buddy_weights(self):
        from ..resilience.buddy import unpack_snapshot

        for url in self._peer_urls():
            try:
                with urllib.request.urlopen(
                    url + "/weights", timeout=self.args.buddy_timeout_s
                ) as r:
                    blob = r.read()
            except OSError as e:
                log.info("buddy weights from %s failed: %s", url, str(e)[:120])
                continue
            import numpy as np

            snap = unpack_snapshot(np.frombuffer(blob, dtype=np.uint8))
            if snap is not None and "params" in snap.get("state", {}):
                log.info("weights restored from buddy %s", url)
                return snap["state"]["params"]
        return None

    # -- loops ---------------------------------------------------------------------

    def _chaos_tick(self) -> None:
        """Feed the injector the tier-appropriate progress counter: decode
        and monolithic workers count generated tokens, prefill workers
        count prefilled tokens (they generate only the first token)."""
        if self.injector is None:
            return
        total = (self.engine.total_prefill_tokens if self.tier == "prefill"
                 else self.engine.total_tokens)
        self.injector.on_serve_tokens(total, self.rank, tier=self.tier)

    def _chaos_phase(self, phase: str) -> None:
        """slow_serve@phase=... hook: an armed per-phase delay sleeps here,
        just before the named serving phase runs (chaos/plan.py)."""
        if self.injector is not None:
            self.injector.on_serve_phase(phase, self.rank, tier=self.tier)

    def _engine_loop(self) -> None:
        last_ship = 0.0
        engine = self.engine
        # one `serve:idle` span for each stretch with no queue and no active
        # slot (not one for each 2 ms sleep), closed when work arrives: a
        # profile then tells a device idle for lack of load from one the
        # engine keeps waiting
        with contextlib.ExitStack() as idle:
            idling = False
            while not self._stop.is_set():
                if idling and (engine.queue.depth()
                               or engine.slot_mgr.active_count):
                    idle.close()
                    idling = False
                self._chaos_phase("decode")
                done = engine.step()
                self._chaos_tick()
                now = time.monotonic()
                if (self.args.config_server
                        and now - last_ship > self.args.warm_ship_s):
                    last_ship = now
                    self._ship_warm()
                if not done and not engine.slot_mgr.active_count \
                        and not engine.queue.depth():
                    if not idling:
                        idle.enter_context(
                            T.trace_scope("serve:idle", cat="serving"))
                        idling = True
                    time.sleep(0.002)

    def _ship_warm(self) -> None:
        """Best-effort POST of in-flight progress to the ring buddy; a dead
        buddy costs one short timeout, never a decode stall."""
        items = self.engine.in_flight()
        urls = self._peer_urls()
        if not urls:
            return
        body = json.dumps({"origin": self.rank, "items": items}).encode()
        req = urllib.request.Request(
            urls[0] + "/warm", data=body, method="POST",
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=1.0):
                pass
        except OSError:
            if self.counters is not None:
                self.counters.inc_event("warm_ship_failed")

    # -- HTTP ----------------------------------------------------------------------

    def serve(self) -> int:
        from ..monitor.server import maybe_start_monitor

        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _send(self, code: int, body: bytes,
                      ctype: str = "application/json") -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path = self.path.split("?", 1)[0].rstrip("/")
                if path == "/healthz":
                    stats = dict(outer.engine.stats())
                    from ..parallel.moe import stats_health

                    # the router's probe lands here every 250 ms: the block
                    # is as of the last /metrics scrape or profile capture
                    moe = stats_health(outer._moe_stats(refresh=False))
                    if moe is not None:
                        stats["moe"] = moe
                    stats.update(ok=True, rank=outer.rank,
                                 incarnation=outer.incarnation,
                                 weight_rung=outer.weight_rung,
                                 tier=outer.tier)
                    self._send(200, json.dumps(stats).encode())
                elif path == "/weights":
                    self._send(200, outer._weights(),
                               "application/octet-stream")
                elif path == "/kv_result":
                    q = self.path.partition("?")[2]
                    req_id = ""
                    for part in q.split("&"):
                        if part.startswith("id="):
                            req_id = part[len("id="):]
                    with outer._ship_lock:
                        pending = outer._ship_pending.get(req_id)
                    if pending is None:
                        self._send(404, b'{"error": "unknown request"}')
                        return
                    result = pending.wait(outer.args.request_timeout_s)
                    with outer._ship_lock:
                        outer._ship_pending.pop(req_id, None)
                    if result is None:
                        self._send(504, b'{"error": "request timed out"}')
                        return
                    self._send(200, json.dumps(result.to_json()).encode())
                elif path == "/warm":
                    q = self.path.partition("?")[2]
                    origin = -1
                    for part in q.split("&"):
                        if part.startswith("origin="):
                            origin = int(part[len("origin="):])
                    self._send(200, json.dumps(
                        {"items": outer.warm.get(origin)}).encode())
                else:
                    self._send(404, b'{"error": "not found"}')

            def _handle_kv_ship(self, blob: bytes) -> None:
                from ..monitor.journal import journal_event
                from ..ops.kv_ship import unpack_kv
                from .engine import BackpressureError
                from .request import Request

                got = unpack_kv(blob)
                if got is None:
                    self._send(400, b'{"error": "bad kv blob"}')
                    return
                meta, rows = got
                t0 = time.monotonic()
                try:
                    req = Request.from_json(meta["request"])
                    # re-parent to the shipping rank's kv_ship span (the
                    # cross-process hop context rides in the blob meta), so
                    # this rank's graft/decode spans chain under the ship
                    ctx = T.parse_traceparent(meta.get("traceparent", ""))
                    if ctx is not None:
                        req.trace_id = req.trace_id or ctx.trace_id
                        req.parent_span = ctx.span_id
                    pending = outer.engine.submit_prefilled(req, meta, rows)
                except BackpressureError as e:
                    self._send(503, json.dumps({"error": str(e)}).encode())
                    return
                except (ValueError, KeyError) as e:
                    self._send(400, json.dumps({"error": str(e)}).encode())
                    return
                with outer._ship_lock:
                    outer._ship_pending[req.req_id] = pending
                journal_event("kv_shipped", req_id=req.req_id,
                              tokens=int(meta.get("cursor", 0)),
                              origin_rank=int(meta.get("origin_rank", -1)),
                              rank=outer.rank, tenant=req.tenant,
                              trace_id=req.trace_id,
                              admit_ms=round((time.monotonic() - t0) * 1e3, 3))
                if outer.counters is not None:
                    outer.counters.inc_event("kv_ships_received")
                self._send(200, b'{"ok": true}')

            def _trace_ctx(self, req) -> None:
                """Adopt the dispatching hop's context: the traceparent
                header wins, the request-body fields are the fallback."""
                ctx = T.parse_traceparent(
                    self.headers.get(T.TRACEPARENT_HEADER, ""))
                if ctx is not None:
                    req.trace_id = req.trace_id or ctx.trace_id
                    req.parent_span = ctx.span_id

            def _handle_prefill_generate(self, doc: dict) -> None:
                """Prefill tier: run the prefill half, ship KV to a decode
                rank, proxy the final result back to the router."""
                from .disagg import ship_to_decode
                from .request import Request

                try:
                    req = Request.from_json(doc)
                    self._trace_ctx(req)
                    outer._chaos_phase("prefill")
                    first, rows, total, hit = outer.engine.prefill_only(req)
                except ValueError as e:
                    self._send(400, json.dumps({"error": str(e)}).encode())
                    return
                outer._chaos_tick()
                urls = (outer.decode_pool.pick()
                        if outer.decode_pool is not None else [])
                if not urls:
                    self._send(503, b'{"error": "no decode workers"}')
                    return
                result, err = ship_to_decode(
                    urls, req, first, rows, total, outer.rank,
                    result_timeout_s=outer.args.request_timeout_s,
                    counters=outer.counters,
                    phase_hook=lambda: outer._chaos_phase("kv_ship"),
                )
                if result is None:
                    # a dead decode rank reads as a failed dispatch at the
                    # router (502 -> requeue-front, warm resume included)
                    self._send(502, json.dumps({"error": err}).encode())
                    return
                self._send(200, json.dumps(result).encode())

            def do_POST(self):
                n = int(self.headers.get("Content-Length", "0"))
                body = self.rfile.read(n)
                path = self.path.rstrip("/")
                if path == "/kv_ship":
                    self._handle_kv_ship(body)
                    return
                try:
                    doc = json.loads(body.decode())
                except ValueError as e:
                    self._send(400, json.dumps({"error": str(e)}).encode())
                    return
                if path == "/warm":
                    outer.warm.put(int(doc.get("origin", -1)),
                                   doc.get("items", []))
                    self._send(200, b"{}")
                    return
                if path != "/generate":
                    self._send(404, b'{"error": "not found"}')
                    return
                if outer.tier == "prefill":
                    self._handle_prefill_generate(doc)
                    return
                from .engine import BackpressureError
                from .request import Request

                try:
                    req = Request.from_json(doc)
                    self._trace_ctx(req)
                    pending = outer.engine.submit(req)
                except BackpressureError as e:
                    self._send(503, json.dumps({"error": str(e)}).encode())
                    return
                except ValueError as e:
                    self._send(400, json.dumps({"error": str(e)}).encode())
                    return
                result = pending.wait(outer.args.request_timeout_s)
                if result is None:
                    self._send(504, b'{"error": "request timed out"}')
                    return
                self._send(200, json.dumps(result.to_json()).encode())

        httpd = ThreadingHTTPServer((self.args.host, self.args.port), Handler)
        monitor = maybe_start_monitor(self.args.port, host=self.args.host)
        loop = threading.Thread(target=self._engine_loop, daemon=True)
        loop.start()
        import jax

        dev = jax.devices()[0]
        print(f"SERVE_WORKER_READY: rank={self.rank} "
              f"url=http://{self.args.host}:{self.args.port} "
              f"rung={self.weight_rung} platform={dev.platform} "
              f"device_kind={dev.device_kind!r} devices={jax.device_count()}"
              + (f" tier={self.tier}" if self.tier else ""), flush=True)
        from ..monitor import boot

        boot.complete()
        try:
            httpd.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            self._stop.set()
            loop.join(timeout=5)
            httpd.server_close()
            if monitor is not None:
                monitor.close()
        return 0


def main(argv: Optional[List[str]] = None) -> int:
    from ..env import starts_dir
    from ..monitor import boot

    # `boot:interpreter` and `boot:imports` (the package, jax and this
    # module's own imports) end here, by hand: no span could open earlier
    boot.enter("serve-worker", starts_dir())
    ap = argparse.ArgumentParser(prog="kungfu_tpu.serving.worker")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--launch-rank", type=int, default=0)
    ap.add_argument("--incarnation", type=int, default=0)
    ap.add_argument("--config-server", default="")
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--model-json", default="",
                    help="TransformerConfig field overrides as JSON")
    ap.add_argument("--tier", default="", choices=("", "prefill", "decode"),
                    help="disaggregated pool membership (empty: monolithic "
                         "prefill+decode engine)")
    ap.add_argument("--prefix-cache", default="auto",
                    choices=("auto", "on", "off"),
                    help="radix prefix KV cache (auto: the "
                         "KFT_PREFIX_CACHE_MB budget decides; decode-tier "
                         "workers never prefill, so never cache)")
    ap.add_argument("--spec-draft", default="",
                    help="speculative decoding draft: a PRESETS name, "
                         "'same' for self-draft (the target's own params — "
                         "the mechanics A/B), or 'mtp' for the target's own "
                         "prediction module (mtp_layers 1; width 2); empty "
                         "disables speculation")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="verify width: the [slots, k] target step commits "
                         "up to k tokens per round")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--queue-capacity", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--weights-file", default="",
                    help="pickled params pytree (checkpoint-exported)")
    ap.add_argument("--warm-ship-s", type=float, default=0.15)
    ap.add_argument("--buddy-timeout-s", type=float, default=3.0)
    ap.add_argument("--request-timeout-s", type=float, default=120.0)
    args = ap.parse_args(argv)
    from ..env import apply_platform_override, enable_compile_cache

    apply_platform_override()
    enable_compile_cache()
    # the TPU runtime comes up at the first question about devices: asked
    # here, so that it has a name (`boot:backend`) and not a share of the
    # weights
    T.backend_devices()
    return ServingWorker(args).serve()


if __name__ == "__main__":
    raise SystemExit(main())
