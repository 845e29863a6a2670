"""Persistent plan cache — tuning survives restarts.

Winning plans persist to one JSON file keyed by

    (world size, topology digest, tensor-size bucket)

so a restarted job (or the next job on the same fleet shape) installs the
measured winner immediately and skips re-probing/re-measuring.  A resize
or re-meshing changes the key, and `invalidate_stale` drops every entry
that no longer matches the live fleet — stale plans are never replayed
onto a cluster they were not tuned for.

File format (version 1):

    {"version": 1,
     "entries": {"<world>|<digest>|<bucket>": {
         "plan": {...Plan.to_json...},
         "predicted_ms": 0.42, "measured_ms": 0.40,
         "model": {...CostModel.to_json...},
         "created_t_wall": 1722770000.1}}}

Corrupt or future-versioned files are treated as empty (a cache must
never be able to wedge planning), but `load_error` records why.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional

from .candidates import Plan
from .model import CostModel

CACHE_VERSION = 1

CACHE_ENV = "KFT_PLAN_CACHE"

DEFAULT_CACHE_PATH = ".kft_plan_cache.json"


def default_cache_path() -> str:
    return os.environ.get(CACHE_ENV, "") or DEFAULT_CACHE_PATH


def cache_key(world: int, digest: str, bucket_id: str) -> str:
    return f"{world}|{digest}|{bucket_id}"


class PlanCache:
    """One JSON file of winning plans; all mutations write through."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self.entries: Dict[str, dict] = {}
        self.load_error: Optional[str] = None
        self._load()

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                d = json.load(f)
        except FileNotFoundError:
            return
        except (OSError, ValueError) as e:
            self.load_error = f"{type(e).__name__}: {e}"
            return
        if not isinstance(d, dict) or d.get("version") != CACHE_VERSION:
            self.load_error = f"unsupported cache version {d.get('version')!r}"
            return
        entries = d.get("entries")
        if isinstance(entries, dict):
            self.entries = dict(entries)

    def save(self) -> None:
        payload = json.dumps(
            {"version": CACHE_VERSION, "entries": self.entries},
            indent=2, sort_keys=True,
        )
        tmp = f"{self.path}.tmp.{os.getpid()}"
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(tmp, "w") as f:
            f.write(payload)
        os.replace(tmp, self.path)  # atomic: a reader never sees a torn file

    def get(self, world: int, digest: str, bucket_id: str) -> Optional[dict]:
        return self.entries.get(cache_key(world, digest, bucket_id))

    def get_plan(self, world: int, digest: str,
                 bucket_id: str) -> Optional[Plan]:
        e = self.get(world, digest, bucket_id)
        if not e or "plan" not in e:
            return None
        try:
            return Plan.from_json(e["plan"])
        except (KeyError, ValueError):
            return None

    def put(self, world: int, digest: str, bucket_id: str, plan: Plan,
            predicted_ms: Optional[float] = None,
            measured_ms: Optional[float] = None,
            model: Optional[CostModel] = None) -> None:
        self.entries[cache_key(world, digest, bucket_id)] = {
            "plan": plan.to_json(),
            "predicted_ms": predicted_ms,
            "measured_ms": measured_ms,
            "model": model.to_json() if model is not None else None,
            "created_t_wall": round(time.time(), 3),
        }
        self.save()

    def invalidate_stale(self, world: int, digest: str) -> int:
        """Drop every entry not keyed to the live (world, digest); returns
        how many were dropped.  Called on resize/re-mesh — plans tuned for
        another fleet shape must never be replayed."""
        prefix = f"{world}|{digest}|"
        stale = [k for k in self.entries if not k.startswith(prefix)]
        for k in stale:
            del self.entries[k]
        if stale:
            self.save()
        return len(stale)

    def __len__(self) -> int:
        return len(self.entries)
