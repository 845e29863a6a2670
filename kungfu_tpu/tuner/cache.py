"""Persistent measured-prior cache — compute tuning survives restarts.

The planner/cache.py pattern applied to the compute side: winning
`StepConfig`s persist to one JSON file keyed by

    (shape digest | backend | jax version)

so a restarted job — or the next job with the same shape — installs the
measured winner immediately and skips the runoff.  Any piece of the key
changing (a different model shape or batch,
a different backend, a jax upgrade that re-lowers the kernels) misses the
cache naturally; `invalidate_stale` additionally drops entries that no
longer match the live key, so a cache file can't grow unboundedly on a
fleet that re-tunes across versions.

File format (version 1):

    {"version": 1,
     "entries": {"<digest>|<backend>|<jax>": {
         "config": {...StepConfig.to_json...},
         "shape": {...ShapeKey.to_json...},
         "predicted_ms": 311.2, "measured_ms": 289.9, "default_ms": 380.6,
         "source": "runoff", "created_t_wall": 1722770000.1}}}

Corrupt or future-versioned files are treated as empty (a cache must
never wedge tuning) with `load_error` recording why.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional

from .space import ShapeKey, StepConfig

CACHE_VERSION = 1

CACHE_ENV = "KFT_TUNER_CACHE"

DEFAULT_CACHE_PATH = ".kft_tuner_cache.json"


def default_cache_path() -> str:
    return os.environ.get(CACHE_ENV, "") or DEFAULT_CACHE_PATH


def jax_version() -> str:
    import jax

    return jax.__version__


def backend_name() -> str:
    import jax

    return jax.default_backend()


def cache_key(digest: str, backend: str, jaxv: str) -> str:
    return f"{digest}|{backend}|{jaxv}"


class PriorCache:
    """One JSON file of measured winners; all mutations write through."""

    def __init__(self, path: Optional[str] = None):
        # None = the default file; "" = no file at all
        self.path = default_cache_path() if path is None else path
        self.entries: Dict[str, dict] = {}
        self.load_error: Optional[str] = None
        self._load()

    def _load(self) -> None:
        if not self.path:
            return
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
        if not self.path:
            return
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

    def get(self, digest: str, backend: str, jaxv: str) -> Optional[dict]:
        return self.entries.get(cache_key(digest, backend, jaxv))

    def get_config(self, digest: str, backend: str,
                   jaxv: str) -> Optional[StepConfig]:
        e = self.get(digest, backend, jaxv)
        if not e or "config" not in e:
            return None
        try:
            return StepConfig.from_json(e["config"])
        except (KeyError, TypeError, ValueError):
            return None

    def put(self, shape: ShapeKey, backend: str, jaxv: str,
            config: StepConfig, predicted_ms: Optional[float] = None,
            measured_ms: Optional[float] = None,
            default_ms: Optional[float] = None,
            source: str = "runoff") -> None:
        self.entries[cache_key(shape.digest(), backend, jaxv)] = {
            "config": config.to_json(),
            "shape": shape.to_json(),
            "predicted_ms": predicted_ms,
            "measured_ms": measured_ms,
            "default_ms": default_ms,
            "source": source,
            "created_t_wall": round(time.time(), 3),
        }
        self.save()

    def invalidate_stale(self, backend: str, jaxv: str) -> int:
        """Drop every entry tuned under another (backend, jax version);
        returns how many were dropped.  Shape entries for other digests
        are kept — several model shapes legitimately share one cache."""
        suffix = f"|{backend}|{jaxv}"
        stale = [k for k in self.entries if not k.endswith(suffix)]
        for k in stale:
            del self.entries[k]
        if stale:
            self.save()
        return len(stale)

    def __len__(self) -> int:
        return len(self.entries)
