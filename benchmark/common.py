"""Pieces every runner shares: device facts, compile watching, percentiles,
and the one HTTP call a serving run and a family's check both make.

`CacheWatch` and `peak_hbm` are copies of chip_smoke.py's (checked on the
chip in PR 21); the yardstick may not move with the program, so they live
here.
"""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request


_T0 = time.perf_counter()     # run.py imports this module first of all


def since_start() -> float:
    """Seconds since the process started measuring; set-up counts from 0."""
    return time.perf_counter() - _T0


def log(phase: str, **fields):
    """A free-form progress line on stdout (never the last one)."""
    print(json.dumps({"phase": phase, "t": round(since_start(), 2),
                      **fields}), flush=True)


def post(url: str, doc: dict, timeout: float = 600.0):
    """-> (status, body dict); a refusal's status and body, not a raise."""
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode(errors="replace")[:200]}
    except OSError as e:
        return 0, {"error": repr(e)[:200]}


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_hbm(n_devices: int) -> int:
    """Peak bytes held on the fullest of the first `n_devices` chips: the
    allocator's `peak_bytes_in_use` (arrays: state, weights, KV pools) plus
    `peak_bytes_reserved`, the region libtpu sets aside for the compiled
    programs' temporaries, which `bytes_in_use` never counts (ERNIE-large
    b40: 3.7 GB in use beside 10.6 GB reserved on the chip, where the step
    compiled ahead of time for a described v5e reports 3.2 GB of arguments
    and 10.8 GB of temporaries; my chip run and my compile, PR 23)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:n_devices]]
    log("memory_stats", per_device=stats)
    return int(max(s.get("peak_bytes_in_use", 0)
                   + s.get("peak_bytes_reserved", 0) for s in stats))


def held_hbm(n_devices: int) -> int:
    """Bytes held right now on the fullest of the first `n_devices` chips,
    counted as `peak_hbm` counts its peak: arrays in use plus the region
    reserved for program temporaries."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:n_devices]]
    return int(max(s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0)
                   for s in stats))


class CompileWatch:
    """Counts JAX's persistent-cache hits and misses and every backend
    compile, over the whole run; `mark()` / `since_mark()` bracket the
    measured window, in which nothing may compile."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0
        self._mark = None
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def snapshot(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "backend_compiles": self.compiles,
                "backend_compile_s": round(self.compile_s, 3)}

    def mark(self):
        self._mark = self.compiles

    def since_mark(self) -> int:
        return self.compiles - self._mark


def percentile(values, q: float) -> float:
    """numpy's default (linear interpolation between the two nearest ranks)."""
    import numpy as np

    if not len(values):
        raise ValueError("percentile of nothing")
    return float(np.percentile(values, q))
