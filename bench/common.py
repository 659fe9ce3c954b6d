"""Pieces every driver shares: the run context, the compile cache, the
compile clock and the run record that metric readers and checks read."""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, List, Optional

@dataclasses.dataclass
class Context:
    """What a driver is given: the parsed arguments, the cell's files and
    the chips it may use."""
    args: Any
    bench: Dict
    workload: Dict
    cfg: Dict
    mix: Dict
    cell: Dict
    devices: List[Any]
    t_start: float
    bench_dir: str
    root: str


@dataclasses.dataclass
class Run:
    """What a driver returns.  Host-clock numbers are in ``host``, engine
    counters in ``counters`` (the ones a run prints in ``engine``), the
    reduced trace (``--trace 1``) in ``trace``; ``check`` holds what the
    correctness comparison needs."""
    ctx: Context
    host: Dict[str, float]
    counters: Dict[str, Any]
    attempted: int
    unfinished: int
    memory_peak_bytes: Optional[int]
    trace: Optional[Dict[str, Any]] = None
    engine: Dict[str, Any] = dataclasses.field(default_factory=dict)
    check: Dict[str, Any] = dataclasses.field(default_factory=dict)


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache in ``<checkout>/.jax_cache``, a
    fixed path inside the checkout (the path is part of the cache's
    key), with no size cap.  Every program is cached, however quickly it
    compiled, so that a run's set-up finds all of them."""
    import jax

    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Backend compiles and persistent-cache hits, stamped on the host
    clock, from JAX's own monitoring events.  Records while entered."""

    def __init__(self):
        self.compiles: List[tuple] = []     # (perf_counter, seconds)
        self.hits: List[float] = []

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(), secs))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits.append(time.perf_counter())

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def between(self, t0: float, t1: float) -> Dict[str, float]:
        c = [s for t, s in self.compiles if t0 <= t < t1]
        return {"compiles": len(c), "compile_s": sum(c),
                "cache_hits": sum(1 for t in self.hits if t0 <= t < t1)}


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile with linear interpolation between order
    statistics (numpy's default); None for no values."""
    import numpy as np

    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    v = stats.get("peak_bytes_in_use")
    return int(v) if v is not None else None
