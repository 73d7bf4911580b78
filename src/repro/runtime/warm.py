"""Warm-start cache — the compiled-executable analog of frozen containers.

The paper freezes initialized containers so a "cold" Spark-session start
(seconds-minutes) becomes a ~300 ms thaw.  The JAX analog: tracing+XLA
compilation is the cold start; re-invoking a cached executable for the
same (fingerprint, abstract shapes) is the warm start.  We make the split
explicit with ``.lower().compile()`` so both phases are measurable —
benchmarks/bench_serverless.py reports the cold:warm ratio next to the
paper's claim.  The compiled program carries the spec's name
(``jit_<pipeline>_stage<n>`` for a pipeline stage).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Tuple

import jax

from repro.runtime.function import FunctionSpec
from repro.runtime.phases import named
from repro.utils.hashing import stable_hash
from repro.utils.logging import get_logger

log = get_logger("runtime.warm")


@dataclass
class StartupStats:
    cold_starts: int = 0
    warm_hits: int = 0
    cold_seconds: float = 0.0

    @property
    def warm_ratio(self) -> float:
        total = self.cold_starts + self.warm_hits
        return self.warm_hits / total if total else 0.0


def _abstract_key(tree: Any) -> str:
    leaves = [
        (str(getattr(l, "shape", None)), str(getattr(l, "dtype", None)))
        for l in jax.tree_util.tree_leaves(tree)
    ]
    treedef = str(jax.tree_util.tree_structure(tree))
    return stable_hash({"leaves": leaves, "treedef": treedef})


@dataclass
class WarmFunctionCache:
    """fingerprint × abstract-input-key → compiled executable."""

    stats: StartupStats = field(default_factory=StartupStats)
    _cache: Dict[Tuple[str, str], Callable] = field(default_factory=dict)

    def __post_init__(self) -> None:
        self._lock = threading.Lock()

    def get_or_compile(self, spec: FunctionSpec, *example_inputs: Any) -> Callable:
        """Return an executable for ``spec`` at these input shapes."""
        if not spec.jit:
            return spec.fn
        key = (spec.fingerprint, _abstract_key(example_inputs))
        with self._lock:
            hit = self._cache.get(key)
            if hit is not None:
                self.stats.warm_hits += 1
                return hit
        t0 = time.perf_counter()
        abstract = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype)
            if hasattr(l, "shape")
            else l,
            example_inputs,
        )
        compiled = jax.jit(named(spec.fn, spec.name)).lower(*abstract).compile()
        dt = time.perf_counter() - t0
        with self._lock:
            self._cache[key] = compiled
            self.stats.cold_starts += 1
            self.stats.cold_seconds += dt
        log.debug("cold start %s: %.1f ms", spec.name, dt * 1e3)
        return compiled

    def has_fingerprint(self, fingerprint: str) -> bool:
        """True when ANY compiled executable exists for this function
        fingerprint (some shape already paid the cold start).  The wave
        scheduler stamps this onto ``StageScheduled`` as the warm/cold
        admission hint — shapes are only known once the stage's scans
        complete, so the fingerprint is the honest pre-dispatch signal."""
        with self._lock:
            return any(k[0] == fingerprint for k in self._cache)

    def invalidate(self) -> None:
        with self._lock:
            self._cache.clear()
