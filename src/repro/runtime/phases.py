"""How the program times its work, in one place.

A *phase* is one named step of a query or a pipeline stage: read, copy,
compile, device, fetch, write.  :class:`Phases` times each step with
``time.perf_counter`` and opens a ``jax.profiler.TraceAnnotation`` named
``<prefix>.<phase>`` around it.  While the profiler traces, the annotations
land on the ``/host:CPU`` plane, on the device trace's clock, so a gap in
the device's work can be named by the phase the host was in; with no
trace running an annotation is a no-op and a phase costs two clock reads.

Programs handed to XLA are counted per thread: one ``jax.monitoring``
listener on :data:`BACKEND_COMPILE`, registered when this module is first
imported, bumps the counter of the thread that compiled (JAX reports the
event synchronously, on the compiling thread, whether XLA compiled the
program or loaded it from the persistent cache).

:func:`named` gives a function the name its compiled program carries, so
a device trace names programs by what they compute (``jit_<name>``).
"""
from __future__ import annotations

import contextlib
import functools
import re
import threading
import time
from typing import Any, Callable, Dict, Iterator

import jax

__all__ = ["BACKEND_COMPILE", "Phases", "compiles", "named"]

#: JAX's event for one program handed to XLA
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

_local = threading.local()


def _on_duration(event: str, duration: float, **kwargs: Any) -> None:
    if event == BACKEND_COMPILE:
        _local.compiles = compiles() + 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compiles() -> int:
    """Programs the calling thread has handed to XLA so far."""
    return getattr(_local, "compiles", 0)


class Phases:
    """Seconds per phase of one query or stage, and the programs its
    thread handed to XLA since it was made.  A phase entered twice
    accumulates (a query's copy is enqueued in its scan and awaited in
    its execution)."""

    def __init__(self, prefix: str):
        self.prefix = prefix
        self.seconds: Dict[str, float] = {}
        self._compiles_at_start = compiles()

    @contextlib.contextmanager
    def __call__(self, phase: str, **args: Any) -> Iterator[None]:
        """Time ``phase``; ``args`` ride on its span as trace metadata."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"{self.prefix}.{phase}", **args):
                yield
        finally:
            self.seconds[phase] = (
                self.seconds.get(phase, 0.0) + time.perf_counter() - t0
            )

    def __getitem__(self, phase: str) -> float:
        return self.seconds.get(phase, 0.0)

    @property
    def compiles(self) -> int:
        return compiles() - self._compiles_at_start


def named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name`` (made an identifier), for ``jax.jit`` to
    name its program by."""

    @functools.wraps(fn)
    def program(*args: Any, **kwargs: Any) -> Any:
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = (
        re.sub(r"\W+", "_", name).strip("_") or "program"
    )
    return program
