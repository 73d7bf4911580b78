"""FunctionSpec — the unit of serverless execution.

One spec == one node of a physical plan (or one training/serving step).
The fingerprint plays the role of the paper's pinned environment
(`@requirements`): since the OS/container/interpreter layers are fixed in
a single JAX process, the degrees of freedom left are exactly (code,
static config, dtype policy, mesh axes) — so they are what we hash.
Same fingerprint + same abstract inputs → the warm cache may reuse a
compiled executable; anything else is a cold start.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

from repro.runtime.resources import ResourceRequest
from repro.utils.hashing import fingerprint_fn, stable_hash


@dataclass(frozen=True)
class FunctionSpec:
    name: str
    fn: Callable[..., Any]
    static_config: Dict[str, Any] = field(default_factory=dict)
    resources: Optional[ResourceRequest] = None
    #: non-traceable functions opt out of jit (executed eagerly, still
    #: retried/speculated like any other task)
    jit: bool = True

    @functools.cached_property
    def fingerprint(self) -> str:
        # computed once per spec: hashing the source takes milliseconds,
        # and a dispatch reads the fingerprint several times
        return stable_hash(
            {
                "name": self.name,
                "code": fingerprint_fn(self.fn),
                "config": self.static_config,
                "jit": self.jit,
            }
        )

    def __hash__(self) -> int:
        return hash(self.fingerprint)
