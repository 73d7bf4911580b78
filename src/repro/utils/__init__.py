"""Shared utilities: hashing, pytree helpers, logging."""
from repro.utils.hashing import stable_hash, content_hash, fingerprint_fn
from repro.utils.logging import get_logger

__all__ = [
    "stable_hash",
    "content_hash",
    "fingerprint_fn",
    "get_logger",
]
