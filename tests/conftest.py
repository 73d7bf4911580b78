import os

# Smoke tests and benches must see ONE device. Only launch/dryrun.py sets
# xla_force_host_platform_device_count (and only in its own process).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

from repro.catalog import Catalog
from repro.io import ObjectStore
from repro.table import TableFormat


@pytest.fixture
def store(tmp_path):
    return ObjectStore(tmp_path / "lake")


@pytest.fixture
def fmt(store):
    return TableFormat(store, shard_rows=128)


@pytest.fixture
def catalog(store):
    return Catalog(store)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def pytest_configure(config):
    # registered in pyproject.toml too; duplicated here so the marker
    # exists even when pytest runs without that config file (e.g. pytest
    # invoked on a single test file from another rootdir)
    config.addinivalue_line(
        "markers", "slow: slow property-based tests (deselect with -m 'not slow')"
    )


def pytest_collection_modifyitems(config, items):
    """Auto-mark property-based tests as slow so `-m 'not slow'` gives a
    quick signal pass (hypothesis marks its tests with ``fn.hypothesis``)."""
    for item in items:
        if hasattr(getattr(item, "function", None), "hypothesis"):
            item.add_marker(pytest.mark.slow)
