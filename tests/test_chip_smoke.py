"""chip_smoke.py's phases and reference checks, on the CPU at 50k rows.

The chip runs the same function at 16M rows; here the kernels are
interpreted, so only the Mosaic custom-call check has nothing to find.
"""
import importlib.util
import json
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO_ROOT / "chip_smoke.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_smoke_phases_match_references(chip_smoke, capsys):
    chip_smoke.run_smoke(50_000)
    out = capsys.readouterr().out
    assert "pickups: " in out and "groups match numpy" in out
    for name in ("count_auto", "sum_avg_kernel", "sum_avg_jnp", "join"):
        assert f"query {name}: engine_path=" in out
    for name in ("count_auto", "sum_avg_kernel"):
        assert f"program {name}: tpu_custom_call=" in out
    assert "executor: " in out and "retries=0" in out


def test_smoke_refuses_a_host_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    for line in capsys.readouterr().out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
