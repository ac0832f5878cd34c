"""Tests of the benchmark itself (not part of the program's tier-1
suite): ``python -m pytest bench/tests`` from the root of the checkout.
They run on the CPU at small sizes."""

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def small(cell: str, scale: int = 10) -> tuple[dict, dict]:
    """The cell's configuration cut to ``scale``, on the jitted chunk
    stepper the chip runs, and its traffic mix."""
    import run

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    _, cfg, mix = run.resolve(spec, cell)
    cfg["generator"]["scale"] = scale
    cfg["capacity"] = 2 * cfg["generator"]["edgefactor"] << scale
    cfg["server"]["host_kernels"] = False
    return cfg, mix


@pytest.fixture
def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())
