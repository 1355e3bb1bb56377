"""CPU tests of the benchmark's yardstick. Run from the repository root:

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests

The served path runs with its Pallas kernels in interpret mode at a tiny
configuration (``configs/tiny.json``)."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
for p in (str(CHIP), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MIX = {"loop": "closed", "batch": 4, "rate_per_s": 4.0,
            "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                       "min": 8, "max": 100},
            "output": {"dist": "uniform", "min": 30, "max": 60},
            "ramp_s": 1, "drain_cap_s": 30}
# an open loop at chat-like lengths, for the generator's tests
OPEN_MIX = {"loop": "open", "batch": 64, "rate_per_s": 2.0,
            "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                       "min": 64, "max": 4096},
            "output": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                       "min": 16, "max": 512},
            "ramp_s": 20, "drain_cap_s": 30}
CLOSED_MIX = {"loop": "closed", "batch": 64,
              "prompt": {"dist": "uniform", "min": 128, "max": 1024},
              "output": {"dist": "lognormal", "median": 256, "sigma": 0.6,
                         "min": 64, "max": 1024}}


@pytest.fixture
def tiny_cfg():
    cfg = json.loads((HERE / "configs" / "tiny.json").read_text())
    cfg["name"] = "tiny"
    return cfg


@pytest.fixture
def tiny_cell(tiny_cfg, monkeypatch):
    """A cell of the tiny configuration, on whatever device JAX has, with
    that device's kind given the v5e's peaks."""
    import jax

    from chipbench import peaks, spec
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return spec.Cell("tiny", 1, tiny_cfg, dict(TINY_MIX),
                     bench["end_to_end"], bench["per_layer"])
