"""chip_smoke.py: its checks on the CPU at a tiny size.

The script itself runs only on a TPU; here its served path runs in
interpret mode on a small dense model, and its dispatch check is fed the
counters a silent fallback would leave behind."""
import importlib.util
import json
import pathlib

import pytest

from repro.configs.base import ArchConfig
from repro.obs import MetricsRegistry

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

TINY = ArchConfig(name="tiny", family="dense", n_layers=2, d_model=128,
                  n_heads=8, n_kv_heads=2, head_dim=16, d_ff=352,
                  vocab_size=512, attn_chunk=64)


def test_refuses_to_run_without_a_tpu(tmp_path, capsys):
    assert chip_smoke.main(["--out", str(tmp_path)]) != 0
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert '"ok"' not in out
    assert not any(tmp_path.iterdir())


def test_served_path_passes_its_checks(tmp_path):
    # prompts span two attn_chunks, so the logit check's prefill reads
    # the pages its first chunk wrote
    res = chip_smoke.smoke(TINY, tmp_path, seed=0, n_requests=2,
                           prompt_lo=70, prompt_hi=120, max_new=4)
    assert res["tokens"] == 2 * 4
    assert res["logit_rel"] == 0.0   # the CPU runs both paths alike
    manifest = json.loads((tmp_path / "artifact" / "manifest.json")
                          .read_text())
    assert manifest["fmt"] == "mxfp4"


def _registry(paths, kernels):
    reg = MetricsRegistry()
    for role, path in paths:
        reg.counter("quant_dispatch_total",
                    {"op": "qlinear", "path": path, "role": role}).inc()
    for op in kernels:
        reg.counter("kernel_dispatch_calls_total",
                    {"op": op, "traced": "true"}).inc()
    return reg


FUSED = [(r, "fused") for r in chip_smoke.QUANT_ROLES] + [("head", "ref")]


@pytest.mark.parametrize("paths,kernels,ok", [
    (FUSED, chip_smoke.SERVED_KERNELS, True),
    (FUSED + [("qkv", "ref")], chip_smoke.SERVED_KERNELS, False),
    (FUSED[1:], chip_smoke.SERVED_KERNELS, False),
    (FUSED, chip_smoke.SERVED_KERNELS[:-1], False),
])
def test_dispatch_check_catches_fallbacks(paths, kernels, ok):
    reg = _registry(paths, kernels)
    if ok:
        chip_smoke.check_dispatch(reg)
    else:
        with pytest.raises(AssertionError):
            chip_smoke.check_dispatch(reg)
