"""The configuration files: published sizes kept, byte arithmetic, and
seeded weights that are the same made whole or one layer at a time."""
import json

import jax
import numpy as np
import pytest

from conftest import CHIP, ROOT
from families import dense_gqa as fam

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _cfg(name):
    """A configuration file, and its BENCHMARK.json entry when a cell
    uses it (None otherwise)."""
    conf = {c["name"]: c for c in BENCH["configs"]}.get(name)
    return conf, json.loads((CHIP / "configs" / f"{name}.json").read_text())


CONFIGS = sorted(p.stem for p in (CHIP / "configs").glob("*.json"))


def test_deepseek_stage_bytes():
    _, cfg = _cfg("deepseek-67b.stage12")
    per_layer = (8192 * 8192 * 2 + 8192 * 1024 * 2 + 8192 * 22016 * 3)
    assert per_layer == pytest.approx(692.1e6, rel=1e-3)
    assert fam.packed_weight_bytes(cfg) == 12 * per_layer * 17 // 32
    assert fam.kv_bytes_per_token(cfg) == 12 * 2 * 1056
    assert fam.float_weight_bytes(cfg) == 6 * 102400 * 8192


@pytest.mark.parametrize("name", CONFIGS)
def test_reduced_lists_every_change(name):
    conf, cfg = _cfg(name)
    if conf is not None:
        assert conf["reduced"] == cfg["reduced"]
    for key in cfg["reduced"]:
        assert cfg["published"][key] != cfg[key]
    assert not any(k.endswith(("_dim", "_rank", "_size")) or
                   k in ("intermediate_size", "num_attention_heads",
                         "num_key_value_heads") for k in cfg["reduced"])


@pytest.mark.parametrize("name", CONFIGS)
def test_pool_fits_its_bytes(name):
    _, cfg = _cfg(name)
    sv = cfg["serving"]
    page = sv["page_size"] * fam.kv_bytes_per_token(cfg)
    n = sv["kv_pool_bytes"] // page
    assert 0 < n * page <= sv["kv_pool_bytes"]
    assert sv["page_size"] % sv["attn_chunk"] == 0


def test_layers_made_whole_or_alone_agree(tiny_cfg):
    w = fam.make_weights(tiny_cfg, 2**33 + 9)
    for layer in (0, 1):
        one = fam.make_layer(tiny_cfg, 2**33 + 9, layer)
        for name, *_ in fam.MATRICES:
            for a, b in zip(w["layers"][name], one[name]):
                np.testing.assert_array_equal(np.asarray(a[layer]),
                                              np.asarray(b))
    g = fam.make_globals(tiny_cfg, 2**33 + 9)
    np.testing.assert_array_equal(np.asarray(g["head"]),
                                  np.asarray(w["head"]))


def test_codes_and_scales(tiny_cfg):
    w = fam.make_weights(tiny_cfg, 5)
    codes, scales = (np.asarray(a) for a in w["layers"]["wg"])
    assert ((codes & 15) != 15).all() and ((codes >> 4) != 15).all()
    e0 = fam.scale_exponent(tiny_cfg["hidden_size"], 1.0)
    assert set(np.unique(scales.astype(int) - 127)) <= {e0 - 1, e0, e0 + 1}
    other = np.asarray(fam.make_weights(tiny_cfg, 6)["layers"]["wg"][0])
    assert (other != codes).mean() > 0.9
