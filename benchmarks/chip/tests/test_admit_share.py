"""``admit_share`` reads the engine's admission seconds across the
window, and reads nothing from an engine that does not count them."""
import types

from chipbench import spec


def _run(stats0, stats1, window_s):
    win = types.SimpleNamespace(stats0=stats0, stats1=stats1)
    return types.SimpleNamespace(win=win, window_s=window_s)


def test_share_of_the_window_spent_admitting():
    read = spec.load_module("metrics", "admit_share").read
    run = _run({"admit_seconds": 3.5}, {"admit_seconds": 13.5}, 50.0)
    assert read(run) == 20.0
    assert read(_run({"admit_seconds": 2.0}, {"admit_seconds": 2.0},
                     50.0)) == 0.0


def test_nothing_from_an_engine_without_the_counter():
    read = spec.load_module("metrics", "admit_share").read
    assert read(_run({"admitted": 1}, {"admitted": 9}, 50.0)) is None
