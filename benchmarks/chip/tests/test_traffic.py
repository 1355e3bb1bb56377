"""The traffic generator: deterministic from the seed, the same work for
every seed, and the length and arrival statistics its mix states."""
import numpy as np
import pytest

from chipbench import traffic
from conftest import CLOSED_MIX, OPEN_MIX, TINY_MIX

SEEDS = (0, 7, 2**31 + 5, 2**40 + 3)


def _chat():
    return dict(OPEN_MIX)


@pytest.mark.parametrize("seed", SEEDS)
def test_open_schedule_is_deterministic(seed):
    a = traffic.open_schedule(_chat(), seed, 30, 32768)
    b = traffic.open_schedule(_chat(), seed, 30, 32768)
    assert [p.due for p in a] == [p.due for p in b]
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(a, b))


def test_every_seed_gets_the_same_work_in_another_order():
    runs = [traffic.open_schedule(_chat(), s, 30, 32768) for s in SEEDS]
    for r in runs[1:]:
        assert sorted(len(p.prompt) for p in r) == sorted(
            len(p.prompt) for p in runs[0])
        assert sorted(p.max_new for p in r) == sorted(
            p.max_new for p in runs[0])
        gaps = np.diff([p.due for p in r])
        gaps0 = np.diff([p.due for p in runs[0]])
        assert abs(gaps.sum() - gaps0.sum()) < 30    # one gap at most apart
        assert [len(p.prompt) for p in r] != [len(p.prompt)
                                             for p in runs[0]]
        assert not (r[0].prompt[:8] == runs[0][0].prompt[:8]).all() or \
            len(r[0].prompt) != len(runs[0][0].prompt)


def test_open_schedule_statistics():
    mix, seconds = _chat(), 30
    plans = traffic.open_schedule(mix, 3, seconds, 32768)
    horizon = mix["ramp_s"] + seconds
    assert len(plans) == round(mix["rate_per_s"] * horizon)
    due = np.array([p.due for p in plans])
    assert due[0] == 0 and (np.diff(due) > 0).all() and due[-1] < horizon
    plen = np.array([len(p.prompt) for p in plans])
    olen = np.array([p.max_new for p in plans])
    assert plen.min() >= mix["prompt"]["min"]
    assert plen.max() <= mix["prompt"]["max"]
    assert olen.min() >= mix["output"]["min"]
    assert olen.max() <= mix["output"]["max"]
    # quantiles of the stated lognormals: medians land on the stated ones
    assert abs(np.median(plen) - mix["prompt"]["median"]) <= 0.1 * \
        mix["prompt"]["median"]
    assert abs(np.median(olen) - mix["output"]["median"]) <= 0.1 * \
        mix["output"]["median"]
    assert all(0 <= p.prompt.min() and p.prompt.max() < 32768
               for p in plans)


def test_exponential_gaps_have_the_stated_rate():
    """Over a long horizon the gaps' coefficient of variation is ~1, as
    for a Poisson process, and their mean is 1 / rate."""
    mix = dict(TINY_MIX, rate_per_s=2.0, ramp_s=0)
    due = np.array([p.due for p in traffic.open_schedule(mix, 1, 2000,
                                                         512)])
    gaps = np.diff(due)
    assert abs(gaps.mean() - 0.5) < 0.01
    assert 0.9 < gaps.std() / gaps.mean() < 1.1


def test_closed_requests_cycle_one_set():
    a = traffic.closed_requests(CLOSED_MIX, 11, 1000)
    b = traffic.closed_requests(CLOSED_MIX, 12, 1000)
    assert len(a) == traffic.SET_SIZE
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt)
                                                      for p in b)
    plen = np.array([len(p.prompt) for p in a])
    assert plen.min() >= 128 and plen.max() <= 1024
    assert abs(plen.mean() - 576) < 2


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_blocks_hold_the_same_work(seed):
    """Each block of ``batch`` requests (the first fills every lane, each
    later one refills them) holds the same lengths on every seed, and its
    first requests already hold one length from each stratum."""
    B = CLOSED_MIX["batch"]
    ref = traffic.closed_requests(CLOSED_MIX, 1, 1000)
    got = traffic.closed_requests(CLOSED_MIX, seed, 1000)
    for k in range(3):
        blk, blk0 = got[k * B:(k + 1) * B], ref[k * B:(k + 1) * B]
        for f in (lambda p: len(p.prompt), lambda p: p.max_new):
            assert sorted(map(f, blk)) == sorted(map(f, blk0))
        for g in range(0, B, 4):
            quarter = sorted(
                int(np.searchsorted(sorted(map(f, blk)), f(p), "right")
                    - 1) * 4 // B
                for p in blk[g:g + 4] for f in [lambda p: len(p.prompt)])
            assert quarter == [0, 1, 2, 3]
    if seed != 1:
        assert [len(p.prompt) for p in got[:3 * B]] != [
            len(p.prompt) for p in ref[:3 * B]]


@pytest.mark.parametrize("n", (1, 5, 64, 100))
def test_stratified_order_is_a_permutation(n):
    for mask in (0, 3, 77):
        assert sorted(traffic.stratified_order(n, mask)) == list(range(n))
