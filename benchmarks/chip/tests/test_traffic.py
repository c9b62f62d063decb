"""The traffic mix and the loop it names, found by name; the window's
work does not depend on the seed."""
import collections

import pytest

import traffic


class FakeSession:
    """Fits that take ``fit_s`` on a clock of their own."""

    def __init__(self, fit_s):
        self.fit_s, self.now, self.ks = fit_s, 0.0, []

    def clock(self):
        return self.now

    def fit(self, k):
        self.ks.append(k)
        self.now += self.fit_s
        return k


@pytest.mark.parametrize("fit_s", [26.0, 13.9, 7.0, 0.5])
def test_window_fits_whole_passes_whatever_the_seed(monkeypatch, fit_s):
    mix = traffic.load("cold_fits")
    loop = traffic.loop(mix)
    runs = []
    for seed in (3, 2 ** 31 + 11, 2 ** 33 + 5):
        ses = FakeSession(fit_s)
        monkeypatch.setattr(loop.time, "perf_counter", ses.clock)
        fits = loop.window(ses, mix, seed, 40.0)
        counts = collections.Counter(fits)
        assert sorted(counts) == list(range(mix["pool"]))
        assert len(set(counts.values())) == 1
        # a pass starts only before the window's end
        assert ses.now - fit_s * mix["pool"] < 40.0
        runs.append(sorted(fits))
    assert runs[0] == runs[1] == runs[2]


def test_warm_up_and_trace_use_the_mix():
    mix = traffic.load("cold_fits")
    loop = traffic.loop(mix)
    ses = FakeSession(1.0)
    loop.warm_up(ses, mix)
    assert len(ses.ks) == mix["warmup_fits"]
    assert all(k >= mix["pool"] for k in ses.ks)
    assert loop.traced(ses, mix, 7) == [loop.order(mix, 7)[0]]
    with pytest.raises(ValueError):
        loop.warm_up(ses, dict(mix, warmup_fits=0))
