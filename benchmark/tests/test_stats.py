"""Rate and tail arithmetic on a synthetic window with a stall in it."""

import pytest

from benchlib import stats


def steady(n, period, work=100.0, t0=0.0):
    return [(t0 + (i + 1) * period, work) for i in range(n)]


def test_rate_is_work_over_time_between_two_completions():
    comps = steady(100, 0.5)
    t_open = stats.open_instant(comps, warm=3)
    assert t_open == pytest.approx(2.0)
    w = stats.window(comps, t_open, seconds=10.0)
    assert w.full and w.t_close == pytest.approx(12.0)
    # 20 completions of 100 between the two instants: the one AT t_open is the ramp's
    assert w.n == 20 and w.work == 2000.0
    assert w.rate == pytest.approx(200.0)


def test_window_edges_cut_no_work():
    """A fixed window that cut a 1.2 s shard would swing by a whole shard;
    between completion instants the rate of a steady system is exact
    whatever the phase of the window."""
    rates = []
    for phase in (0.0, 0.3, 0.7, 1.1):
        comps = steady(60, 1.2, work=1536.0, t0=phase)
        w = stats.window(comps, stats.open_instant(comps, 2), seconds=20.0)
        rates.append(w.rate)
    assert max(rates) - min(rates) < 1e-9
    assert rates[0] == pytest.approx(1280.0)


def test_stalls_move_the_rate_and_the_p95_not_the_median():
    """Every twelfth completion waits a second longer (8% of the gaps): the
    whole-window rate and the 95th percentile of the gaps move, the median
    gap does not. That is why no median is an end-to-end metric."""

    def stream(stall: float):
        t, out = 0.0, []
        for i in range(400):
            t += 0.1 + (stall if i % 12 == 11 else 0.0)
            out.append((t, 100.0))
        return out

    def gaps(comps):
        return [(b[0], b[0] - a[0]) for a, b in zip(comps, comps[1:])]

    calm, stalled = stream(0.0), stream(1.0)
    w_calm = stats.window(calm, stats.open_instant(calm, 5), 10.0)
    w_stall = stats.window(stalled, stats.open_instant(stalled, 5), 10.0)
    g_calm, g_stall = stats.in_window(gaps(calm), w_calm), stats.in_window(gaps(stalled), w_stall)
    assert w_stall.rate < 0.6 * w_calm.rate
    assert stats.quantile(g_stall, 0.95) > 5 * stats.quantile(g_calm, 0.95)
    assert stats.quantile(g_stall, 0.5) == pytest.approx(stats.quantile(g_calm, 0.5))


def test_load_that_runs_out_closes_at_its_last_completion():
    comps = steady(10, 1.0)
    w = stats.window(comps, stats.open_instant(comps, 1), seconds=60.0)
    assert not w.full and w.t_close == pytest.approx(10.0) and w.n == 8


def test_quantile_matches_numpy():
    import numpy as np

    xs = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for q in (0.0, 0.5, 0.95, 1.0):
        assert stats.quantile(xs, q) == pytest.approx(float(np.quantile(xs, q)))
