"""The traffic generator reads a mix's data file, and refuses one it
cannot serve as written."""
import numpy as np
import pytest

from bench import arrivals, build


def _mix(**kw):
    t = build.load("traffic", "replay-backlog-4096")
    t.update(kw)
    return t


def test_backlog_mix():
    p = arrivals.plan(_mix(), 3)
    assert not p.open_loop and p.sizes == (4096,) and p.size(17) == 4096


def test_burst_cycle_is_warmed_up():
    p = arrivals.plan(_mix(sizes=[4096, 4096, 12288], warmup_windows=3), 3)
    assert [p.size(k) for k in range(4)] == [4096, 4096, 12288, 4096]
    with pytest.raises(ValueError, match="warm-up"):
        arrivals.plan(_mix(sizes=[4096, 4096, 12288], warmup_windows=2), 3)


@pytest.mark.parametrize("bad", [
    {"arrivals": "zipf"}, {"arrivals": "spike"}, {"arrivals": None},
    {"burst": 3}, {"rate_per_s": 100.0},
    {"arrivals": "poisson", "rate_per_s": 0.0},
    {"arrivals": "poisson", "rate_per_s": 9.0, "sizes": [4096]}])
def test_unknown_traffic_is_refused(bad):
    t = _mix(**bad)
    if bad.get("arrivals", "") is None:
        del t["arrivals"]
    with pytest.raises((ValueError, KeyError)):
        arrivals.plan(t, 3)


def test_poisson_arrivals_follow_the_rate_and_the_seed():
    mix = _mix(arrivals="poisson", rate_per_s=1000.0)
    a = arrivals.plan(mix, 2 ** 40 + 1).clock()
    first = a.next(4096)
    second = a.next(4096)
    assert second[0] > first[-1] and np.all(np.diff(first) > 0)
    assert abs(8192 / second[-1] - 1000.0) < 50.0
    again = arrivals.plan(mix, 2 ** 40 + 1).clock().next(4096)
    assert np.array_equal(first, again)
    other = arrivals.plan(mix, 2 ** 40 + 2).clock().next(4096)
    assert not np.array_equal(first, other)


def test_open_loop_run_times_each_request_from_its_arrival():
    """A Poisson mix through the whole harness at a CPU size: every
    window is served after its last request arrived, and each request
    keeps its arrival time."""
    import jax

    from bench import check
    from bench.run import execute
    from bench.tests import tiny

    w, cfg, tr = tiny.cell("geotenants-replay-sat")
    tr.update(arrivals="poisson", rate_per_s=2000.0)
    got = {}

    def keep(run, seed):
        got["run"] = run
        return check.run(run, seed)

    out, lines = execute(w, cfg, tr, jax.devices(), seed=2 ** 36 + 5,
                         seconds=1.0, trace=False, check_fn=keep)
    assert out["correct"], "\n".join(lines)
    ws = got["run"].windows
    assert len(ws) > 3
    for win in ws:
        assert len(win.arrived) == win.n and win.done > win.arrived[-1]
        assert win.lag > -1e-3
    span = ws[-1].arrived[-1] - ws[0].arrived[0]
    assert abs(sum(win.n for win in ws) / span / 2000.0 - 1) < 0.25
