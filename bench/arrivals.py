"""The traffic generator: one reader for every mix's data file.

A mix, ``bench/traffic/<mix>.json``, states its traffic as parameters:

- ``source``: the request source that makes each window's requests,
  the module ``bench/sources/<source>.py`` (``"replay"``: requests
  replay the tables of ``users`` users);
- ``users``: how many users the source draws requests from;
- ``window``: the base window size, which the configuration's
  per-window budget is set for;
- ``arrivals``: how requests reach the system -
  ``"backlog"``: windows back to back, as fast as the system takes
  them, their sizes cycling through ``sizes`` (bursts, swings) or all
  ``window``;
  ``"poisson"``: an open loop - requests arrive at ``rate_per_s`` as a
  Poisson process drawn from the seed, a window closes when its
  ``window`` requests have arrived, and is handed to the system no
  earlier than that;
- ``warmup_windows``: windows served before the timed window, which
  cover every window size the mix uses;
- ``why``: one line on what the mix exercises.

A key or a value this reader does not know is refused, so a mix is
never timed as something it does not say.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

KEYS = {"source", "users", "arrivals", "window", "sizes", "rate_per_s",
        "warmup_windows", "why"}
ARRIVALS = ("backlog", "poisson")


@dataclass
class Plan:
    """The windows of one run of a mix."""

    sizes: tuple  # window sizes, cycled over the run's windows
    rate: float | None  # requests per second of an open loop, or None
    warmup: int
    seed: int

    @property
    def open_loop(self) -> bool:
        return self.rate is not None

    def size(self, k: int) -> int:
        return self.sizes[k % len(self.sizes)]

    def clock(self) -> "Clock":
        return Clock(self)


class Clock:
    """Arrival times of an open loop's requests, window after window,
    as offsets in seconds from the start of the timed window."""

    def __init__(self, plan: Plan):
        self.plan = plan
        self.rng = np.random.default_rng((plan.seed & 0xFFFFFFFFFFFF,
                                          0xA221))
        self.last = 0.0

    def next(self, n: int) -> np.ndarray:
        gaps = self.rng.exponential(1.0 / self.plan.rate, n)
        at = self.last + np.cumsum(gaps)
        self.last = float(at[-1])
        return at


def plan(traffic: dict, seed: int) -> Plan:
    """The mix's windows, or ValueError for a mix this reader cannot
    serve as it is written."""
    unknown = sorted(set(traffic) - KEYS)
    if unknown:
        raise ValueError(f"traffic keys not understood: {unknown}")
    kind = traffic.get("arrivals")
    if kind not in ARRIVALS:
        raise ValueError(f"arrivals {kind!r} not one of {ARRIVALS}")
    if kind == "poisson":
        if "sizes" in traffic:
            raise ValueError("an open loop's windows all hold 'window' "
                             "requests; 'sizes' is for a backlog")
        rate = float(traffic["rate_per_s"])
        if not rate > 0:
            raise ValueError(f"rate_per_s {rate!r} is not positive")
    elif "rate_per_s" in traffic:
        raise ValueError("a backlog takes no rate_per_s")
    else:
        rate = None
    sizes = tuple(int(s) for s in traffic.get("sizes",
                                              [traffic["window"]]))
    warmup = int(traffic["warmup_windows"])
    if min(sizes) < 1:
        raise ValueError(f"window sizes {sizes} must be positive")
    if set(sizes[k % len(sizes)] for k in range(warmup)) != set(sizes):
        raise ValueError(f"{warmup} warm-up windows do not serve every "
                         f"window size of {sizes}")
    return Plan(sizes=sizes, rate=rate, warmup=warmup, seed=int(seed))
