"""Window, rate and tail arithmetic.

A rate is work over time between two completion instants: the window opens
AT a completion and closes at the first completion at or after ``seconds``
later, so no partly-done shard or request is cut by either edge. A tail is
over every sample whose end falls between the same two instants.
"""

from __future__ import annotations

import math
from dataclasses import dataclass


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile (numpy's default), q in [0, 1]."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("quantile of no samples")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclass
class Window:
    t_open: float
    t_close: float
    work: float      # summed over completions in (t_open, t_close]
    n: int           # completions in (t_open, t_close]
    full: bool       # False when the load ran out before ``seconds`` passed

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    @property
    def rate(self) -> float:
        return self.work / self.seconds


def open_instant(completions, warm: int) -> float | None:
    """The window opens at completion number ``warm`` (0-based) in time order:
    the first one after ``warm`` completions of ramp."""
    ts = sorted(t for t, _ in completions)
    return ts[warm] if len(ts) > warm else None


def close_instant(completions, t_open: float, seconds: float) -> float | None:
    for t in sorted(t for t, _ in completions):
        if t >= t_open + seconds:
            return t
    return None


def window(completions, t_open: float, seconds: float) -> Window:
    """``completions`` is [(instant, work)]. Work is counted for completions
    in (t_open, t_close]: the one that opened the window belongs to the ramp."""
    t_close = close_instant(completions, t_open, seconds)
    full = t_close is not None
    if t_close is None:
        later = [t for t, _ in completions if t > t_open]
        if not later:
            raise ValueError("no completion after the window opened")
        t_close = max(later)
    inside = [w for t, w in completions if t_open < t <= t_close]
    return Window(t_open, t_close, float(sum(inside)), len(inside), full)


def in_window(samples, w: Window):
    """Values of [(end_instant, value)] whose end falls inside the window."""
    return [v for t, v in samples if w.t_open < t <= w.t_close]
