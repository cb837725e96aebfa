"""Reduction of the program's spans (utils/tracing wire form) to numbers.

Arithmetic copied from ``dmlc_tpu/cluster/critpath.py``'s definition of
self time (a span's duration minus the part of it its children cover); the
benchmark keeps its own copy so that a later PR cannot move the yardstick.
"""

from __future__ import annotations


def union_length(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def to_clock(spans, offset: float):
    """Wire spans carry ``start`` on the tracer's clock; ``offset`` is
    perf_counter minus that clock. Returns dicts with t0/t1 on perf_counter."""
    out = []
    for s in spans:
        t0 = s["start"] + offset
        out.append({**s, "t0": t0, "t1": t0 + s["dur"]})
    return out


def ended_in(spans, t_open: float, t_close: float):
    return [s for s in spans if t_open < s["t1"] <= t_close]


def self_times(spans) -> dict[str, float]:
    """span id -> self seconds. Children are clipped to their parent."""
    kids: dict[str, list] = {}
    for s in spans:
        if s.get("parent"):
            kids.setdefault(s["parent"], []).append(s)
    out = {}
    for s in spans:
        covered = union_length(
            (max(c["t0"], s["t0"]), min(c["t1"], s["t1"]))
            for c in kids.get(s["span"], ())
            if c["t1"] > s["t0"] and c["t0"] < s["t1"]
        )
        out[s["span"]] = max(0.0, s["dur"] - covered)
    return out
