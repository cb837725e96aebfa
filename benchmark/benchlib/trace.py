"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

What the trace gives (on-chip-measurement guide, section 4): per device the
intervals in which an operation ran. From those: busy seconds (their union),
the idle gaps (the complement inside the traced window), the time of one
kernel (the sum over exactly that kernel's events) and the heaviest
operations. The program's spans are put on the same clock through one
``TraceAnnotation`` whose perf_counter instant the harness wrote down.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

from benchlib.spans import union_length

#: The harness runs one tiny jitted program of this name right after the
#: profiler starts and writes down the perf_counter instant at which it was
#: seen to end; its event on the "XLA Modules" line ends at the same instant.
SYNC_NAME = "jit_bench_sync"
#: Lines of a device plane: one event per executed operation, and one per
#: executed program (a jitted function's whole run on the device).
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


@dataclass
class DeviceTrace:
    """Operations of one traced window, times in seconds on perf_counter."""

    t0: float
    t1: float
    devices: dict = field(default_factory=dict)   # plane name -> [(name, s, e)]
    modules: dict = field(default_factory=dict)   # plane name -> [(name, s, e)]

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def _clipped(self, ops):
        return [(max(s, self.t0), min(e, self.t1)) for _, s, e in ops
                if e > self.t0 and s < self.t1]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        per = [union_length(self._clipped(ops)) for ops in self.devices.values()]
        return sum(per) / len(per)

    def op_seconds(self, pattern: str) -> tuple[float, int]:
        """Summed device time and count of the events whose name matches
        ``pattern`` (a regular expression), over all devices, inside the window."""
        rx = re.compile(pattern)
        total, n = 0.0, 0
        for ops in self.devices.values():
            for name, s, e in ops:
                if e > self.t0 and s < self.t1 and rx.search(name):
                    total += min(e, self.t1) - max(s, self.t0)
                    n += 1
        return total, n

    def module_runs(self, pattern: str) -> list[float]:
        """Device seconds of each run, wholly inside the window, of the
        programs whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return [e - s for mods in self.modules.values() for name, s, e in mods
                if s >= self.t0 and e <= self.t1 and rx.search(name)]

    def top_ops(self, k: int = 10):
        """[(group, seconds)]: events grouped by name with instance numbers
        dropped, heaviest first, summed over devices."""
        acc: dict[str, float] = {}
        for ops in self.devices.values():
            for name, s, e in ops:
                if e > self.t0 and s < self.t1:
                    key = group_name(name)
                    acc[key] = acc.get(key, 0.0) + min(e, self.t1) - max(s, self.t0)
        return sorted(acc.items(), key=lambda kv: -kv[1])[:k]

    def gaps(self, device: str | None = None):
        """Idle intervals of one device (the first, by default) in the window."""
        if not self.devices:
            return [(self.t0, self.t1)]
        ops = self.devices[device or sorted(self.devices)[0]]
        out, cursor = [], self.t0
        for s, e in sorted(self._clipped(ops)):
            if s > cursor:
                out.append((cursor, s))
            cursor = max(cursor, e)
        if cursor < self.t1:
            out.append((cursor, self.t1))
        return out

    def idle_by_span(self, spans, k: int = 10):
        """[(span name, idle seconds)]: each idle gap is charged to the
        program span that was open at its middle and started last (the
        innermost one); gaps under no span go to ``(no span)``."""
        acc: dict[str, float] = {}
        spans = sorted(spans, key=lambda s: s["t0"])
        for g0, g1 in self.gaps():
            mid = 0.5 * (g0 + g1)
            owner = "(no span)"
            for s in spans:
                if s["t0"] > mid:
                    break
                if s["t1"] >= mid:
                    owner = s["name"]
            acc[owner] = acc.get(owner, 0.0) + (g1 - g0)
        return sorted(acc.items(), key=lambda kv: -kv[1])[:k]


def group_name(name: str) -> str:
    """An operation's kind and result shape, instance numbers dropped:
    ``%fusion.12.remat = bf16[1536,56,56,256]{...} fusion(...)`` ->
    ``fusion.remat bf16[1536,56,56,256]``; a bare ``fusion.123`` -> ``fusion``."""
    head, eq, rest = name.lstrip("%").partition(" = ")
    kind = re.sub(r"\.\d+", "", head.split(" ")[0]) or head
    if eq:
        m = re.match(r"\(?([a-z0-9]+\[[\d,]*\])", rest)
        if m:
            return f"{kind} {m.group(1)}"
    return kind


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_planes(path: str):
    """[(plane name, [(line name, [(event name, start_ns, duration_ns, stats)])])]
    from a profiler ``.xplane.pb`` or from a recorded excerpt (``.json``, as
    ``excerpt`` writes it: what the tests keep beside them)."""
    if str(path).endswith((".json", ".json.gz")):
        import gzip
        import json

        opener = gzip.open if str(path).endswith(".gz") else open
        with opener(path, "rt") as f:
            return [(p["name"], [(ln["name"], [tuple(e) for e in ln["events"]])
                                 for ln in p["lines"]]) for p in json.load(f)["planes"]]
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [
                (ev.name, ev.start_ns, ev.duration_ns, {k: str(v) for k, v in ev.stats})
                for ev in line.events]))
        out.append((plane.name, lines))
    return out


def load(path: str, sync_perf: float, t0: float, t1: float) -> DeviceTrace:
    """Read the profile, find the end of the sync program on a device's
    module line, and return the device operations with times moved onto
    perf_counter. ``sync_perf`` is the perf_counter instant at which the host
    saw that program end; ``t0``/``t1`` are the traced window on perf_counter."""
    planes = read_planes(path)
    sync_ns = next((start + dur for name, lines in planes if name.startswith("/device:")
                    for line_name, events in lines if line_name == MODULE_LINE
                    for ev_name, start, dur, _ in events if ev_name.startswith(SYNC_NAME)), None)
    if sync_ns is None:
        raise RuntimeError(f"the trace holds no run of {SYNC_NAME!r} to align clocks by")
    shift = sync_perf - sync_ns * 1e-9
    out = DeviceTrace(t0, t1)
    for name, lines in planes:
        if not name.startswith("/device:"):
            continue
        by_name = dict(lines)
        ops = [(event_label(ev_name, stats), start * 1e-9 + shift, (start + dur) * 1e-9 + shift)
               for ev_name, start, dur, stats in by_name.get(OP_LINE, ())]
        if ops:
            out.devices[name] = ops
        if MODULE_LINE in by_name:
            out.modules[name] = [
                (ev_name, start * 1e-9 + shift, (start + dur) * 1e-9 + shift)
                for ev_name, start, dur, _ in by_name[MODULE_LINE]]
    return out


def excerpt(path: str, out_path: str, t_from_ns: float, t_to_ns: float) -> None:
    """Write the device lines' events that start in [t_from_ns, t_to_ns), and
    the sync program's run, as JSON (gzipped where the name ends in ``.gz``):
    a small recorded trace for the tests."""
    import gzip
    import json

    planes = []
    for name, lines in read_planes(path):
        kept_lines = []
        for line_name, events in lines:
            if not name.startswith("/device:") or line_name not in (OP_LINE, MODULE_LINE):
                continue
            kept = [e for e in events if t_from_ns <= e[1] < t_to_ns
                    or (line_name == MODULE_LINE and e[0].startswith(SYNC_NAME))]
            if kept:
                kept_lines.append({"name": line_name, "events": [
                    [n[:240], s, d, {}]
                    for n, s, d, st in kept]})
        if kept_lines:
            planes.append({"name": name, "lines": kept_lines})
    opener = gzip.open if str(out_path).endswith(".gz") else open
    with opener(out_path, "wt") as f:
        json.dump({"planes": planes}, f)


def event_label(name: str, stats: dict) -> str:
    """An operation's name; where the short name hides what it is (a custom
    call's kernel lives in its long name), the long name is appended."""
    for key in ("long_name", "hlo_op", "tf_op", "kernel_details"):
        text = stats.get(key)
        if text and text != name:
            return f"{name} | {text[:400]}"
    return name


def describe(path: str, limit: int = 12) -> list[str]:
    """Plane and line names with a few events each: what a builder reads
    before trusting the reduction on a new chip or JAX version."""
    out = []
    for name, lines in read_planes(path):
        out.append(f"PLANE {name}")
        for line_name, events in lines:
            out.append(f"  LINE {line_name!r} events={len(events)}")
            for ev_name, start, dur, stats in events[:limit]:
                short = {k: v[:160] for k, v in stats.items()}
                out.append(f"    {ev_name[:120]!r} start_ns={start} dur_ns={dur} {short}")
    return out
