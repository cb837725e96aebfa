"""``idle_in_spans_pct`` on a synthetic trace: gaps and spans with known overlaps."""

from types import SimpleNamespace

import pytest

from benchlib import manifest
from benchlib.trace import DeviceTrace


def span(name, t0, t1):
    return {"name": name, "t0": t0, "t1": t1}


@pytest.fixture(scope="module")
def reader():
    return manifest.plugins("readers")["idle_in_spans_pct"]


@pytest.fixture
def ctx():
    # Window [0, 10]; the device runs in [1, 2], [4, 6] and [9, 10]: idle
    # gaps [0, 1], [2, 4], [6, 9] = 6 s.
    trace = DeviceTrace(0.0, 10.0, devices={
        "/device:TPU:0": [("op", 1.0, 2.0), ("op", 4.0, 5.0), ("op", 5.0, 6.0), ("op", 9.0, 10.5)]})
    spans = [
        span("engine/run", 0.5, 3.0), span("engine/run", 3.5, 8.0),          # nobody: 0-0.5, 3-3.5, 8-10
        span("ingest/decode_wait", 0.5, 1.5), span("ingest/decode_wait", 2.5, 3.0),
        span("gen/step", 2.0, 7.0), span("gen/step_sync", 3.0, 6.5),
        span("gen/step", 6.5, 7.5),                                             # another thread: overlaps
        span("before", -5.0, -1.0),
    ]
    return SimpleNamespace(trace=trace, spans=spans)


@pytest.mark.parametrize("args,want", [
    # decode_wait meets the gaps in [0.5, 1] and [2.5, 3]: 1 s of 6
    ({"names": ["ingest/decode_wait"]}, 100.0 * 1.0 / 6.0),
    # engine/run covers [0.5, 3] and [3.5, 8]: gaps under it 0.5 + 1 + 0.5 + 2 = 4 s
    ({"names": ["engine/run"]}, 100.0 * 4.0 / 6.0),
    # ... and the rest of the idle time lies under no engine/run: 0.5 + 0.5 + 1 = 2 s
    ({"outside": ["engine/run"]}, 100.0 * 2.0 / 6.0),
    # gen/step is [2, 7.5] as a union; less the sync [3, 6.5] it is [2, 3] + [6.5, 7.5]: 2 s of idle
    ({"names": ["gen/step"], "minus": ["gen/step_sync"]}, 100.0 * 2.0 / 6.0),
    # two names are one union: [0.5, 1.5] + [2, 7.5] meets the gaps in 0.5 + 2 + 1.5 = 4 s
    ({"names": ["ingest/decode_wait", "gen/step"]}, 100.0 * 4.0 / 6.0),
    # a minus that is not there takes nothing away
    ({"names": ["ingest/decode_wait"], "minus": ["absent"]}, 100.0 * 1.0 / 6.0),
])
def test_known_overlaps(reader, ctx, args, want):
    assert reader.read(ctx, **args) == pytest.approx(want)


def test_names_and_outside_split_the_idle_time(reader, ctx):
    inside = reader.read(ctx, names=["engine/run"])
    outside = reader.read(ctx, outside=["engine/run"])
    assert inside + outside == pytest.approx(100.0)


@pytest.mark.parametrize("args", [
    {"names": ["absent"]},                      # the parent commit: no such span
    {"outside": ["absent"]},
    {"names": ["before"]},                      # a span, but not in the traced window
    {"names": ["absent"], "minus": ["gen/step_sync"]},
])
def test_none_where_no_such_span_is_in_the_window(reader, ctx, args):
    assert reader.read(ctx, **args) is None


def test_none_without_a_device_or_without_idle_time(reader, ctx):
    assert reader.read(SimpleNamespace(trace=DeviceTrace(0.0, 1.0), spans=ctx.spans),
                       names=["engine/run"]) is None
    busy = DeviceTrace(0.0, 1.0, devices={"/device:TPU:0": [("op", -1.0, 2.0)]})
    assert reader.read(SimpleNamespace(trace=busy, spans=[span("engine/run", 0.0, 1.0)]),
                       names=["engine/run"]) is None
