"""``span_leg_ms_p50`` on a small hand-written span list: legs joined by the
trace id and by an attribute, the window's edge, the earliest span per key."""

from types import SimpleNamespace

import pytest

from benchlib import manifest
from benchlib.stats import Window


def span(name, t0, t1, trace="-", **attrs):
    return {"name": name, "t0": t0, "t1": t1, "dur": t1 - t0, "trace": trace, "attrs": attrs}


@pytest.fixture(scope="module")
def reader():
    return manifest.plugins("readers")["span_leg_ms_p50"]


@pytest.fixture
def ctx():
    spans = [
        # three requests, a trace each: client call, wait at the scheduler, first token pushed
        span("cli/first_token", 1.0, 1.9, "a"), span("gen/wait", 1.2, 1.3, "a"), span("gen/first", 1.3, 1.5, "a"),
        span("cli/first_token", 2.0, 3.3, "b"), span("gen/wait", 2.4, 2.5, "b"), span("gen/first", 2.5, 2.8, "b"),
        span("cli/first_token", 3.0, 3.9, "c"), span("gen/wait", 3.1, 3.2, "c"), span("gen/first", 3.2, 3.3, "c"),
        # a request migrated and admitted again: its first admission counts
        span("gen/wait", 3.6, 3.7, "c"), span("gen/first", 3.7, 3.8, "c"),
        # a request whose later edge lies after the window closes, and one with no client record
        span("cli/first_token", 9.0, 11.0, "d"), span("gen/wait", 10.5, 10.6, "d"), span("gen/first", 10.6, 10.7, "d"),
        span("gen/wait", 4.0, 4.1, "e"), span("gen/first", 4.1, 4.2, "e"),
        # two runs of a program: dispatched in one span, read in another, joined by a number
        span("gen/prefill_call", 5.0, 5.01, run=7), span("gen/prefill_sync", 5.03, 5.05, run=7),
        span("gen/prefill_call", 6.0, 6.02, run=8), span("gen/prefill_sync", 6.05, 6.09, run=8),
        span("gen/prefill_call", 7.0, 7.01, run=9),                      # never read
        span("gen/prefill_sync", 0.1, 0.2),                              # no number (the parent commit's)
    ]
    return SimpleNamespace(spans=spans, window=Window(0.5, 10.0, 0.0, 0, True))


@pytest.mark.parametrize("args,want_ms", [
    # leg A: client call -> scheduler submit: 0.2, 0.4, 0.1 -> median 0.2 s
    (dict(frm="cli/first_token", frm_edge="t0", to="gen/wait", to_edge="t0", key="trace"), 200.0),
    # leg D: first token pushed -> in the client's hands: 0.4, 0.5, 0.6 (c's FIRST gen/first) -> 0.5 s
    (dict(frm="gen/first", frm_edge="t1", to="cli/first_token", to_edge="t1", key="trace"), 500.0),
    # a run from its call's start to its read's end, by attr run: 0.05 and 0.09 -> 0.07 s
    (dict(frm="gen/prefill_call", frm_edge="t0", to="gen/prefill_sync", to_edge="t1", key="run"), 70.0),
])
def test_known_legs(reader, ctx, args, want_ms):
    assert reader.read(ctx, **args) == pytest.approx(want_ms)


def test_a_leg_counts_where_its_later_edge_lies_in_the_window(reader, ctx):
    args = dict(frm="cli/first_token", frm_edge="t0", to="gen/wait", to_edge="t0", key="trace")
    ctx.window = Window(0.5, 11.0, 0.0, 0, True)        # now d's 1.5 s counts too
    assert reader.read(ctx, **args) == pytest.approx(1e3 * (0.2 + 0.4) / 2)
    ctx.window = Window(2.0, 3.0, 0.0, 0, True)         # only b's submit at 2.4
    assert reader.read(ctx, **args) == pytest.approx(400.0)


@pytest.mark.parametrize("args", [
    dict(frm="cli/first_token", frm_edge="t0", to="absent", to_edge="t0", key="trace"),
    dict(frm="absent", frm_edge="t0", to="gen/wait", to_edge="t0", key="trace"),
    # the parent commit: the spans are there and carry no such attribute
    dict(frm="gen/prefill_call", frm_edge="t0", to="gen/prefill_sync", to_edge="t1", key="seq"),
])
def test_none_where_no_key_has_both(reader, ctx, args):
    assert reader.read(ctx, **args) is None
