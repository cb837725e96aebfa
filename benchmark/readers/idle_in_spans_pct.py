"""Share of the device's idle time, in the traced window, that lies under the
program spans named in ``names`` (less the spans in ``minus``: ``gen/step``
minus ``gen/step_sync`` is the host's part of a step) or, with ``outside``,
under NO span of those names (idle because nobody holds the engine).

An overlap of intervals, not the innermost-span rule of the breakdown's
``idle_gaps``: a span on another thread cannot take a gap away, so the shares
of spans that tile one thread sum to what that thread saw of the idle time.
``None`` when the window holds no span of the names asked for."""
from benchlib.spans import union_length


def merged(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def intersect(a, b):
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append([s, e])
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(a, t0, t1):
    """[t0, t1] less the sorted, disjoint intervals ``a`` (all inside it)."""
    out, cursor = [], t0
    for s, e in a:
        if s > cursor:
            out.append([cursor, s])
        cursor = max(cursor, e)
    if cursor < t1:
        out.append([cursor, t1])
    return out


def read(ctx, names=(), minus=(), outside=()):
    if not ctx.trace.devices:
        return None
    t0, t1 = ctx.trace.t0, ctx.trace.t1
    gaps = merged(ctx.trace.gaps())
    idle = union_length(gaps)
    if idle <= 0.0:
        return None

    def under(wanted):
        return merged((max(s["t0"], t0), min(s["t1"], t1)) for s in ctx.spans
                      if s["name"] in wanted and s["t1"] > t0 and s["t0"] < t1)

    cover = under(outside or names)
    if not cover:
        return None
    if outside:
        region = complement(cover, t0, t1)
    else:
        region = intersect(cover, complement(under(minus), t0, t1))
    return 100.0 * union_length(intersect(gaps, region)) / idle
