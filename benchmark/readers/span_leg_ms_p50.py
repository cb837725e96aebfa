"""Median, in ms, of a LEG between two program spans that share a key: from
the ``frm_edge`` (``"t0"`` or ``"t1"``) of the span called ``frm`` to the
``to_edge`` of the span called ``to`` with the same ``key`` (``"trace"``: the
spans' trace id, a request's; any other: an attribute both spans carry, such
as the ``run`` that joins a program run's dispatch to its read), over the legs
whose later edge lies in the window. The earliest span of each name counts
per key. ``None`` when no key has both spans."""
from benchlib import stats


def earliest(spans, name: str, key: str) -> dict:
    out: dict = {}
    for s in spans:
        if s["name"] != name:
            continue
        k = s["trace"] if key == "trace" else s["attrs"].get(key)
        if k is not None and (k not in out or s["t0"] < out[k]["t0"]):
            out[k] = s
    return out


def read(ctx, frm: str, frm_edge: str, to: str, to_edge: str, key: str):
    starts, ends = earliest(ctx.spans, frm, key), earliest(ctx.spans, to, key)
    legs = []
    for k in starts.keys() & ends.keys():
        a, b = starts[k][frm_edge], ends[k][to_edge]
        if ctx.window.t_open < max(a, b) <= ctx.window.t_close:
            legs.append(b - a)
    return 1e3 * stats.quantile(legs, 0.5) if legs else None
