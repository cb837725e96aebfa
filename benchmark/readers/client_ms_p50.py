"""Median, in ms, of a client-clock series (``ttft``: submit to first token;
``gaps``: between consecutive tokens of a stream) over the samples that
ended in the window."""
from benchlib import stats


def read(ctx, series: str):
    values = stats.in_window(getattr(ctx, series), ctx.window)
    return 1e3 * stats.quantile(values, 0.5) if values else None
