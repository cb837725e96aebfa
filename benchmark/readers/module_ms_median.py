"""Median device time, in ms, of one run of the compiled programs whose name
matches ``pattern``, from the profiler's trace."""
from benchlib import stats


def read(ctx, pattern: str):
    runs = ctx.trace.module_runs(pattern)
    return 1e3 * stats.quantile(runs, 0.5) if runs else None
