"""Device time, in ms per run of a program, of the events a ``kernels/``
file names: the summed device time in the traced window of the events whose
name matches that file's ``EVENTS`` over the number of runs of the programs
whose name matches ``pattern`` (one decode step is one run of ``jit_step``).
A mixer's events are told apart by the shapes only it produces."""


def read(ctx, kernel: str, pattern: str):
    module = ctx.kernels.get(kernel)
    runs = ctx.trace.module_runs(pattern)
    if module is None or not runs:
        return None
    seconds, events = ctx.trace.op_seconds(module.EVENTS)
    return 1e3 * seconds / len(runs) if events else None
