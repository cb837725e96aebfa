"""Mean device time, in us, of one call of a kernel: the summed device time
of exactly that kernel's events in the traced window over their number."""


def read(ctx, kernel: str):
    module = ctx.kernels.get(kernel)
    if module is None:
        return None
    seconds, events = ctx.trace.op_seconds(module.EVENTS)
    return 1e6 * seconds / events if events else None
