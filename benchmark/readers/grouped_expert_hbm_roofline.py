"""A prefill's grouped expert products' share of what the chip's memory
allows: one expert layer's held weights (``kernels/<kernel>.py``'s
``layer_bytes``: every matrix of every expert held here, once) for every two
of the kernel's events (one layer's two products over one prompt's pairs),
over their summed device time and the HBM bandwidth. The weights are what
such a product must read: a prompt's rows are a few dozen an expert, so the
operations are a few percent of the peak's time.

The count assumes every held expert gets a row in every prompt. By the mixes'
prompt lengths that holds in ``replies`` (256-1,024 rows, top 4 of 32) and
``longform`` (512-2,048 rows, top 6: 16 held), and in ``answers`` (64-512
rows, top 22 of 512, 128 held) at most about 1% of the held experts go
without a row in a prompt (mostly the shortest): there the share reads up to
about 1% over what was read. A program without the events, or a
configuration without held experts, gives nothing to read."""


def read(ctx, kernel: str):
    module = ctx.kernels.get(kernel)
    if module is None:
        return None
    per_layer = module.layer_bytes(ctx.config)
    seconds, events = ctx.trace.op_seconds(module.EVENTS)
    if per_layer is None or events < 2:
        return None
    return 100.0 * (events / 2 * per_layer / ctx.peaks["hbm_bytes_per_s"]) / seconds
