"""Share of its roofline that the mutual-KL Pallas kernel reaches in the
traced campaign: the least time the chip needs for the kernel calls the
schedule requires (``flops.kl_work``; memory-bound at these widths) over
the kernel's device time in the trace."""

# the Mosaic custom call takes the name of the jitted wrapper that holds
# it (``kl_loss``), e.g. ``vmap_jit_kl_loss__.2`` in the compiled HLO
KERNEL = "kl_loss"


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace.schedule is None:
        return None
    seconds, calls = trace.op_seconds(lambda name: KERNEL in name)
    if calls == 0 or seconds <= 0:
        return None
    a, E = trace.schedule
    fl = ctx["flops"]
    ops, nbytes = fl.kl_work(ctx["kind"], ctx["config"], a, E,
                             ctx["mix"]["seeds_per_campaign"])
    least, _ = fl.roofline_seconds(ops, nbytes, ctx["peaks"])
    return 100.0 * least / seconds
