"""Operations of the forward and backward passes that the window's
schedules need (``flops.train_flops``), over the window's seconds times
the chips times the chip's bf16 peak."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace.schedule is None:
        return None
    a, E = trace.schedule
    per_campaign = ctx["flops"].train_flops(
        ctx["kind"], ctx["config"], a, E, ctx["mix"]["seeds_per_campaign"])
    peak = ctx["peaks"]["bf16_tflops"] * 1e12
    return (100.0 * per_campaign * ctx["campaigns"]
            / (ctx["window_s"] * ctx["chips"] * peak))
