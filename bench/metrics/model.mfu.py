"""Model FLOPs utilization of the traced window: the FLOPs of every
prefill and decode token the engine ran in it (attention over the keys
each token sees; the head only where logits are used) over window x peak
bf16 FLOP/s, in %."""


def read(ctx):
    if ctx.trace_pc is None or ctx.model is None:
        return None
    a, b = ctx.trace_pc
    m, flops = ctx.model, 0.0
    for s in ctx.log.engine_steps:
        if not a <= 0.5 * (s[1] + s[2]) < b:
            continue
        if s[0] == "prefill":
            _, _, _, off, n, last = s
            flops += sum(m.token_flops(p + 1, False)
                         for p in range(off, off + n))
            flops += 2.0 * m.head_params if last else 0.0
        else:
            flops += sum(m.token_flops(p + 1, True) for p in s[3])
    return 100.0 * flops / ((b - a) * ctx.peaks.bf16_flops) if flops else None
