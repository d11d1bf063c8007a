"""Model FLOP/s utilization: the FLOPs one round's forward and backward
passes need (``train_flops_per_round`` of the configuration's reference
file, from shapes, no recomputation counted) over the round period of
the untraced part of the window, over chips x the bf16 peak."""


def read(ctx):
    win = ctx["window"]
    n = win["first_traced"] - win["first"]
    if n <= 0:
        return None
    period = (win["t_trace"] - win["t_start"]) / n
    flops = ctx["ref"].train_flops_per_round(ctx["run"].ref_spec,
                                             ctx["cell"])
    return 100.0 * flops / period / (ctx["chips"]
                                     * ctx["peaks"]["bf16_flops"])
