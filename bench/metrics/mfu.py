"""mfu: model FLOPs of the clients' forward and backward passes
(``model_flops_per_round`` of the configuration) times the rounds of the
untraced window, over its wall time, the chips and their bf16 peak."""


def read(ctx):
    flops = ctx["flops_per_round"] * ctx["rounds"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops"]
    return 100.0 * flops / ctx["elapsed_s"] / peak
