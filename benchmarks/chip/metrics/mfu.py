"""Step: model FLOP/s utilisation of the measured window, in percent:
the benchmark's model FLOPs per token (no recompute counted) x tokens/s
over chips x the chip's published bf16 peak."""


def read(ctx):
    win = ctx["window"]
    if not win["steps"]:
        return None
    achieved = ctx["flops_per_token"] * win["tokens_per_s"]
    return 100.0 * achieved / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
