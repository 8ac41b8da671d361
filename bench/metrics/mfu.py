"""Model FLOP utilization of the whole step: the window's tokens per
second times the model's FLOPs per token (``bench.flops``), over the
chips' bf16 peak (``bench/peaks.json``)."""


def read(ctx):
    return (100.0 * ctx["tokens_per_s"] * ctx["flops_per_token"]
            / (ctx["chips"] * ctx["peak_flops"]))
