"""Training step's share of the chips' bf16 peak: the configuration's
training FLOPs per token (its reference file counts them) times the tokens
per second of the traced window, over chips times peak."""


def read(ctx):
    if not ctx.get("tokens") or not ctx.get("flops_per_token"):
        return None
    rate = ctx["tokens"] / ctx["window_s"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * ctx["flops_per_token"] * rate / peak
