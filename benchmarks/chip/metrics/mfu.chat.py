"""The whole serving step's share of the chip's peak, in %: model FLOPs of
the real prompt tokens admitted and the tokens decoded in the traced
window, over the window's seconds times peak FLOP/s."""


def read(ctx):
    f, c, pk = ctx["flops"], ctx["config"], ctx["peaks"]
    if pk is None:
        return None
    flops = 0
    for s in ctx["window"]["steps"]:
        flops += sum(f.prompt_flops(c, n) for n in s["prompts"])
        flops += sum(f.decode_flops(c, n) for n in s["contexts"])
    secs = ctx["window"]["seconds"]
    return 100.0 * flops / (secs * pk["flops_bf16"] * ctx["chips"]) if flops else None
