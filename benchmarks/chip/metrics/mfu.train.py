"""The training step's share of the chips' peak, in %: model FLOPs of the
sequences trained in the traced window (forward and backward, no
recomputation, causal attention) over the window's seconds times the
chips' peak FLOP/s."""


def read(ctx):
    f, c, pk, job = ctx["flops"], ctx["config"], ctx["peaks"], ctx["traffic"]
    w = ctx["window"]
    if pk is None or not w["steps"]:
        return None
    flops = f.train_flops_per_sequence(c, job["seq_len"]) * job["batch"] * w["steps"]
    return 100.0 * flops / (w["seconds"] * pk["flops_bf16"] * ctx["chips"])
