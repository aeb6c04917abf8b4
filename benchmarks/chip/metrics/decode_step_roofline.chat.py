"""The decode step's share of its roofline, in %: the least time the chip
could take for the window's decode steps (the larger of their model FLOPs
over peak FLOP/s and of the bytes they must move over peak bandwidth:
every weight once, and the keys and values each row attends to) over the
decode step's device time in the trace."""


def read(ctx):
    p = ctx["trace"]["programs"].get("decode_step")
    if not p or p["seconds"] <= 0 or ctx["peaks"] is None:
        return None
    f, c, pk = ctx["flops"], ctx["config"], ctx["peaks"]
    flops = bytes_ = 0
    for s in ctx["window"]["steps"]:
        if s["contexts"]:
            bytes_ += f.decode_step_bytes(c, s["contexts"])
            flops += sum(f.decode_flops(c, n) for n in s["contexts"])
    if not bytes_:
        return None
    least = max(flops / pk["flops_bf16"], bytes_ / pk["hbm_bytes_per_s"])
    return 100.0 * least / p["seconds"]
