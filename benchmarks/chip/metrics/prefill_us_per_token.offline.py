"""Device time of the persistent prefill per real (unpadded) prompt token
admitted in the traced window, in us."""


def read(ctx):
    p = ctx["trace"]["programs"].get("prefill_step")
    toks = sum(sum(s["prompts"]) for s in ctx["window"]["steps"])
    if not p or not toks:
        return None
    return 1e6 * p["seconds"] / toks
