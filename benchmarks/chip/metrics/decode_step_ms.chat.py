"""Device time per execution of the persistent decode step, in ms."""


def read(ctx):
    p = ctx["trace"]["programs"].get("decode_step")
    if not p or p["count"] < 1:
        return None
    return 1e3 * p["seconds"] / p["count"]
