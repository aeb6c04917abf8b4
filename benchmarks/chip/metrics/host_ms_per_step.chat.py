"""Host time per engine step outside the device's work, in ms: the
``bench.step`` spans (one ``Engine.step`` call each) of the traced window,
less the device's busy time in the window, over the number of steps.
Device work runs only inside engine steps, so no clock alignment is
needed."""


def read(ctx):
    t = ctx["trace"]
    st = t["spans"].get("bench.step")
    if not st or not st["count"]:
        return None
    return 1e3 * (st["seconds"] - t["busy_s"]) / st["count"]
