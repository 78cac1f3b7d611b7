"""Cascade waves per epoch: the delta of the engine's ``waves``
counter (``BatchEngine._cascade``'s while-loop iterations) over the
window's epochs."""


def read(ctx):
    waves = ctx.get("counters", {}).get("waves")
    if waves is None or not ctx["epochs"]:
        return None
    return waves / ctx["epochs"]
