"""Host seconds of the program's ``make_fleet`` (``sim/simulator.py``):
the ``lc.make_fleet`` span of ``repro.obs``, which holds tenant
generation (``lc.make_tenants``) and the market's build
(``lc.market_build``).  Read in a run that measured a window; a program
without the span gives nothing."""


def read(ctx):
    red = ctx.get("trace")
    if red is None or red.window_ns <= 0:
        return None
    try:
        from repro.obs import host_seconds
    except ImportError:
        return None
    return host_seconds().get("make_fleet")
