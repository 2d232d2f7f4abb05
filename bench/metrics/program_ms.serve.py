"""Device time of the serving bucket programs (``_hist_impl``,
``_fresh_impl``) per ``serve_batch`` call, in milliseconds."""

PROGRAMS = ("jit__hist_impl", "jit__fresh_impl")


def read(ctx):
    mods = ctx["trace"]["modules"]
    sec = sum(mods.get(p, (0.0, 0))[0] for p in PROGRAMS)
    if sec <= 0 or not ctx["calls"]:
        return None
    return 1e3 * sec / ctx["calls"]
