"""Device time of the fused round chunk (``jit_chunk``: vmapped LocalUpdate,
merge and write-back) per round traced, in milliseconds."""

PROGRAM = "jit_chunk"


def read(ctx):
    sec, n = ctx["trace"]["modules"].get(PROGRAM, (0.0, 0))
    if not n or not ctx["rounds"]:
        return None
    return 1e3 * sec / ctx["rounds"]
