"""Mean host time of a ``QueryEngine.serve_batch`` call (packing, neighbour
lookup, the bucket programs and the copy back), in milliseconds."""


def read(ctx):
    d = ctx["spans"].durations("serve_batch")
    return 1e3 * sum(d) / len(d) if d else None
