"""Mean host time of a graph update (``add_edges`` / ``add_nodes``) or a
cache ``refresh`` call, in milliseconds."""


def read(ctx):
    d = ctx["spans"].durations("update", "refresh")
    return 1e3 * sum(d) / len(d) if d else None
