"""Device time of the server's full-graph eval program
(``jit__eval_logits``) per evaluation, in milliseconds."""

PROGRAM = "jit__eval_logits"


def read(ctx):
    sec, n = ctx["trace"]["modules"].get(PROGRAM, (0.0, 0))
    evals = ctx["rounds"] * ctx["evals_per_round"]
    if not n or not evals:
        return None
    return 1e3 * sec / evals
