"""The round chunk's share of its roofline, in percent: the least time the
chip could take for a round (the larger of its operations over the bf16
peak and its least bytes over HBM bandwidth, both from shapes in
``bench/flops.py``) over the chunk's device time per round."""

from bench.peaks import peaks

PROGRAM = "jit_chunk"


def read(ctx):
    sec, n = ctx["trace"]["modules"].get(PROGRAM, (0.0, 0))
    if not n or not ctx["rounds"] or sec <= 0:
        return None
    pk = peaks(ctx["device"]["kind"])
    least = max(ctx["round_flops"] / pk["bf16_flops"],
                ctx["round_bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / (sec / ctx["rounds"])
