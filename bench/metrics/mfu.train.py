"""Model FLOP utilisation of the whole training step, in percent: model
operations per round (loss pass, batch forward and backward, and the
round's share of evaluations, from shapes in ``bench/flops.py``) times
rounds per second, over the chip's bf16 peak."""

from bench.peaks import peaks


def read(ctx):
    if not ctx["rounds_per_s"]:
        return None
    flops = ctx["round_flops"] + ctx["evals_per_round"] * ctx["eval_flops"]
    return (100.0 * flops * ctx["rounds_per_s"]
            / peaks(ctx["device"]["kind"])["bf16_flops"])
