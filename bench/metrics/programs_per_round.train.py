"""Programs the device ran per round traced: every execution on the
``XLA Modules`` line in the window (the round chunks, the evaluations and
the eager operations between them), per device, over the rounds."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["devices"] or not tr["modules"] or not ctx["rounds"]:
        return None
    runs = sum(n for _, n in tr["modules"].values())
    return runs / tr["devices"] / ctx["rounds"]
