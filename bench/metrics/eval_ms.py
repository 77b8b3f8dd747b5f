"""Evaluation: the mean of the ``eval`` spans inside the traced rounds, in
ms.  Rounds that do not evaluate give nothing to read."""


def read(run):
    vals = [s.dur_s for _r, inner in run.rounds for s in inner if s.name == "eval"]
    return 1e3 * sum(vals) / len(vals) if vals else None
