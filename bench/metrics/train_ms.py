"""Cohort training: the mean of the ``train`` spans of the traced rounds,
in ms.  The span ends when the clients' losses are on the host, so it holds
batch assembly, the transfer and the device's training."""


def read(run):
    vals = [s.dur_s for _r, inner in run.rounds for s in inner if s.name == "train"]
    return 1e3 * sum(vals) / len(vals) if vals else None
