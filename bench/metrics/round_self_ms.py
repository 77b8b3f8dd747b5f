"""Round loop self time: the mean per traced round of the ``round`` span
less its ``train`` and ``eval`` child spans (selection, MARL update,
aggregation and server-update dispatch, carbon accounting), in ms."""


def read(run):
    vals = [r.dur_s - sum(s.dur_s for s in inner if s.name in ("train", "eval") and s.depth == r.depth + 1)
            for r, inner in run.rounds]
    return 1e3 * sum(vals) / len(vals) if vals else None
