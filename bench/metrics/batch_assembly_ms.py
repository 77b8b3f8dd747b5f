"""Batch assembly: the mean of the ``cohort_inputs`` spans inside the
traced rounds, in ms.  The span covers stacking the cohort's step batches
on the host, enqueueing their copy to the device, the FedProx mu and the
correction broadcast, before the trainer is dispatched.  A program without
the span gives nothing to read."""


def read(run):
    vals = [s.dur_s for _r, inner in run.rounds for s in inner if s.name == "cohort_inputs"]
    return 1e3 * sum(vals) / len(vals) if vals else None
