"""The cohort trainer's device time per traced round and chip, in ms: the
time the profiler's trace gives the ``jit_cohort_train`` program, summed
over the chips, over the traced rounds and the chips.  Nothing where the
trace has no program of that name."""

PROGRAM = "jit_cohort_train"


def read(run):
    s = run.summary
    if s is None or not run.rounds or not s.module_ns.get(PROGRAM):
        return None
    return s.module_ns[PROGRAM] / 1e6 / len(run.rounds) / run.chips
