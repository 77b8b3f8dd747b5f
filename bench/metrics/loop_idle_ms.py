"""Round-loop idle time: the device's idle time per traced round while the
innermost open host span is one of the round loop's own (selection,
aggregation and server-update dispatch, carbon accounting, the MARL update,
or the round itself), in ms.  Idle gaps are labelled from the profiler's
trace of the first chip; nothing without a trace."""

LOOP_SPANS = ("round", "select", "aggregate", "server_update", "accounting", "policy_update")


def read(run):
    s = run.summary
    if s is None or not run.rounds:
        return None
    idle_ns = sum(s.idle_ns_by_span.get(name, 0.0) for name in LOOP_SPANS)
    return idle_ns / 1e6 / len(run.rounds)
