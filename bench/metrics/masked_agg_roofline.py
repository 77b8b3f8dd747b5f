"""Share of its roofline that the ``masked_agg`` kernel reaches, in %.

The kernel unmasks, decodes and sums the cohort's k ring rows of P
parameters.  The least the operation needs is to read the k masked rows
and the k masks (4 bytes each) and write one float32 sum row: (2k+1)·P·4
bytes, whatever implements it; its arithmetic is a few integer operations
per byte, so memory bounds it.  Least time is those bytes over the chip's
peak HBM bandwidth; the share is that over the device time of the
operations under the ``repro.kernels/masked_agg`` scope.
"""


def least_bytes(k: int, dim: int) -> int:
    return (2 * k + 1) * dim * 4


def read(run):
    s = run.summary
    if s is None or not s.kernel_ns.get("masked_agg"):
        return None
    calls = s.kernel_runs["masked_agg"]
    least_s = calls * least_bytes(run.cohort, run.param_dim) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (s.kernel_ns["masked_agg"] / 1e9)
