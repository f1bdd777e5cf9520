"""Device time of one training step: the median duration of the
``jit_step`` programs in the trainer's trace (line ``XLA Modules``), in
ms."""

import statistics

from benchmark import trace_reduce


def read(m):
    durations = trace_reduce.module_durations(m.trace, "jit_step")
    return 1e3 * statistics.median(durations) if durations else None
