"""Share of the traced window that lies between decode steps, on the
device's clock, in percent: the seconds from the end of each ``jit_step``
of device 0's ``XLA Modules`` to the start of the next (an admission's
prefills and chunks, the small programs around them, and idle), over
first start to last end of its ``XLA Ops``. It is the device-clock stall
that ``engine_loop.decode_stall_share`` times from the host. A trace
without modules (a rehearsal on the CPU) or with fewer than two steps
reports nothing."""

from benchmark import trace_reduce


def read(m):
    if not m.trace.devices:
        return None
    dev = m.trace.devices[0]
    steps = sorted(
        (e.start_s, e.end_s) for e in dev.modules
        if trace_reduce.module_name(e.name) == "jit_step"
    )
    if len(steps) < 2:
        return None
    t0, t1 = trace_reduce.window(dev)
    gap_s = sum(max(0.0, b[0] - a[1]) for a, b in zip(steps, steps[1:]))
    return 100.0 * gap_s / (t1 - t0)
