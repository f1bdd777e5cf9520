"""Share of the device's busy time spent in prompt processing: the
``jit_prefill`` and ``jit_chunk`` programs (line ``XLA Modules``) over the
union of all device operations, in percent."""

from benchmark import trace_reduce


def read(m):
    busy_s, _ = trace_reduce.busy_and_window(m.trace)
    if busy_s <= 0 or not any(dev.modules for dev in m.trace.devices):
        return None
    prompt = trace_reduce.module_durations(m.trace, "jit_prefill", "jit_chunk")
    return 100.0 * sum(prompt) / len(m.trace.devices) / busy_s
