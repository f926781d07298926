"""decode_step_ms (ms, device trace, model step layer): device time of the
decode program per execution, over the traced waves."""

from bench.client import DECODE_PROGRAM


def read(run):
    if run.trace is None:
        return None
    times = run.trace.module_times(DECODE_PROGRAM)
    return 1e3 * sum(times) / len(times) if times else None
