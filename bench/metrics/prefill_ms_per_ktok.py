"""prefill_ms_per_ktok (ms/ktok, device trace, model step layer): device
time of the prefill programs per 1000 prompt tokens, over the traced waves."""

from bench.client import PREFILL_PROGRAM


def read(run):
    if run.trace is None:
        return None
    times = run.trace.module_times(PREFILL_PROGRAM)
    tokens = sum(p.plen for p in run.window.prefills if p.traced)
    if not times or not tokens or len(times) != sum(p.traced for p in run.window.prefills):
        return None
    return 1e3 * sum(times) / (tokens / 1e3)
