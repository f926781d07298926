"""prefill_roofline (%, device trace, kernels layer): the least time the chip
could take for the traced prefills, as the configuration's family counts
their work (`prefill_work`; for the dense family: 2 x matmul params x plen,
causal attention, one logit row; weights read once, K/V rows written), over
the prefill program's device time."""

from bench.client import PREFILL_PROGRAM
from bench.family import least_time


def read(run):
    if run.trace is None:
        return None
    times = run.trace.module_times(PREFILL_PROGRAM)
    traced = [p for p in run.window.prefills if p.traced]
    if not times or len(times) != len(traced):
        return None
    least = sum(least_time(*run.family.prefill_work(run.dims, p.plen), run.peaks)
                for p in traced)
    return 100.0 * least / sum(times)
