"""decode_roofline (%, device trace, kernels layer): the least time the chip
could take for the traced decode ticks, over the decode program's device
time.  The least time of a tick is the larger of its needed FLOPs over peak
FLOP/s and its needed bytes over peak bandwidth, as the configuration's
family counts them (`decode_work`; for the dense family: weights read once,
the K/V rows at or below each active slot's position read, one row written
per active slot).  Finished slots, rows past the position and cache copies
are not needed work."""

from bench.client import DECODE_PROGRAM
from bench.family import least_time


def read(run):
    if run.trace is None:
        return None
    times = run.trace.module_times(DECODE_PROGRAM)
    ticks = [t for t in run.window.ticks if t.traced]
    if not times or len(times) != len(ticks):
        return None
    least = sum(least_time(*run.family.decode_work(run.dims, [t.position] * t.active),
                           run.peaks) for t in ticks)
    return 100.0 * least / sum(times)
