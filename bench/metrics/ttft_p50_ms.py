"""ttft_p50_ms (ms, host clock): median over the window's requests of the
time from the wave's submission to the request's first token.

Every request of a wave gets its first token from the same `schedule()`
call, which prefills the whole wave, so a window's independent samples are
its waves (18 to 24), not its requests.  The median is the highest
percentile that leaves ten or more of them beyond it; a 95th percentile
would be the one or two slowest waves, which a single host stall moves."""

from bench.stats import percentile


def read(run):
    return percentile([(r.t_first - r.t_submit) * 1e3
                       for r in run.window.requests if r.output], 50)
