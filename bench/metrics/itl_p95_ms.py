"""itl_p95_ms (ms, host clock): 95th percentile over the window's requests
of each request's mean gap between tokens, (last token - first token) /
(tokens - 1), as the client stamps them.  A request's mean spans at least 31
gaps, a quarter second or more, so the host clock's half millisecond is
below a hundredth of it."""

from bench.stats import percentile


def read(run):
    per_req = [(r.t_last - r.t_first) / (len(r.output) - 1) * 1e3
               for r in run.window.requests if len(r.output) > 1]
    return percentile(per_req, 95)
