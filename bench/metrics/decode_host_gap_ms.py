"""decode_host_gap_ms (ms, program counter, scheduler layer): the engine's
mean host gap before a decode dispatch, 1000 x `serve.decode.host_gap_s` /
`serve.decode.host_gaps` (`repro.serve.engine`): from the last device
result's read-back to the decode dispatch's return, counted while the
engine holds work.  The counters cover the whole process: the warm-up and
every wave of the window, traced or not.  None when the program keeps no
such counters or counted no gap."""

from bench.counters import scrape


def read(run, registry=None):
    c = scrape(registry) or {}
    n = c.get("serve.decode.host_gaps", 0)
    return 1e3 * c["serve.decode.host_gap_s"] / n if n else None
