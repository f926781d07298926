"""engine_compile_s (s, program counter, model step layer): seconds the
engine's dispatches spent compiling, `serve.compile_s` summed over its
programs (`repro.serve.engine`): each dispatch that added an entry to its
jitted program's cache, timed whole (trace, lower, and compile or load from
the persistent cache).  The counters cover the whole process: the warm-up
and every wave of the window, traced or not.  None when the program keeps
no such counters or counted no compile."""

from bench.counters import scrape


def read(run, registry=None):
    c = scrape(registry) or {}
    if not sum(v for k, v in c.items() if k.startswith("serve.compiles{")):
        return None
    return sum(v for k, v in c.items() if k.startswith("serve.compile_s{"))
