"""expert_tokens_per_expert (pairs, program counter, experts layer): the
(token, expert) pairs routed to the experts held here, per held expert and
per expert layer of each decode tick: `serve.experts.held_pairs` /
(`serve.experts.layer_steps` x `serve.experts.held`) (`repro.serve.engine`).
The decode program counts them on the device, for the lanes that carry a
request (it computes free lanes too, and does not count them), so the
reading follows the load that requests bring, about occupancy x 12 at 128
slots; the engine reads them back only when the registry is scraped, after
the window.  The counters cover the whole process: the
warm-up and every wave.  None when the program keeps no such counters or
counted no decode tick."""

from bench.counters import scrape


def read(run, registry=None):
    c = scrape(registry) or {}
    steps, held = c.get("serve.experts.layer_steps", 0), c.get("serve.experts.held", 0)
    return c["serve.experts.held_pairs"] / (steps * held) if steps and held else None
