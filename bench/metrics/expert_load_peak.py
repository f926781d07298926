"""expert_load_peak (ratio, program counter, experts layer): the busiest
held expert's pairs over the mean pairs of a held expert, pooled over every
expert layer of every decode tick: `serve.experts.held` x
`serve.experts.peak_pairs` / `serve.experts.held_pairs`
(`repro.serve.engine`), over the lanes that carry a request.  1 is an
even load; the grouped expert matmul's time follows its busiest group.  The counters cover the whole process, as
`expert_tokens_per_expert`'s do.  None when the program keeps no such
counters or counted no pair on a held expert."""

from bench.counters import scrape


def read(run, registry=None):
    c = scrape(registry) or {}
    pairs, held = c.get("serve.experts.held_pairs", 0), c.get("serve.experts.held", 0)
    return held * c["serve.experts.peak_pairs"] / pairs if pairs and held else None
