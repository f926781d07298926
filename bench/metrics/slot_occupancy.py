"""slot_occupancy (%, counted by the client, scheduler layer): slot-steps
that carried an unfinished request over n_slots x decode ticks, over the
whole window."""


def read(run):
    ticks = run.window.ticks
    if not ticks:
        return None
    return 100.0 * sum(t.active for t in ticks) / (run.n_slots * len(ticks))
