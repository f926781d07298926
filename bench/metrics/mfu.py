"""mfu (%, device trace, device layer): model FLOPs of every prompt and
output token processed in the traced window (the matmuls, causal attention
and logit rows that were needed, as the configuration's family counts them
in `prefill_work` and `decode_work`), over the traced window x chips x peak
bf16 FLOP/s.  It bounds the kernels' roofline shares: a kernel taken off the
path leaves its own share silent, not this."""


def read(run):
    if run.trace is None or not run.trace.window_s:
        return None
    total = sum(run.family.prefill_work(run.dims, p.plen)[0]
                for p in run.window.prefills if p.traced)
    total += sum(run.family.decode_work(run.dims, [t.position] * t.active)[0]
                 for t in run.window.ticks if t.traced)
    if not total:
        return None
    return 100.0 * total / (run.trace.window_s * run.chips * run.peaks["bf16_flops"])
