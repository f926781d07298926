"""mfu (%, device trace, device layer): model FLOPs of every prompt and
output token processed in the traced window (dense matmuls, causal
attention and the logit rows that were needed, bench/flops.py), over the
traced window x chips x peak bf16 FLOP/s.  It bounds the kernels' roofline
shares: a kernel taken off the path leaves its own share silent, not this."""

from bench import flops


def read(run):
    if run.trace is None or not run.trace.window_s:
        return None
    total = sum(flops.prefill_work(run.dims, p.plen)[0]
                for p in run.window.prefills if p.traced)
    total += sum(flops.decode_work(run.dims, [t.position] * t.active)[0]
                 for t in run.window.ticks if t.traced)
    if not total:
        return None
    return 100.0 * total / (run.trace.window_s * run.chips * run.peaks["bf16_flops"])
