"""The paged decode attention kernel's share of its roofline: for every
decode launch in the traced stretch, each layer's least time (the larger
of operations over peak and bytes over bandwidth, from the shapes,
``flops.decode_attn_call``), over the kernel's device time in the trace.
Nothing to read where no decode kernel ran."""
import flops


def read(run):
    if run.trace is None or not run.launches:
        return None
    dev = run.trace.kernel_s.get(run.decode_kernel, 0.0)
    if dev <= 0:
        return None
    layers = run.cfg["num_hidden_layers"]
    least = sum(layers * flops.roofline_seconds(
        *flops.decode_attn_call(run.cfg, e["ctx"]), run.peaks)
        for e in run.launches if e["kind"] == "decode"
        and run.trace_t0 <= e["t"] <= run.trace_t1)
    return 100.0 * least / dev
