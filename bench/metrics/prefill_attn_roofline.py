"""The paged prefill attention kernel's share of its roofline, as
``decode_attn_roofline`` for the chunked prefill launches
(``flops.prefill_attn_call``)."""
import flops


def read(run):
    if run.trace is None or not run.launches:
        return None
    dev = run.trace.kernel_s.get(run.prefill_kernel, 0.0)
    if dev <= 0:
        return None
    layers = run.cfg["num_hidden_layers"]
    least = sum(layers * flops.roofline_seconds(
        *flops.prefill_attn_call(run.cfg, e["rows"]), run.peaks)
        for e in run.launches if e["kind"] == "prefill"
        and run.trace_t0 <= e["t"] <= run.trace_t1)
    return 100.0 * least / dev
