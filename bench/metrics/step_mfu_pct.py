"""Model operations of the launches in the traced stretch (``flops.py``:
matrix products, causal attention, the head for each used row) over the
summed durations of the engine steps there, the chips used and the
chip's bf16 peak."""
import flops


def read(run):
    if run.trace is None or not run.launches or run.trace.step_s <= 0:
        return None
    total = 0
    for e in run.launches:
        if not run.trace_t0 <= e["t"] <= run.trace_t1:
            continue
        if e["kind"] == "decode":
            total += flops.step_flops(run.cfg, decode_ctx=e["ctx"])
        else:
            total += flops.step_flops(run.cfg, prefill_rows=e["rows"])
    return 100.0 * total / (run.trace.step_s * run.chips
                            * run.peaks["bf16_flops"])
