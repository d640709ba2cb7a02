"""Whole-step model FLOP utilization, in %: the FLOPs the model requires
for the prompt and output tokens of the window (live context only, the LM
head once per served token) over the window's time and the chip's bf16
peak."""
import roofline


def read(run):
    w = run.window
    decode_tokens = sum(d.rows for d in run.window_dispatches()
                        if d.kind == "step")
    admitted = sum(1 for r in w.arrived if r.rid in run.rec.admit_start)
    flops = roofline.model_flops(run.spec, w.prompt_tokens, decode_tokens,
                                 admitted, w.prefill_ctx + w.decode_ctx)
    if w.span_s <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (w.span_s * run.peak["bf16_flops_per_s"])
