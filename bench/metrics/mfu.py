"""Device: the operations the window's tokens require, over the traced
window times the chip's peak, in %. Prompt tokens count their layers
and attention over the prompt so far (the head on the last one only);
decode tokens count their layers, attention over the live context and
the head. FFN work counts the deployed non-zero tiles only; padding
rows, idle slots and zero tiles a kernel reads are not required work."""


def read(rec):
    m = rec["model"]
    flops = sum(m.prefill_flops(n) for n in rec["prompt_lens"])
    flops += sum(m.token_flops(c, head=True) for c in rec["decode_ctx"])
    if not flops:
        return None
    t = rec["trace"]
    return 100.0 * flops / (t["window_s"] * rec["peak"]["bf16_flops_per_s"]
                            * t["chips"])
