"""Model FLOP utilization of decoding over the traced stretch, %: tokens
decoded times the model operations per token at each row's context
(the configuration's family's ``token_flops``), over the stretch's
seconds times the chip's bf16 peak."""


def read(run):
    t = run.get("trace")
    if not t or t["window_s"] <= 0 or not t["decode_flops"]:
        return None
    return 100.0 * t["decode_flops"] / (t["window_s"] * t["peak_flops"])
