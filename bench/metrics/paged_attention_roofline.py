"""The paged-attention kernel's share of its roofline, %: over every call
the traced stretch made, the least time the chip could take (the larger
of operations over peak FLOP/s and bytes over peak bandwidth, from the
call's rows and lengths by the configuration's family's
``attention_work``) over the kernel's device time in the trace."""


def read(run):
    t = run.get("trace")
    if not t or t["kernel_s"] <= 0 or not t["work_calls"]:
        return None
    return 100.0 * t["kernel_roofline_s"] / t["kernel_s"]
