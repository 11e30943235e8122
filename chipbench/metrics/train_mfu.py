"""Model FLOP/s utilization of the whole train step, on the host clock:
model FLOPs per step (counted from shapes by `chipbench.flops`), times the
steps of the traced window, over the window's length times the chips
times their bf16 peak. It is `train_tokens_per_s` times the model FLOPs
per token over the peak: the whole step's share of the chip, which bounds
the roofline share of any kernel a later change puts on the step's path."""
from chipbench import peaks


def read(trace, facts, device):
    if not facts.get("steps") or "flops_per_step" not in facts:
        return None
    peak = peaks.peak(device["kind"])["bf16_flops_per_s"]
    return (100.0 * facts["steps"] * facts["flops_per_step"]
            / (facts["window_s"] * device["count"] * peak))
