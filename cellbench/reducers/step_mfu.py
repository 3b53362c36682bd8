"""The whole grad step's share of the chips' peak: matrix-multiply FLOPs a
grad step (``flops_per_grad_step``, an argument: ``cellbench/torso_cost.py``'s
count for the configuration, which a test holds it equal to) × grad steps per
second of the timed window ÷ (chips × the published peak) × 100. An
end-to-end utilisation from the host's clock, not a kernel's roofline share;
the FLOPs are the required ones, so it cannot pass 100. No rate or no peak
(a CPU rehearsal) gives nothing."""


def reduce(ctx, flops_per_grad_step: float):
    rate = ctx.values.get("window.grad_steps_per_s")
    chips = ctx.values.get("device.count")
    peak = ctx.values.get("peaks.flops_per_s")
    if None in (rate, chips, peak) or not chips * peak:
        return None
    return 100.0 * float(flops_per_grad_step) * float(rate) / (float(chips) * float(peak))
