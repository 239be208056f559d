"""Model FLOP utilization (%) of the window's training steps: the model
FLOPs of each step (forward and backward from the configuration's shapes,
causal attention included, recomputation not counted) over the summed step
spans times the chips times the chip's peak bf16 FLOP/s."""
from bench import flops


def read(run):
    steps = run.values.get("step_s")
    if not steps:
        return None
    peak = flops.peak_flops(run.device["kind"]) * run.chips
    return 100.0 * run.values["step_flops"] * len(steps) / (sum(steps) * peak)
