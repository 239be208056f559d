"""Model FLOP utilization (%) of serving: the model FLOPs of every token
prefilled and decoded in the window (matrix products, causal attention at
each position, the output head where logits are needed) over the summed
tick spans times the chips times the chip's peak bf16 FLOP/s."""
from bench import flops


def read(run):
    ticks = run.values.get("tick_s")
    if not ticks or not run.values.get("serve_flops"):
        return None
    peak = flops.peak_flops(run.device["kind"]) * run.chips
    return 100.0 * run.values["serve_flops"] / (sum(ticks) * peak)
