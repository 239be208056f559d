"""Roofline share (%) of the Mamba-2 chunked scan in the window's steps:
the least time the chip could take for the ``ssd`` scope's work in a step,
the larger of its FLOPs over the peak bf16 FLOP/s and its HBM bytes over
the peak bytes/s (the architecture's ``ssd_flops`` and ``ssd_bytes``,
counted from the shapes), over the scope's device time per step."""
from bench import common, flops
from bench.metrics._scopes import hbm_bytes_per_s, step_scope_s


def read(run):
    arch = common.arch(run.config)
    if not hasattr(arch, "ssd_flops"):
        return None
    s = step_scope_s(run, "ssd")
    if not s:
        return None
    kind, mix = run.device["kind"], run.mix
    shape = (run.config, mix["batch"], mix["seq_len"])
    least = max(arch.ssd_flops(*shape) / flops.peak_flops(kind),
                arch.ssd_bytes(*shape) / hbm_bytes_per_s(kind))
    return 100.0 * least / s
