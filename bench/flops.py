"""Peak FLOP/s of the chips the benchmark runs on.  A model's FLOPs are
its architecture's (``bench/archs/<model_type>.py``)."""
from __future__ import annotations

import json
from pathlib import Path


def peak_flops(device_kind):
    """Peak bf16 FLOP/s of one chip; an unknown device is an error."""
    table = json.loads((Path(__file__).resolve().parent / "peaks.json")
                       .read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]["bf16_flops_per_s"]
