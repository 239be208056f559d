"""A checkout of the benchmark at a size a CPU test can run: the real
BENCHMARK.json, drivers and metric readers, with one tiny configuration and
one traffic file per driver written into a temporary root."""
from __future__ import annotations

import copy
import json
from pathlib import Path

from bench import common

#: the widths of a configuration small enough for the CPU
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 300, "padded_vocab": 512}

#: each traffic at a size a CPU test can run
SMALL = {
    "train_ckpt": {"batch": 2, "seq_len": 32, "save_every": 2},
    "resume_xflavor": {"batch": 2, "seq_len": 32},
    "serve_closed": {"max_len": 128, "prompt_lognormal": [32, 0.8, 4, 96],
                     "output_lognormal": [8, 0.7, 2, 30], "check_tokens": 40},
}


#: limits at the tiny size, set as the cells' are, from CPU readings of
#: the fixed test seed: the program (bf16) reads loss 2.3e-4 / 4.5e-4,
#: gradient 1.1e-3 / 1.5e-3, change 1.1e-3 / 1.3e-3, served 0.014; the
#: float8 control reads loss 4.9e-3, gradient 1.8e-2, change 6.1e-3,
#: served 0.42; half the batch reads gradient 0.12, change 0.19.
TINY_LIMITS = {"loss_gap": 1.5e-3, "grad_gap": 6e-3, "change_gap": 4e-3,
               "served_gap": 0.1, "save_mismatch": 0, "state_mismatch": 0,
               "loss_mismatch": 0, "short_streams": 0, "unchecked_tokens": 0}


def _metric(name, unit, better, moves=None):
    m = {"name": name, "unit": unit, "better": better, "source": "host_clock"}
    return dict(m, layer="serving engine", moves=moves) if moves else m


#: metrics of the drivers that BENCHMARK.json has no cell for yet
UNLISTED = {"serve_closed": {
    "end_to_end": [_metric("serve_tokens_per_s", "tokens/s", "higher"),
                   _metric("itl_p95_ms", "ms", "lower")],
    "per_layer": [_metric("serve_tick_ms", "ms", "lower", "serve_tokens_per_s"),
                  _metric("serve_prefill_ms", "ms", "lower", "itl_p95_ms"),
                  _metric("serve_mfu", "%", "higher", "serve_tokens_per_s")]}}


def tiny_root(tmp, traffic, *, mix=None, limits=None, cell_traffic=None,
              config=None):
    """Write a checkout under ``tmp`` whose cell ``tiny.<cell_traffic>``
    runs the driver of ``traffic`` at a tiny size; ``config`` changes keys
    of the tiny configuration.  Returns (spec, name)."""
    tmp = Path(tmp)
    cell_traffic = cell_traffic or traffic
    for d in ("configs", "workloads", "limits"):
        (tmp / "bench" / d).mkdir(parents=True, exist_ok=True)
    cfg = common.load_json(common.BENCH / "configs" / "granite-3-2b-d6.json")
    cfg.update(TINY)
    cfg.update(config or {})
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    m = common.load_json(common.BENCH / "workloads" / f"{traffic}.json")
    m.update(SMALL[traffic])
    m.update(mix or {})
    (tmp / "bench/workloads" / f"{cell_traffic}.json").write_text(json.dumps(m))
    name = f"tiny.{cell_traffic}"
    spec = copy.deepcopy(common.load_json(common.ROOT / "BENCHMARK.json"))
    real = [w for w in spec["workloads"] if w["traffic"] == traffic]
    lim = dict(TINY_LIMITS, **(limits or {}))
    (tmp / "bench/limits" / f"{name}.json").write_text(json.dumps(lim))
    spec["configs"].append({"name": "tiny", "file": "bench/configs/tiny.json"})
    spec["workloads"].append({"name": name, "config": "tiny",
                              "traffic": cell_traffic, "chips": 1})
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if real and real[0]["name"] in metric.get("workloads", []):
            metric["workloads"].append(name)
    if not real:                    # a driver that no cell uses yet
        for section, metrics in UNLISTED.get(traffic, {}).items():
            spec[section] += [dict(m, workloads=[name]) for m in metrics]
    return spec, name


def run_tiny(tmp, traffic, *, seconds=0.5, trace=0, **kw):
    import jax

    from bench import run as bench_run
    spec, name = tiny_root(tmp, traffic, **kw)
    return bench_run.execute(spec, name, seed=2**40 + 3, seconds=seconds,
                             trace=trace, devices=jax.devices(), root=tmp)
