"""Each driver runs its whole loop once at a tiny size on the CPU, with the
cell's own limits, and comes out correct; a traced run reads the per-layer
metrics it can read without a device trace."""
from __future__ import annotations

import pytest

from bench.tests import helpers

E2E = {"train_ckpt": ["train_tokens_per_s", "setup_s"],
       "resume_xflavor": ["resume_ms", "setup_s"],
       "serve_closed": ["serve_tokens_per_s", "itl_p95_ms", "setup_s"]}

LAYER = {"train_ckpt": ["train_step_ms", "ckpt_blocking_ms"],
         "resume_xflavor": ["restore_total_ms", "resume_first_step_ms"],
         "serve_closed": ["serve_tick_ms", "serve_prefill_ms"]}


@pytest.mark.parametrize("traffic", sorted(E2E))
def test_driver_runs_once_and_is_correct(tmp_path, traffic):
    res = helpers.run_tiny(tmp_path, traffic)
    assert res["correct"], res["checks"]
    assert sorted(res["metrics"]) == sorted(E2E[traffic])
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("traffic", sorted(LAYER))
def test_traced_run_reads_the_span_metrics(tmp_path, traffic, monkeypatch):
    from bench import flops
    # the peaks table knows chips only; give the CPU a stand-in peak here
    monkeypatch.setattr(flops, "peak_flops", lambda kind: 1e12)
    res = helpers.run_tiny(tmp_path, traffic, trace=1)
    assert res["correct"], res["checks"]
    for name in LAYER[traffic]:
        assert res["metrics"][name]["value"] > 0
    # the CPU has no TPU plane: the device metrics stay silent, never 0
    assert not any(k.startswith("device_idle_share") for k in res["metrics"])
