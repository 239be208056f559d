"""Closed-loop training with a checkpoint every ``save_every`` steps.

Set-up: weights from the seed, three steps (the reference follows them),
the fingerprint program warmed on the state, and the page cache flushed.
The window is a whole number of save cycles (``save_every`` steps and one
save): it opens after set-up and closes at the first cycle boundary after
``--seconds``, so every window holds the same mix of steps and stalls.
Right after each save returns, a fingerprint of the state it was taken
from is dispatched to the device (a few milliseconds of device time per
cycle, never waited for in the window).  After the window every save is
read back and compared with its fingerprint, and the first steps with the
reference.
"""
from __future__ import annotations

import os
import time

import numpy as np

from bench import common, training


def run(r):
    from repro.core.restore import load_arrays
    mix = r.mix
    tr, batches = training.build(r, record=3)
    try:
        prog = training.first_steps(r, tr, 3)
        saves = []
        common.fingerprint(training.state_leaves(tr)).block_until_ready()
        # the window starts from a flushed page cache, so that its save
        # competes with no earlier writeback
        os.sync()
        steps = 0
        with r.window():
            while True:
                for _ in range(mix["save_every"]):
                    with r.rec.span("step"):
                        tr.step_once()
                    steps += 1
                with r.rec.span("ckpt"):
                    req = tr.checkpoint()
                saves.append((req, common.fingerprint(training.state_leaves(tr))))
                if time.perf_counter() - r.t_open >= r.seconds:
                    break
        window_s = r.t_close - r.t_open
        tokens = mix["batch"] * mix["seq_len"]
        r.values["train_tokens_per_s"] = steps * tokens / window_s
        r.values["step_s"] = r.rec.durations("step", r.t_open, r.t_close)
        r.values["step_flops"] = common.arch(r.config).train_step_flops(
            r.config, mix["batch"], mix["seq_len"])
        r.attempted = steps + len(saves)
        for req, _ in saves:
            req.wait(timeout=900)
        r.values["ckpt_blocking_ms"] = [q.timings["blocking_ms"] for q, _ in saves]
        r.log(f"{steps} steps and {len(saves)} saves in {window_s:.6f} s; "
              f"blocking_ms {r.values['ckpt_blocking_ms']}, persist_ms "
              f"{[q.timings['persist_ms'] for q, _ in saves]}")
        want = [np.asarray(fp) for _, fp in saves]
    finally:
        training.close(tr)

    bad = 0
    for (req, _), fp in zip(saves, want):
        n = len(common.load_json(req.directory / "manifest.json")["leaves"])
        got = load_arrays(req.directory, [None] * n)[:len(fp)]
        bad += int(not np.array_equal(np.asarray(common.fingerprint(got)), fp))
        del got
    r.check("save_mismatch", bad, 0)
    gaps = training.step_gaps(prog, training.reference_readings(r, batches))
    for name, value in gaps.items():
        r.check(name, value, r.limits[name])
    r.log(f"losses {prog['losses']}")
