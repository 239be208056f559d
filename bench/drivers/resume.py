"""Time to resume after the loss of a rank, across MPI implementations.

Set-up takes three steps (the reference follows them), saves under the
traffic's ``flavor``, waits for the commit, takes step 4 (its loss is the
pre-kill loss), then makes one warm-up resume.  Each resume in the window kills the last rank, drops the
state on the chip (the node's memory is lost with it), restores the saved
checkpoint through ``Trainer.restore`` under the next flavor of
``flavors`` (each of another MPI family than the one that wrote it), and
takes step 4 again until its loss is on the host.  The window is a whole
number of rounds over ``flavors``, so every window holds the same mix.  Right after the restore
a fingerprint of the restored state is dispatched to the device.  The
checkpoint was just written, so its reads hit the host's page cache: this
measures placement and rebinding, not the disk.

After the window: every restored state against the saved one, every
post-restore loss against the pre-kill loss (both exact), and the three
set-up steps against the reference as the training cell compares them:
their losses, the first gradient and the parameters' change.
"""
from __future__ import annotations

import os
import time

import numpy as np

from bench import common, training


def run(r):
    mix = r.mix
    tr, batches = training.build(r, record=3)
    flavors = mix["flavors"]
    try:
        prog = training.first_steps(r, tr, 3)
        with r.rec.span("ckpt"):
            req = tr.checkpoint()
        saved = np.asarray(common.fingerprint(training.state_leaves(tr)))
        req.wait(timeout=900)
        os.sync()
        ckpt = req.directory
        with r.rec.span("step"):
            before = np.float32(tr.step_once()["loss"])

        def resume(flavor):
            with r.rec.span("resume"):
                tr.cluster.kill_rank(len(tr.cluster.ranks) - 1)
                tr.params = tr.opt_state = None
                with r.rec.span("restore"):
                    tr.restore(ckpt, new_backend=flavor)
                fp = common.fingerprint(training.state_leaves(tr))
                with r.rec.span("first_step"):
                    loss = np.float32(tr.step_once()["loss"])
            return fp, loss, dict(tr.restart_timings), flavor

        done = [resume(flavors[-1])]
        with r.window():
            while True:
                for flavor in flavors:
                    done.append(resume(flavor))
                if time.perf_counter() - r.t_open >= r.seconds:
                    break
        window = done[1:]
        r.values["resume_ms"] = float(np.mean(
            r.rec.durations("resume", r.t_open, r.t_close))) * 1e3
        r.values["restore_total_ms"] = [t["total_ms"] for _, _, t, _ in window]
        r.values["first_step_ms"] = [
            d * 1e3 for d in r.rec.durations("first_step", r.t_open, r.t_close)]
        r.attempted = len(window)
        r.log(f"{len(window)} resumes under {[f for *_, f in window]}; "
              f"restart_timings {[t for _, _, t, _ in window]}")
        results = [(np.asarray(fp), loss) for fp, loss, _, _ in done]
    finally:
        training.close(tr)
    r.check("state_mismatch",
            sum(not np.array_equal(fp, saved) for fp, _ in results), 0)
    r.check("loss_mismatch",
            sum(loss.tobytes() != before.tobytes() for _, loss in results), 0)
    gaps = training.step_gaps(prog, training.reference_readings(r, batches))
    for name, value in gaps.items():
        r.check(name, value, r.limits[name])
