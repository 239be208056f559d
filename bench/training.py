"""Set-up and checks shared by the training drivers.

Set-up builds one ``Trainer`` (the program's entry point), gives it weights
made from the seed, and drives it through its first steps with the same
``step_once`` the window calls, recording the rows it was fed.  The checks
hold those first steps against the plain reference: each step's loss, the
first gradient as the optimizer got it (read back from AdamW's first moment
after one step), and the change of the parameters after the last step.
"""
from __future__ import annotations

import gc

import jax
import numpy as np

from bench import common, reference, weights

OPT_KEYS = ("lr", "total_steps", "adam_b1", "adam_b2", "adam_eps",
            "weight_decay", "grad_clip")


def build(run, record):
    """A Trainer with weights from the seed; the first ``record`` batches
    it is fed are kept (host copies) for the reference."""
    from repro.configs import CkptIOConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import Trainer
    mix = run.mix
    cfg = common.program_config(run.config)
    mesh = make_host_mesh(devices=run.devices) if run.chips > 1 else None
    tr = Trainer(cfg, batch_size=mix["batch"], seq_len=mix["seq_len"],
                 world_size=mix["world_size"], backend=mix["flavor"],
                 ckpt_dir=run.tmp / "ckpt",
                 ckpt_io=CkptIOConfig(codec=mix["codec"], keep=mix["keep"]),
                 lr=mix["lr"], total_steps=mix["total_steps"],
                 seed=run.seeds["data"], mesh=mesh)
    weights.check_layout(run.config, tr.model.abstract())
    params = weights.make(run.config, run.seeds["weights"])
    opt = jax.jit(tr.optimizer.init)(params)
    if mesh is not None:
        params = jax.device_put(params, tr.param_sh)
        opt = jax.device_put(opt, tr.opt_sh)
    tr.params, tr.opt_state, tr.step = params, opt, 0

    batches = []
    feed = tr.pipeline.next

    def recorded_next():
        b = feed()
        if len(batches) < record:
            batches.append({k: np.array(b[k]) for k in ("tokens", "targets")})
        return b

    tr.pipeline.next = recorded_next
    return tr, batches


def state_leaves(tr):
    """Params and optimizer state in the checkpoint's leaf order."""
    return jax.tree.leaves({"opt": tr.opt_state, "params": tr.params})


def first_steps(run, tr, n):
    """Steps 1..n through ``step_once``; the readings the checks compare."""
    losses, grads = [], None
    for _ in range(n):
        with run.rec.span("step"):
            m = tr.step_once()
        losses.append(float(m["loss"]))
        if grads is None:
            m1 = np.asarray(common.leaf_norms(jax.tree.leaves(tr.opt_state["m"])))
            grads = m1.astype(np.float64) / (1.0 - run.mix["adam_b1"])
    p0 = weights.make(run.config, run.seeds["weights"])
    change = np.asarray(common.diff_norms(jax.tree.leaves(tr.params),
                                          jax.tree.leaves(p0)), np.float64)
    del p0
    return {"losses": losses, "grad_norms": grads, "change_norms": change}


def close(tr):
    """Stop the trainer's threads and free its device state now (it sits in
    reference cycles, so dropping the last name would not)."""
    tr.pipeline.stop()
    if tr.cluster.writer is not None:
        tr.cluster.writer.close()
    tr.params = tr.opt_state = None
    gc.collect()


def step_gaps(prog, ref):
    """The three numbers compared for a run of first steps."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr)))}
    if prog.get("grad_norms") is not None:
        g = ref["grad_norms"]
        keep = g >= 1e-3 * np.median(g)
        out["grad_gap"] = common.worst_leaf_gap(prog["grad_norms"], g)
        out["change_gap"] = common.worst_leaf_gap(prog["change_norms"],
                                                  ref["change_norms"], keep)
    return out


def reference_readings(run, batches, quant=False):
    """The reference over the recorded batches, from weights it makes
    again from the seed."""
    opt = {k: run.mix[k] for k in OPT_KEYS}
    p0 = weights.make(run.config, run.seeds["weights"])
    return reference.train_readings(run.config, opt, p0, batches, quant)
