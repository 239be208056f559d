"""Subprocess scenario: 8 host devices. Train on mesh (2,4) with 4 ranks under
craympi; checkpoint; elastically restart on mesh (4,2) with 2 ranks under
openmpi; verify the training trajectory continues bit-compatibly (modulo
reduction-order noise from the new sharding)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import sys
import tempfile

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from dataclasses import replace
from repro.configs import smoke_config
from repro.launch.train import Trainer
from repro.launch.mesh import make_host_mesh


def main():
    assert len(jax.devices()) == 8
    cfg = replace(smoke_config("granite-3-2b"), n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=256, vocab_pad_multiple=64)
    tmp = tempfile.mkdtemp()

    mesh_a = make_host_mesh((2, 4), ("data", "model"))
    tr = Trainer(cfg, batch_size=8, seq_len=16, world_size=4,
                 backend="craympi", ckpt_dir=tmp, mesh=mesh_a, total_steps=40)
    tr.init_state()
    tr.run(10, ckpt_every=10, log_every=5)
    loss_at_10 = tr.history[-1]["loss"]
    tr.run(5, log_every=5)                       # reference continuation
    ref_loss_15 = tr.history[-1]["loss"]
    tr.pipeline.stop()
    # the step-10 checkpoint persists in the background: wait for its
    # COMMIT before asking for the latest committed image (reading
    # latest() mid-write is a race — the write usually, not always, wins)
    tr.cluster.writer.wait_idle()
    ck = tr.cluster.writer.latest()
    assert ck is not None, "no checkpoint committed"

    # elastic restart: different mesh shape, world size, AND backend
    mesh_b = make_host_mesh((4, 2), ("data", "model"))
    tr2 = Trainer(cfg, batch_size=8, seq_len=16, world_size=2,
                  backend="openmpi", ckpt_dir=tmp, mesh=mesh_b, total_steps=40)
    tr2.restore(ck, new_world_size=2, new_backend="openmpi")
    assert tr2.step == 10, tr2.step
    assert len(tr2.cluster.ranks) == 2
    tr2.run(5, log_every=5)
    new_loss_15 = tr2.history[-1]["loss"]
    tr2.pipeline.stop()

    err = abs(new_loss_15 - ref_loss_15) / max(abs(ref_loss_15), 1e-9)
    print(f"loss@10={loss_at_10:.6f} ref@15={ref_loss_15:.6f} "
          f"elastic@15={new_loss_15:.6f} rel_err={err:.2e}")
    assert err < 5e-3, "elastic continuation diverged"
    # params sharded over the NEW mesh
    leaf = jax.tree.leaves(tr2.params)[0]
    assert leaf.sharding.mesh.devices.shape == (4, 2)
    print("ELASTIC_SCENARIO_OK")


if __name__ == "__main__":
    main()
