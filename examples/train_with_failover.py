"""Fault-tolerant training: a rank is killed mid-run; the coordinator
detects it, restarts the cluster from the latest transparent checkpoint —
under a DIFFERENT MPI-implementation-flavor backend — and training
continues (the paper's develop-once-run-everywhere plus the §9
cross-implementation restart, resolved per pair by
``repro.core.restore.translation_plan``: craympi and openmpi are different
families, so every non-constant object is rebuilt from its serialized
description).

Runs the production checkpoint engine (CkptIOConfig: zlib + incremental +
pipelined snapshot — the same knobs ``repro.launch.train`` exposes as
``--ckpt-codec/--ckpt-incremental/--ckpt-pipeline``) and prints the
restart-side phase timings after recovery.

  PYTHONPATH=src python examples/train_with_failover.py
"""
import tempfile

from repro.configs import CkptIOConfig, smoke_config
from repro.launch.train import Trainer


def main():
    cfg = smoke_config("qwen2.5-14b")
    ckpt_io = CkptIOConfig(codec="zlib", incremental=True, pipeline=True,
                           keep=3)
    with tempfile.TemporaryDirectory() as td:
        tr = Trainer(cfg, batch_size=4, seq_len=32, world_size=4,
                     backend="craympi", ckpt_dir=td, total_steps=90,
                     ckpt_io=ckpt_io)
        tr.init_state()
        tr.run(90, ckpt_every=20, kill_rank_at=50,
               new_backend_on_restart="openmpi", log_every=10)
        tr.pipeline.stop()
        tr.cluster.writer.close()
        print(f"\nevents: {[e[0] for e in tr.cluster.events]}")
        t = tr.restart_timings
        print(f"final backend: {tr.cluster.backend_name} "
              f"(restarts: {tr.cluster.restart_count}; last restart: "
              f"rebind {t['rebind_ms']:.1f}ms / arrays {t['arrays_ms']:.1f}ms"
              f" [read {t['read_ms']:.1f}ms, place {t['place_ms']:.1f}ms],"
              f" total {t['total_ms']:.1f}ms)")
        assert tr.cluster.backend_name == "openmpi"
        assert tr.cluster.restart_count == 1
        assert tr.history[-1]["loss"] < tr.history[0]["loss"]
        print("failover example OK")


if __name__ == "__main__":
    main()
