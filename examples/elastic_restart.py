"""Elastic restart: checkpoint with one world size, restart with another —
under a different MPI flavor.  The checkpoint format is topology-oblivious
(logical shards + index), so the restore path reassembles and reshards onto
whatever fleet exists (paper §1, §9): here 8 mpich ranks are preempted and
training resumes on 3 exampi ranks.

Uses the production checkpoint engine end-to-end: zlib-compressed
incremental shards, the pipelined double-buffered snapshot (CkptIOConfig),
and the parallel restore engine whose phase timings the Trainer surfaces
after every restart.

  PYTHONPATH=src python examples/elastic_restart.py
"""
import tempfile

from repro.configs import CkptIOConfig, smoke_config
from repro.launch.train import Trainer

CKPT_IO = CkptIOConfig(codec="zlib", incremental=True, pipeline=True,
                       snapshot_batch_mb=8.0, keep=3)


def main():
    cfg = smoke_config("granite-moe-3b-a800m")
    with tempfile.TemporaryDirectory() as td:
        big = Trainer(cfg, batch_size=4, seq_len=32, world_size=8,
                      backend="mpich", ckpt_dir=td, total_steps=60,
                      ckpt_io=CKPT_IO)
        big.init_state()
        big.run(20, log_every=10)
        req = big.checkpoint()
        req.wait()
        big.pipeline.stop()
        ck = big.cluster.writer.latest()
        print(f"trained on 8 ranks, checkpoint at {ck.name} "
              f"(blocking {req.timings['blocking_ms']:.1f}ms, "
              f"persist {req.timings['persist_ms']:.1f}ms)")

        # the job is preempted; only 3 ranks are available afterwards
        small = Trainer(cfg, batch_size=4, seq_len=32, world_size=3,
                        backend="exampi", ckpt_dir=td, total_steps=60,
                        ckpt_io=CKPT_IO)
        small.restore(ck, new_world_size=3, new_backend="exampi")
        t = small.restart_timings
        print(f"restored on {len(small.cluster.ranks)} ranks "
              f"under {small.cluster.backend_name} at step {small.step} "
              f"(rebind {t['rebind_ms']:.1f}ms / arrays {t['arrays_ms']:.1f}ms"
              f" [read {t['read_ms']:.1f}ms, place {t['place_ms']:.1f}ms],"
              f" total {t['total_ms']:.1f}ms)")
        small.run(20, log_every=10)
        small.pipeline.stop()
        small.cluster.writer.close()
        assert small.history[-1]["loss"] < big.history[0]["loss"]
        print("elastic example OK")


if __name__ == "__main__":
    main()
