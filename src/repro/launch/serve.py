"""Serving CLI: batched prefill + decode with transparent snapshots.

The ``Server`` class now lives in :mod:`repro.serving.engine` (next to the
multi-tenant ``ServeEngine`` fleet); this module is the thin command-line
driver plus a deprecation shim so ``from repro.launch.serve import Server``
keeps working one release longer (the ``repro.launch.restart`` precedent).
"""
from __future__ import annotations

import argparse
import time
import warnings

import numpy as np

from repro.configs import smoke_config

_MOVED = {"Server": "repro.serving.engine"}


def __getattr__(name):
    new_mod = _MOVED.get(name)
    if new_mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    warnings.warn(
        f"repro.launch.serve.{name} moved to {new_mod}.{name}; "
        "the repro.launch.serve alias will be removed in a future release",
        DeprecationWarning, stacklevel=2)
    import importlib
    return getattr(importlib.import_module(new_mod), name)


def main():
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.engine import Server
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--backend", default="mpich")
    ap.add_argument("--ckpt-dir", default=None,
                    help="snapshot dir; enables mid-decode checkpointing")
    ap.add_argument("--snapshot-at", type=int, default=0,
                    help="take a serving snapshot after N decode steps")
    ap.add_argument("--resume", action="store_true",
                    help="resume the newest resolvable snapshot in "
                         "--ckpt-dir instead of prefilling from scratch")
    ap.add_argument("--restore-backend", default=None,
                    choices=["mpich", "craympi", "openmpi", "exampi",
                             "fabric"],
                    help="backend flavor to restart under on --resume")
    ap.add_argument("--supervise", action="store_true",
                    help="decode under the auto-recovery supervisor "
                         "(requires --ckpt-dir)")
    ap.add_argument("--fault-plan", default=None,
                    help="chaos testing: inline JSON or a path to a JSON "
                         "fault plan (see train.py --fault-plan); implies "
                         "--supervise")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="supervised mode: snapshot every N decode steps "
                         "(default gen/2)")
    ap.add_argument("--backoff-floor", type=float, default=0.05,
                    help="supervisor backoff floor in seconds (0 disables)")
    ap.add_argument("--backoff-ceiling", type=float, default=2.0,
                    help="supervisor backoff ceiling in seconds")
    ap.add_argument("--rescale", default="preempt",
                    choices=["off", "preempt", "all"],
                    help="rescale-rung policy (see train.py --rescale)")
    ap.add_argument("--ram-tier", action="store_true", default=True,
                    help="peer-replicate snapshots to partner RAM and try "
                         "that tier first on recovery (default)")
    ap.add_argument("--no-ram-tier", dest="ram_tier", action="store_false",
                    help="disk-only recovery (skip peer replication)")
    args = ap.parse_args()
    cfg = smoke_config(args.arch)
    srv = Server(cfg, backend=args.backend, ckpt_dir=args.ckpt_dir)
    from repro.launch.train import install_preempt_handler
    install_preempt_handler(srv)
    rng = np.random.default_rng(0)
    shape = (args.batch, cfg.n_codebooks, args.prompt_len) \
        if cfg.n_codebooks > 1 else (args.batch, args.prompt_len)
    prompts = rng.integers(0, cfg.vocab_size, shape, dtype=np.int32)
    pe = rng.standard_normal((args.batch, cfg.img_tokens, 1024)).astype(np.float32) \
        if cfg.img_tokens else None
    gen = args.gen
    first = None
    resumed = False
    supervised = args.supervise or args.fault_plan
    # resume runs FIRST (matching train.py): a preempted supervised server
    # relaunched with --supervise --resume continues mid-sequence instead
    # of silently cold-starting.  Snapshots persist the cache treedef
    # (runtime-state section), so a successful resume skips the prefill
    # entirely — nothing is recomputed.
    if args.resume and args.ckpt_dir:
        ck = srv.resume_latest(new_backend=args.restore_backend)
        if ck is not None:
            resumed = True
            gen = max(args.prompt_len + args.gen - srv.pos, 0)
            first = srv.resume_tok
            print(f"resumed {ck.name} mid-sequence at pos {srv.pos} under "
                  f"{srv.cluster.backend_name}; {gen} tokens left")
    if first is None:
        # cold start — or a snapshot taken before any token was decoded
        # (no seed token recorded): the prefill recomputes the first token
        # and rebuilds the caches it overwrites
        logits = srv.prefill(prompts, pe, pad_to=args.prompt_len + args.gen)
        first = np.argmax(np.asarray(logits)[..., : cfg.vocab_size], axis=-1)
        if cfg.n_codebooks > 1:
            first = first.reshape(args.batch, -1)[:, : cfg.n_codebooks]
        first = first.astype(np.int32)
    if not resumed and args.ckpt_dir and args.snapshot_at and not supervised:
        toks, dt = srv.decode(min(args.snapshot_at, gen), first)
        srv.checkpoint(tag=srv.pos).wait()
        print(f"serving snapshot at pos {srv.pos} -> "
              f"{srv.cluster.writer.latest().name}")
        gen -= len(toks)
        first = toks[-1]
    if supervised:
        if not args.ckpt_dir:
            raise SystemExit("--supervise requires --ckpt-dir")
        from repro.core.ckpt_tiers import ReplicaTier
        from repro.core.faults import FaultInjector, FaultPlan
        from repro.core.supervisor import Supervisor, SupervisorConfig
        plan = FaultPlan.parse(args.fault_plan) if args.fault_plan \
            else FaultPlan()
        srv.start_decode(first)
        t0 = time.time()
        sup_cfg = SupervisorConfig(backoff_floor_s=args.backoff_floor,
                                   backoff_ceiling_s=args.backoff_ceiling,
                                   rescale=args.rescale)
        with FaultInjector(plan) as injector:
            sup = Supervisor(srv, injector=injector, config=sup_cfg,
                             tier=ReplicaTier() if args.ram_tier else None)
            incidents = sup.run(gen,
                                ckpt_every=args.snapshot_every
                                or max(gen // 2, 1))
        dt = time.time() - t0
        for inc in incidents:
            t = inc.timings
            print(f"incident: {inc.kind} rank={inc.rank} "
                  f"pos={inc.step}->{inc.resumed_step} tier={inc.tier} "
                  f"ckpt={inc.ckpt} "
                  f"restore={t['restore_ms']:.1f}ms", flush=True)
        print(f"supervised decode: {gen} tokens x batch {args.batch} in "
              f"{dt:.2f}s, {len(incidents)} incident(s)")
        return
    toks, dt = srv.decode(gen, first)
    print(f"generated {gen} tokens x batch {args.batch} in {dt:.2f}s "
          f"({gen * args.batch / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
