#!/usr/bin/env python3
"""Drive the checkpoint-restart main path once on TPU, at published widths.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the elastic 4-chip -> 2-chip phase only

One chip runs two phases through the entry points a user calls:

1. Train -> save -> kill -> cross-flavor resume (``launch.train.Trainer``).
   granite-3-2b at every published width, cut from 40 layers to 6 (a dense
   model: one layer is a whole period).  At batch 4 x 2048 the jitted step
   needs about 5.7 GB of donated state (bf16 params, f32 AdamW moments) and
   6.6 GB of temporaries; 8 layers would need 14.1 GB, and a restore could
   then not hold the old and the restored state side by side in 16 GB.
   Three steps, a checkpoint, step 4; then rank 1 dies and the trainer
   restores the step-3 checkpoint under openmpi (written under craympi, the
   other MPI family) and takes step 4 again.  The restored state must sit on
   the chip, hash to the step-3 digests, and give a bit-identical step-4
   loss.
2. Fleet serving (``serving.engine.ServeEngine``) of the full 40-layer
   granite-3-2b: four requests of 256 and 512 prompt tokens, 16 new tokens
   each, continuous batching over the paged cache pool.  One request's last
   decode logits, taken through the cache, must agree with a fresh prefill
   of its prompt plus generated tokens.

``--four-chips`` trains the 6-layer model on a mesh over four chips,
checkpoints, restores under another flavor onto a mesh of two of them, checks
the restored state byte for byte and takes one step.

Weights and data come from fixed seeds.  Checkpoints go to a temporary
directory that is removed at the end.  The script exits non-zero without a
result when JAX finds no TPU; its last line is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TRAIN_LAYERS = 6
TRAIN_BATCH, TRAIN_SEQ = 4, 2048
SERVE_MAX_LEN, SERVE_PAGE = 1024, 16
SERVE_PROMPTS = (256, 512, 256, 512)
SERVE_NEW_TOKENS = 16
#: decode-vs-prefill bound, as a fraction of the largest reference logit.
#: Both paths run in bf16 (unit roundoff 2^-8 = 0.0039) but round at
#: different points: chunked prefill attention against single-token decode
#: attention, in each of 40 layers.  0.05 is about 13 roundoffs of the logit
#: scale -- wide enough for that drift, far below the O(1) relative error of
#: a wrong cache row, position or mask.
DECODE_TOL = 0.05


def check(cond, what):
    """A phase's pass condition; a failure ends the run non-zero."""
    if not cond:
        raise AssertionError(what)


def log(msg):
    print(msg, flush=True)


def device_report(want_count):
    """Name the device; refuse anything but TPU (no CPU fallback)."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"jax {jax.__version__} | platform {d.platform} | kind "
        f"{d.device_kind} | devices {len(devs)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {d.platform!r}); "
                 "this script never falls back to the CPU")
    if len(devs) < want_count:
        sys.exit(f"chip_smoke: needs {want_count} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def train_config():
    from repro.configs import get_config
    full = get_config("granite-3-2b")
    cfg = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    n = cfg.param_count()
    log(f"train model: granite-3-2b depth {full.n_layers} -> {cfg.n_layers} "
        f"(only cut); d_model {cfg.d_model}, heads {cfg.n_heads}q/"
        f"{cfg.n_kv_heads}kv x {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size} (padded {cfg.padded_vocab}), params "
        f"{cfg.param_dtype}, AdamW state {cfg.opt_state_dtype}; "
        f"{n / 1e6:.0f}M params")
    # 2 bytes of bf16 param + 2 x 4 bytes of f32 moments per parameter
    log(f"memory: state {10 * n / 1e9:.2f} GB + ~6.6 GB step temporaries "
        f"(compiled for v5e); 8 layers would need 14.1 GB, and a restore "
        f"must hold 2 x {10 * n / 1e9:.2f} GB of state, so 6 layers")
    return cfg


# ---------------------------------------------------------------------------
# shared checks
# ---------------------------------------------------------------------------

def step_loss(tr):
    """One training step; returns the loss as a 0-d float32 host array."""
    import numpy as np
    loss = np.asarray(tr.step_once()["loss"])
    check(np.isfinite(loss), f"step {tr.step}: loss {loss} is not finite")
    log(f"step {tr.step}: loss {float(loss)!r}")
    return loss


def state_leaves(tr):
    import jax
    return jax.tree.leaves({"params": tr.params, "opt": tr.opt_state})


def state_digests(tr):
    """Per-leaf host digests of params + optimizer state (the checkpoint
    format's own shard digest, over the gathered global array)."""
    import numpy as np

    from repro.core import ckpt_io
    return [ckpt_io.shard_digest(np.asarray(x)) for x in state_leaves(tr)]


def check_placed(tr, devices, what):
    got = {d for x in state_leaves(tr) for d in x.devices()}
    check(got <= set(devices),
          f"{what}: restored state on {sorted(map(str, got))}, expected "
          f"{sorted(map(str, devices))}")
    check(len({d.platform for d in got}) == 1,
          f"{what}: restored state spans platforms")


def close_trainer(tr):
    """Stop the trainer's threads and free its device state now: the
    trainer sits in reference cycles (its state providers close over it), so
    dropping the last name would leave gigabytes on the chip until the next
    cyclic collection -- and the next phase needs that memory."""
    tr.pipeline.stop()
    if tr.cluster.writer is not None:
        tr.cluster.writer.close()
    tr.params = tr.opt_state = None
    gc.collect()


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def train_phase(cfg, *, batch, seq):
    """Train 3 steps, checkpoint, step 4; kill a rank, restore the step-3
    checkpoint under another MPI family, step 4 again."""
    import jax
    import numpy as np

    from repro.configs import CkptIOConfig
    from repro.launch.train import Trainer
    ckpt_dir = Path(tempfile.mkdtemp(prefix="chip_smoke_ckpt_"))
    tr = None
    try:
        # codec "none": zlib over ~5.7 GB of state would dominate the run
        tr = Trainer(cfg, batch_size=batch, seq_len=seq, world_size=2,
                     backend="craympi", ckpt_dir=ckpt_dir,
                     ckpt_io=CkptIOConfig(codec="none"))
        t0 = time.perf_counter()
        tr.init_state()
        log(f"init: {time.perf_counter() - t0:.3f} s")
        for i in range(3):
            t0 = time.perf_counter()
            step_loss(tr)
            log(f"  wall {time.perf_counter() - t0:.3f} s"
                + (" (includes compile)" if i == 0 else ""))
        req = tr.checkpoint()
        want = state_digests(tr)
        stats = req.wait(timeout=1200)
        log(f"checkpoint step 3: timings {json.dumps(req.timings)}")
        log(f"checkpoint step 3: bytes written {stats['bytes_written']} of "
            f"{stats['bytes_total']}, write {stats['write_s']} s")
        loss4 = step_loss(tr)

        ck = tr.cluster.writer.latest()
        check(ck is not None and ck.name == "step_00000003",
              f"latest checkpoint is {ck}, expected step 3")
        victim = len(tr.cluster.ranks) - 1
        tr.cluster.kill_rank(victim)
        log(f"killed rank {victim} under {tr.cluster.backend_name}; "
            f"restoring {ck.name} under openmpi")
        tr.restore(ck, new_backend="openmpi")
        log(f"restart_timings {json.dumps(tr.restart_timings)}")
        check(tr.cluster.backend_name == "openmpi" and tr.step == 3,
              f"restored to {tr.cluster.backend_name} step {tr.step}")
        check_placed(tr, jax.devices(), "train restore")
        got = state_digests(tr)
        bad = sum(a != b for a, b in zip(got, want))
        check(len(got) == len(want) and bad == 0,
              f"{bad} of {len(want)} restored leaves differ from step 3")
        log(f"restored {len(got)} leaves: digests equal step 3's")
        loss4b = step_loss(tr)
        check(loss4b.tobytes() == loss4.tobytes(),
              f"step 4 after restore: loss {float(loss4b)!r} != "
              f"{float(loss4)!r}")
        log(f"step 4 loss bit-identical after craympi -> openmpi restore: "
            f"{float(loss4)!r} (0x{loss4.view(np.uint32).item():08x})")
    finally:
        if tr is not None:
            close_trainer(tr)
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def serve_phase(cfg, *, max_len, page_size, prompts, new_tokens, seed=0):
    """Continuous-batching fleet serving; checks every stream and one
    request's cached decode against a fresh prefill."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.engine import ServeEngine
    pages = sum(-(-(s + new_tokens) // page_size) for s in prompts)
    t0 = time.perf_counter()
    eng = ServeEngine(cfg, world_size=2, backend="mpich", max_len=max_len,
                      page_size=page_size, n_pages=pages, max_running=4,
                      seed=seed)
    log(f"serve model: {cfg.name} {cfg.n_layers} layers, "
        f"{cfg.param_count() / 1e9:.2f}B params ({cfg.param_dtype}); "
        f"init {time.perf_counter() - t0:.3f} s; pool {pages} pages of "
        f"{page_size}")
    rng = np.random.default_rng(seed)
    sids = [eng.submit(rng.integers(0, cfg.vocab_size, size=s),
                       max_new_tokens=new_tokens) for s in prompts]
    target = sids[0]
    nonfinite, last_decode = [], {}
    prefill, decode = eng.prefill_fn, eng.decode_fn

    def checked_prefill(params, batch):
        logits, caches = prefill(params, batch)
        if not bool(jnp.isfinite(logits).all()):
            nonfinite.append(("prefill", batch["tokens"].shape))
        return logits, caches

    def recorded_decode(params, tok, pos, caches):
        logits, new = decode(params, tok, pos, caches)
        if not bool(jnp.isfinite(logits).all()):
            nonfinite.append(("decode", int(pos)))
        if eng.sessions[target].dense is caches:
            last_decode["logits"], last_decode["pos"] = logits, int(pos)
        return logits, new

    eng.prefill_fn, eng.decode_fn = checked_prefill, recorded_decode
    t0 = time.perf_counter()
    ticks = eng.run_until_drained()
    log(f"served {len(sids)} requests (prompts {list(prompts)}) in {ticks} "
        f"ticks, {time.perf_counter() - t0:.3f} s (includes compiles)")
    check(not nonfinite, f"non-finite logits: {nonfinite}")
    for sid in sids:
        got = eng.stream(sid)
        check(len(got) == new_tokens,
              f"{sid}: {len(got)} tokens, expected {new_tokens}")
    s = eng.sessions[target]
    log(f"{target}: prompt {len(s.prompt)}, stream {s.generated}")
    check(last_decode.get("pos") == len(s.prompt) + new_tokens - 2,
          f"{target}: last decode at pos {last_decode.get('pos')}")
    toks = np.asarray(s.prompt + s.generated[:-1], np.int32)[None]
    ref, _ = prefill(eng.params, {"tokens": jnp.asarray(toks)})
    ref = np.asarray(ref, np.float32)
    dec = np.asarray(last_decode["logits"], np.float32)
    err = float(np.max(np.abs(ref - dec)) / (np.max(np.abs(ref)) + 1e-9))
    V = cfg.vocab_size
    agree = int(np.argmax(ref[0, :V])) == int(np.argmax(dec[0, :V]))
    log(f"{target}: decode vs fresh prefill of {toks.shape[1]} tokens: "
        f"rel err {err!r} (bound {DECODE_TOL}), argmax agrees {agree}")
    check(err < DECODE_TOL, f"decode/prefill rel err {err} >= {DECODE_TOL}")


def four_chip_phase(cfg, *, batch, seq):
    """Train on a mesh over four chips, restore under another flavor onto a
    mesh over two of them, compare the state byte for byte, step once."""
    import jax

    from repro.configs import CkptIOConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import Trainer
    devs = jax.devices()[:4]
    check(len(devs) == 4, f"four-chip phase found {len(devs)} devices")
    ckpt_dir = Path(tempfile.mkdtemp(prefix="chip_smoke_ckpt4_"))
    big = small = None
    io = CkptIOConfig(codec="none")
    try:
        big = Trainer(cfg, batch_size=batch, seq_len=seq, world_size=4,
                      backend="craympi", ckpt_dir=ckpt_dir, ckpt_io=io,
                      mesh=make_host_mesh(devices=devs))
        log(f"mesh A: {dict(big.mesh.shape)} over {len(devs)} chips, "
            f"device ids {[d.id for d in big.mesh.devices.flat]}")
        big.init_state()
        for _ in range(2):
            step_loss(big)
        req = big.checkpoint()
        stats = req.wait(timeout=1200)
        log(f"checkpoint step 2: timings {json.dumps(req.timings)}; bytes "
            f"written {stats['bytes_written']}")
        want = state_digests(big)
        ck = big.cluster.writer.latest()

        small = Trainer(cfg, batch_size=batch, seq_len=seq, world_size=2,
                        backend="openmpi", ckpt_dir=ckpt_dir, ckpt_io=io,
                        mesh=make_host_mesh(devices=devs[:2]))
        log(f"mesh B: {dict(small.mesh.shape)} over 2 chips, device ids "
            f"{[d.id for d in small.mesh.devices.flat]}; restoring "
            f"{ck.name} under openmpi, world 4 -> 2")
        small.restore(ck, new_backend="openmpi", new_world_size=2)
        log(f"restart_timings {json.dumps(small.restart_timings)}")
        check(small.cluster.backend_name == "openmpi"
              and len(small.cluster.ranks) == 2 and small.step == 2,
              "restored cluster has the wrong flavor, world or step")
        check_placed(small, devs[:2], "four-chip restore")
        got = state_digests(small)
        bad = sum(a != b for a, b in zip(got, want))
        check(len(got) == len(want) and bad == 0,
              f"{bad} of {len(want)} leaves differ after 4 -> 2 chip restore")
        log(f"restored {len(got)} leaves on 2 chips: byte-identical to the "
            "4-chip state")
        step_loss(small)
    finally:
        for tr in (big, small):
            if tr is not None:
                close_trainer(tr)
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip -> 2-chip elastic restore")
    args = ap.parse_args(argv)
    device = device_report(4 if args.four_chips else 1)

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    cfg = train_config()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ)
    else:
        train_phase(cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ)
        log(f"train phase: {time.perf_counter() - t0:.3f} s")
        t0 = time.perf_counter()
        serve_phase(get_config("granite-3-2b"), max_len=SERVE_MAX_LEN,
                    page_size=SERVE_PAGE, prompts=SERVE_PROMPTS,
                    new_tokens=SERVE_NEW_TOKENS)
    log(f"last phase: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
