"""Transparent checkpoint writer: per-rank images of the UPPER HALF only.

Image contents per rank (mirroring MANA's checkpoint image, but logical rather
than a raw memory dump — which is what buys topology-oblivious elastic
restart):
  * the rank's shards of every array leaf (params, optimizer state, caches),
  * the vid-table snapshot + record-replay log (from Mana.snapshot()),
  * drained in-flight messages,
  * data-iterator state, RNG key, step counter.

Writes are asynchronous and PIPELINED: the blocking window covers only the
batched device->host transfer (``ckpt_pipeline``: rank-aligned batches into a
double-buffered arena pair, each handed to the ``ckpt_io`` writer pool the
moment it lands), and the caller resumes as soon as the last batch is
enqueued.  Digesting, compression, file I/O, manifest assembly and the COMMIT
marker all happen behind the trainer's back; per-rank write durations are
recorded for straggler analysis.  The pre-pipeline path (snapshot everything,
then write) is kept behind ``pipeline=False`` for A/B measurement.

The data plane (chunked shard container, codecs, digests) lives in
``repro.core.ckpt_io``; the blocking-path plane (snapshot planning, batching,
arenas) in ``repro.core.ckpt_pipeline``; this module owns the control plane:
full-vs-delta policy, manifest assembly, atomic publish, and GC that never
deletes a step a live delta chain depends on (see docs/checkpoint_format.md)."""
from __future__ import annotations

import json
import shutil
import threading
from pathlib import Path

import jax
import numpy as np

from repro.core import ckpt_io, ckpt_pipeline
from repro.core.tracing import span


def snapshot_shards(tree, world_size, mesh):
    """Device->host snapshot, grouped by owning rank — the PR 1 blocking
    path, preserved VERBATIM as the measured before/after baseline (one
    blocking ``_to_np`` per shard, every copy done before the writer pool
    sees a byte).  The pipelined engine plans with
    ``ckpt_pipeline.plan_snapshot`` and transfers in batches instead.

    Returns (leaves_meta, {rank: {key: np.ndarray}}).
    Every addressable shard is copied host-side NOW; the caller may keep
    training while the writer thread persists the copies.  Shard entries
    carry (rank, key, index); the writer fills in (step, file) once it knows
    which step dir the bytes physically land in (delta checkpoints point
    clean shards at a PRIOR step's file)."""
    leaves, _ = jax.tree.flatten(tree)
    devices_flat = list(mesh.devices.flatten()) if mesh is not None else []
    per_rank: dict[int, dict[str, np.ndarray]] = {r: {}
                                                  for r in range(world_size)}
    leaves_meta = []
    for li, leaf in enumerate(leaves):
        meta = {"shape": list(leaf.shape),
                "dtype": ckpt_io.dtype_name(leaf.dtype),
                "shards": []}
        shards = getattr(leaf, "addressable_shards", None)
        if not shards:
            key = f"{li}.0"
            rank = 0
            per_rank[rank][key] = _to_np(leaf)
            meta["shards"].append({"rank": rank, "key": key,
                                   "index": [[0, s] for s in leaf.shape]})
        else:
            seen = set()
            for si, sh in enumerate(shards):
                idx = tuple(sh.index)
                norm = tuple((s.start or 0,
                              s.stop if s.stop is not None else dim)
                             for s, dim in zip(idx, leaf.shape))
                if norm in seen:      # replicated shard: store once
                    continue
                seen.add(norm)
                rank = ckpt_pipeline._rank_of_device(sh.device, devices_flat,
                                                     world_size)
                key = f"{li}.{si}"
                per_rank[rank][key] = _to_np(sh.data)
                meta["shards"].append({"rank": rank, "key": key,
                                       "index": [list(t) for t in norm]})
        leaves_meta.append(meta)
    return leaves_meta, per_rank


def _to_np(x):
    arr = np.asarray(x)
    if arr.dtype == jax.numpy.bfloat16:
        return arr  # np supports ml_dtypes bfloat16 via jax's numpy
    return arr


def runtime_leaf_indices(arrays) -> frozenset:
    """Flattened-leaf indices of the conventional top-level ``"runtime"``
    subtree (``repro.core.runtime_state``).  These leaves are bit-for-bit
    ordinary array entries — same delta digests, codecs, tier pushes — but
    the container index and manifest tag them ``kind="runtime"`` so tooling
    can tell live state from params."""
    if not isinstance(arrays, dict) or "runtime" not in arrays:
        return frozenset()
    flat, _ = jax.tree_util.tree_flatten_with_path(arrays)
    out = set()
    for li, (path, _leaf) in enumerate(flat):
        if path and getattr(path[0], "key", None) == "runtime":
            out.add(li)
    return frozenset(out)


class CheckpointRequest:
    """Async handle for an in-flight checkpoint (a REQUEST-kind object: the
    drain protocol completes it before the next snapshot).  ``timings``
    carries the stop-the-world breakdown in milliseconds — drain_ms /
    snapshot_ms / enqueue_ms / blocking_ms filled at call time, persist_ms
    once the background write commits."""

    def __init__(self, directory: Path):
        self.directory = directory
        self.done = threading.Event()
        self.error = None
        self.error_delivered = False  # wait() raised it to SOME caller
        self.write_stats: dict = {}
        self.timings: dict = {}
        self.release = lambda: None   # pipelined: opens the sink floodgates

    def wait(self, timeout=120.0):
        if not self.done.wait(timeout):
            raise TimeoutError(f"checkpoint {self.directory} did not complete")
        if self.error:
            self.error_delivered = True
            raise self.error
        return self.write_stats


class CheckpointWriter:
    """Pipelined async writer over the parallel/incremental/compressed
    ckpt_io engine.  At most one checkpoint is in flight; a new checkpoint()
    drains the previous one first.

    Args beyond the seed writer:
      codec             — "none" | "zlib" | "lz4" | "int8" (lossy, opt-in)
      incremental       — write only shards whose content digest changed,
                          with a full checkpoint every ``keep``-th
      io_workers        — writer pool size; 0 -> min(world_size, cpu)
      chunk_bytes       — raw bytes per streamed chunk
      pipeline          — pipelined snapshot (False -> snapshot-all-then-
                          write, the PR 1 path, kept for A/B)
      snapshot_batch_mb — raw MB per batched device_get group"""

    def __init__(self, base_dir, world_size: int, keep: int = 3, *,
                 codec: str = "none", incremental: bool = False,
                 io_workers: int = 0,
                 chunk_bytes: int = ckpt_io.DEFAULT_CHUNK_BYTES,
                 pipeline: bool = True,
                 snapshot_batch_mb: float = ckpt_pipeline.DEFAULT_BATCH_MB):
        self.base = Path(base_dir)
        self.base.mkdir(parents=True, exist_ok=True)
        self.world_size = world_size
        self.keep = keep
        self.codec_name = codec
        self.codec = ckpt_io.get_codec(codec)
        self.incremental = incremental
        self.chunk_bytes = chunk_bytes
        self.io_workers = io_workers or ckpt_io.default_workers(world_size)
        self.pipeline = pipeline
        self.snapshot_batch_bytes = int(snapshot_batch_mb * (1 << 20))
        # the double-buffered arena pair is shared across checkpoints so the
        # steady state never reallocates host memory
        self._arenas = (ckpt_pipeline.HostArena(), ckpt_pipeline.HostArena())
        self._pool: ckpt_io.IOPool | None = None
        self._inflight: CheckpointRequest | None = None
        # (rank:key) -> {"digest", "step", "file"}: where each shard's bytes
        # currently live on disk.  Only mutated after a successful COMMIT, so
        # a failed write can never poison delta decisions.
        self._digest_table: dict[str, dict] = {}
        self._since_full = 0
        #: optional hook ``cb(committed_step_dir)`` invoked right after an
        #: image commits (rename + GC done) — the RAM replica tier latches
        #: onto this to learn which dirs to push.  Runs on the finalize
        #: thread; exceptions are swallowed (tier bookkeeping must never
        #: fail a committed checkpoint).
        self.on_commit = None

    def _get_pool(self) -> ckpt_io.IOPool:
        if self._pool is None:
            self._pool = ckpt_io.IOPool(self.io_workers)
        return self._pool

    def checkpoint(self, step: int, arrays, mesh, rank_states: dict,
                   extra_meta: dict | None = None, *,
                   defer_release: bool = False) -> CheckpointRequest:
        """arrays: pytree of jax.Arrays; rank_states: {rank: json-able dict}
        (each rank's Mana.snapshot() + iterator/rng state).

        ``defer_release=True`` (pipelined mode) hands the sink floodgate to
        the caller as ``req.release`` so the last scrap of blocking-path
        bookkeeping above this layer can finish before background encode
        starts contending for the GIL; the caller MUST invoke it."""
        if self._inflight is not None:
            self._inflight.wait()
        tdir = self.base / f"step_{step:08d}.tmp"
        fdir = self.base / f"step_{step:08d}"
        if tdir.exists():
            shutil.rmtree(tdir)
        full = (not self.incremental or not self._digest_table
                or self._since_full >= self.keep)
        req = CheckpointRequest(fdir)
        rt_leaves = runtime_leaf_indices(arrays)
        if self.pipeline:
            self._checkpoint_pipelined(step, arrays, mesh, rank_states,
                                       extra_meta, tdir, fdir, full, req,
                                       rt_leaves)
            if not defer_release:
                req.release()
        else:
            self._checkpoint_buffered(step, arrays, mesh, rank_states,
                                      extra_meta, tdir, fdir, full, req,
                                      rt_leaves)
        self._inflight = req
        return req

    # -- pipelined path ------------------------------------------------------
    def _checkpoint_pipelined(self, step, arrays, mesh, rank_states,
                              extra_meta, tdir, fdir, full, req,
                              rt_leaves=frozenset()):
        """Blocking work = plan + batched D2H + enqueue.  Everything else —
        digest/delta decisions, compression, file writes, manifest, COMMIT —
        runs on the pool + a finalize thread while training continues."""
        leaves_meta, items = ckpt_pipeline.plan_snapshot(
            arrays, self.world_size, mesh)
        for li in rt_leaves:
            leaves_meta[li]["kind"] = "runtime"
        pool = self._get_pool()
        lossy = self.codec.lossy
        writers: dict[int, ckpt_io.RankShardWriter] = {}
        wlock = threading.Lock()
        per_rank = {r: {"keys": [], "digests": {}, "fresh": set(),
                        "raw_bytes": 0, "write_ms": 0.0,
                        "lock": threading.Lock()}
                    for r in range(self.world_size)}

        def _writer_for(rank):
            with wlock:
                w = writers.get(rank)
                if w is None:
                    w = writers[rank] = ckpt_io.RankShardWriter(
                        tdir / f"rank{rank:05d}", self.codec,
                        self.chunk_bytes)
                return w

        def sink(rank, its, views):
            """Consume one landed batch: per-shard delta decision + append
            into the rank's shard container.  Runs on pool threads."""
            pr = per_rank[rank]
            with span("ckpt.sink", into=pr, key="write_ms", add=True,
                      step=step, rank=rank,
                      bytes=sum(it.nbytes for it in its)):
                w = _writer_for(rank)
                out = []
                for it, view in zip(its, views):
                    digest, fresh = None, True
                    if self.incremental:
                        if lossy or not full:
                            digest = ckpt_io.shard_digest(view)
                        if not full:
                            prev = self._digest_table.get(
                                f"{rank}:{it.key}", {}).get("digest")
                            fresh = prev != digest
                    if fresh:
                        digest = w.add(
                            it.key, view, digest=digest,
                            compute_digest=self.incremental and not lossy,
                            kind="runtime"
                            if int(it.key.split(".", 1)[0]) in rt_leaves
                            else "array")
                    out.append((it, digest, fresh))
                with pr["lock"]:
                    for it, digest, fresh in out:
                        pr["keys"].append(it.key)
                        pr["raw_bytes"] += it.nbytes
                        if digest is not None:
                            pr["digests"][it.key] = digest
                        if fresh:
                            pr["fresh"].add(it.key)

        pipe = ckpt_pipeline.SnapshotPipeline(
            pool, batch_bytes=self.snapshot_batch_bytes, arenas=self._arenas)
        try:
            res = pipe.run(items, sink, step=step)
        except BaseException as e:       # noqa: BLE001 — incl. injected faults
            # a fault mid-snapshot (e.g. the ckpt.snapshot_batch failpoint)
            # must not leave the writer wedged: run() has already drained the
            # sinks it submitted, so the container handles can be released
            # and the request marked failed before the error propagates to
            # the supervisor
            for w in writers.values():
                w.abort()
            req.error = e
            req.done.set()
            raise
        req.timings["snapshot_ms"] = res["snapshot_ms"]
        req.timings["enqueue_ms"] = res["enqueue_ms"]
        req.write_stats["snapshot_batches"] = res["batches"]

        def _finalize():
            try:
                with span("ckpt.persist", into=req.timings, key="persist_ms",
                          step=step):
                    first_err = None
                    for f in res["futures"]:
                        try:
                            f.result()
                        except BaseException as e:  # noqa: BLE001
                            if first_err is None:
                                first_err = e
                    if first_err is not None:
                        raise first_err
                    # stable once every sink future has resolved
                    req.write_stats["arena_spills"] = res["counters"]["spills"]
                    results = []
                    for r in range(self.world_size):
                        st = _writer_for(r).finish()  # ranks w/o shards: empty
                        ckpt_io.atomic_write_text(
                            tdir / f"rank{r:05d}" / "state.json",
                            json.dumps(rank_states.get(r, {})))
                        pr = per_rank[r]
                        results.append({"rank": r, "keys": pr["keys"],
                                        "digests": pr["digests"],
                                        "fresh": pr["fresh"],
                                        "enc_bytes": st["enc_bytes"],
                                        "fresh_raw_bytes": st["raw_bytes"],
                                        "raw_bytes": pr["raw_bytes"],
                                        "seconds": round(
                                            pr["write_ms"] / 1e3, 4)})
                    self._publish(step, mesh, leaves_meta, results, full,
                                  extra_meta, tdir, fdir, req)
                self._after_commit(req, fdir)
            except Exception as e:  # noqa: BLE001
                req.error = e
                for w in writers.values():
                    w.abort()
            finally:
                req.done.set()

        # finalize rides the pool rather than a fresh thread (spawn is
        # blocking-window cost): sinks were submitted first, so FIFO order
        # guarantees they schedule before the finalize task that awaits them
        pool.submit(_finalize)
        req.release = res["release"]

    # -- buffered (PR 1) path ------------------------------------------------
    def _checkpoint_buffered(self, step, arrays, mesh, rank_states,
                             extra_meta, tdir, fdir, full, req,
                             rt_leaves=frozenset()):
        with span("ckpt.snapshot", into=req.timings, key="snapshot_ms",
                  step=step):
            leaves_meta, per_rank = snapshot_shards(arrays, self.world_size,
                                                    mesh)
        for li in rt_leaves:
            leaves_meta[li]["kind"] = "runtime"
        req.timings["enqueue_ms"] = 0.0

        def _write_rank(rank: int):
            rdir = tdir / f"rank{rank:05d}"
            arrays_r = per_rank.get(rank, {})
            raw_all = sum(a.nbytes for a in arrays_r.values())
            with span("ckpt.sink", step=step, rank=rank, bytes=raw_all) as sp:
                # digests exist to detect clean shards; a non-incremental
                # writer rewrites everything anyway, so skip hashing
                # entirely.  On a full lossless checkpoint the hash is FUSED
                # into the write stream (one memory pass); only delta
                # decisions and lossy codecs need a separate pre-pass.
                lossy = self.codec.lossy
                if self.incremental and (lossy or not full):
                    digests = {k: ckpt_io.shard_digest(a)
                               for k, a in arrays_r.items()}
                else:
                    digests = {}
                if full:
                    fresh_keys = set(arrays_r)
                else:
                    fresh_keys = {
                        k for k in arrays_r
                        if self._digest_table.get(f"{rank}:{k}", {}).get(
                            "digest") != digests[k]}
                st = ckpt_io.write_rank_shards(
                    rdir, {k: arrays_r[k] for k in arrays_r
                           if k in fresh_keys},
                    self.codec, self.chunk_bytes,
                    digests={k: digests[k]
                             for k in fresh_keys & digests.keys()},
                    compute_digests=self.incremental and not lossy,
                    kinds={k: "runtime" for k in fresh_keys
                           if int(k.split(".", 1)[0]) in rt_leaves})
                ckpt_io.atomic_write_text(
                    rdir / "state.json", json.dumps(rank_states.get(rank, {})))
            return {"rank": rank, "keys": list(arrays_r),
                    "digests": {**digests, **st["digests"]},
                    "fresh": fresh_keys,
                    "enc_bytes": st["enc_bytes"],
                    "fresh_raw_bytes": st["raw_bytes"],
                    "raw_bytes": raw_all,
                    "seconds": round(sp.ms / 1e3, 4)}

        def _write():
            try:
                with span("ckpt.persist", into=req.timings, key="persist_ms",
                          step=step):
                    results = self._get_pool().map(_write_rank,
                                                   range(self.world_size))
                    self._publish(step, mesh, leaves_meta, results, full,
                                  extra_meta, tdir, fdir, req)
                self._after_commit(req, fdir)
            except Exception as e:  # noqa: BLE001
                req.error = e
            finally:
                req.done.set()

        threading.Thread(target=_write, daemon=True).start()

    # -- shared publish tail -------------------------------------------------
    def _publish(self, step, mesh, leaves_meta, results, full, extra_meta,
                 tdir, fdir, req):
        """Resolve shard locations, assemble the manifest, COMMIT, atomically
        publish, roll the digest table forward.  Runs on the background
        writer/finalize thread for both snapshot paths, inside the
        ``ckpt.persist`` span; :meth:`_after_commit` follows it."""
        new_table: dict[str, dict] = {}
        src: dict[tuple, dict] = {}
        for r in results:
            rank = r["rank"]
            rfile = f"rank{rank:05d}/{ckpt_io.BIN_NAME}"
            for k in r["keys"]:
                tk = f"{rank}:{k}"
                if k in r["fresh"]:
                    ent = {"digest": r["digests"].get(k),
                           "step": step, "file": rfile}
                else:
                    ent = dict(self._digest_table[tk])
                new_table[tk] = ent
                src[(rank, k)] = ent
        for meta in leaves_meta:
            for sh in meta["shards"]:
                ent = src[(sh["rank"], sh["key"])]
                sh["step"] = ent["step"]
                sh["file"] = ent["file"]
        base_steps = sorted({sh["step"] for meta in leaves_meta
                             for sh in meta["shards"]} - {step})
        total = sum(r["raw_bytes"] for r in results)
        written = sum(r["enc_bytes"] for r in results)
        fresh_shards = sum(len(r["fresh"]) for r in results)
        total_shards = sum(len(r["digests"]) for r in results)
        per_rank_s = {r["rank"]: r["seconds"] for r in results}
        manifest = {
            "format": ckpt_io.FORMAT_VERSION,
            "step": step,
            "world_size": self.world_size,
            "mesh": {"shape": list(mesh.devices.shape),
                     "axes": list(mesh.axis_names)} if mesh is not None else None,
            "leaves": leaves_meta,
            "codec": self.codec_name,
            "incremental": self.incremental,
            "full": full,
            "base_steps": base_steps,
            "bytes_total": total,
            "bytes_written": written,
            "delta": {"fresh_shards": fresh_shards,
                      "total_shards": total_shards},
            "per_rank_write_s": per_rank_s,
            "straggler_rank": max(per_rank_s, key=per_rank_s.get)
            if per_rank_s else 0,
            **(extra_meta or {}),
        }
        ckpt_io.atomic_write_text(tdir / "manifest.json",
                                  json.dumps(manifest))
        ckpt_io.atomic_write_text(tdir / "COMMIT", "ok")
        if fdir.exists():
            shutil.rmtree(fdir)
        tdir.rename(fdir)       # atomic publish
        self._digest_table = new_table
        self._since_full = 1 if full else self._since_full + 1
        req.write_stats.update(
            bytes_total=total, bytes_written=written, full=full,
            fresh_shards=fresh_shards, total_shards=total_shards,
            per_rank_write_s=per_rank_s)

    def _after_commit(self, req, fdir):
        """After the ``ckpt.persist`` span: GC, then the commit hook."""
        req.write_stats["write_s"] = round(req.timings["persist_ms"] / 1e3, 4)
        self._gc()
        cb = self.on_commit
        if cb is not None:
            try:
                cb(fdir)
            except Exception:  # noqa: BLE001
                pass

    # -- directory scanning / GC -------------------------------------------
    def _completed_steps(self) -> list[Path]:
        """Sorted committed step dirs (``.tmp`` and uncommitted dirs are
        invisible: half-written checkpoints can never be restored from).
        Shared with the restore side (``restore.completed_steps``) so writer
        and reader can never disagree on what counts as committed."""
        from repro.core.restore import completed_steps
        return completed_steps(self.base)

    def _gc(self):
        """Delete all but the newest ``keep`` completed checkpoints — except
        any older step that a kept manifest's delta chain still references
        (``base_steps``); deleting those would orphan clean shards."""
        if self.keep <= 0:          # retain everything (seed semantics)
            return
        done = self._completed_steps()
        kept = done[-self.keep:]
        deps: set[int] = set()
        for d in kept:
            try:
                man = json.loads((d / "manifest.json").read_text())
            except (OSError, ValueError):
                continue
            deps.update(man.get("base_steps", []))
        protect = {d.name for d in kept} | {f"step_{s:08d}" for s in deps}
        for d in done[: -self.keep]:
            if d.name not in protect:
                shutil.rmtree(d)

    def latest(self):
        done = self._completed_steps()
        return done[-1] if done else None

    def resumable(self):
        """Newest committed checkpoint whose delta chain fully resolves
        (``restore.find_resumable``) — what resume-from-latest should load.
        Differs from ``latest()`` only when an operator has orphaned a delta
        chain (e.g. hand-deleted a base step)."""
        from repro.core.restore import find_resumable
        return find_resumable(self.base)

    def force_full_next(self):
        """Make the next checkpoint a full one (operators: guaranteed
        self-contained snapshot before migrations; benchmarks: repeatable
        full-write measurements)."""
        self._digest_table = {}
        self._since_full = 0

    def wait_idle(self):
        req = self._inflight
        if req is None:
            return
        # a failure is delivered EXACTLY once: if some caller already saw it
        # via req.wait(), draining here (close(), Cluster.restart, the next
        # checkpoint) must not re-raise it — a supervisor recovering FROM
        # that failure would count the echo as a second incident
        already = req.error_delivered
        try:
            req.wait()
        except BaseException:
            if not already:
                raise
        finally:
            # the request IS finished (possibly failed): clearing it even
            # on error keeps later wait_idle/close calls from re-raising
            # the same failure forever
            self._inflight = None

    def close(self):
        try:
            self.wait_idle()
        finally:
            # the pool must die even if the last checkpoint failed
            if self._pool is not None:
                self._pool.close()
                self._pool = None
