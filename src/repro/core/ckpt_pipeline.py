"""Pipelined, double-buffered device->host snapshot engine.

The paper's users feel the BLOCKING window of a checkpoint — the time ranks
are quiesced and images captured — not the background write (MANA, arXiv
1904.12595; NERSC follow-up, arXiv 2103.08546).  PR 1 made persistence
parallel/incremental/compressed but still copied every shard host-side with
one blocking transfer per shard before the writer pool saw a byte.  This
module owns the blocking half and shrinks it:

  * ``plan_snapshot`` enumerates every owned shard in ONE pass over the
    pytree (replicated leaves dedup'd to a single copy) as lightweight
    work items — no host copies yet;
  * items are grouped into RANK-ALIGNED batches of ``batch_bytes`` raw
    bytes (``snapshot_batch_mb`` knob), and D2H is kicked off EARLY for all
    of them (``copy_to_host_async`` where the runtime exposes it);
  * each batch is completed with one ``jax.device_get`` for the whole
    group — batched transfer, not one dispatch per shard — and handed
    STRAIGHT to the ckpt_io writer pool;
  * the pool task lands the batch in one of a pair of reusable host arenas
    (double buffering: batch N digests/compresses/writes while batch N+1 is
    still transferring) and only then encodes it, so the caller never waits
    for digesting, compression, or file I/O;
  * the caller resumes as soon as the LAST batch is enqueued.

Arena semantics: the pair bounds steady-state memory, not worst-case
latency — if both arenas are busy (writer slower than the device) a batch
spills to a transient buffer instead of stalling the trainer; spills are
counted in the run stats.  Arenas grow to the high-water batch size once
and are then reused across checkpoints.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable

import jax
import numpy as np

from repro.core import ckpt_io
from repro.core.faults import failpoint
from repro.core.tracing import span

DEFAULT_BATCH_MB = 8.0
_MIN_BATCH_BYTES = 64 << 10


@dataclass
class ShardItem:
    """One owned shard: where it belongs in the checkpoint + the (still
    device-resident) array that backs it."""
    rank: int
    key: str                     # "<leaf_idx>.<shard_idx>"
    index: list                  # [[start, stop], ...] into the global leaf
    data: Any                    # device array (leaf or shard.data)
    nbytes: int
    leaf: int


def _rank_of_device(dev, devices_flat, world_size):
    per = max(1, len(devices_flat) // world_size)
    return min(dev.id // per, world_size - 1) if hasattr(dev, "id") else 0


def _nbytes(arr) -> int:
    nb = getattr(arr, "nbytes", None)
    if nb is not None:
        return int(nb)
    return int(arr.size) * np.dtype(arr.dtype).itemsize


def plan_snapshot(tree, world_size, mesh):
    """Single planning pass over the pytree.

    Returns ``(leaves_meta, items)``: the manifest leaf descriptions (shard
    entries carry (rank, key, index); the writer fills in (step, file) once
    it knows where the bytes land) and the flat work-item list.  A fully
    replicated leaf yields exactly ONE item — every replica normalizes to
    the same index, so later copies are dropped."""
    leaves, _ = jax.tree.flatten(tree)
    devices_flat = list(mesh.devices.flatten()) if mesh is not None else []
    leaves_meta: list[dict] = []
    items: list[ShardItem] = []
    dtype_name = ckpt_io.dtype_name       # hot loop: skip attribute lookups
    for li, leaf in enumerate(leaves):
        meta = {"shape": list(leaf.shape),
                "dtype": dtype_name(leaf.dtype),
                "shards": []}
        # meshless runs are single-device: every leaf is one rank-0 shard,
        # and materializing .addressable_shards per leaf (a Shard object +
        # index computation each) would be pure blocking-window overhead
        shards = getattr(leaf, "addressable_shards", None) \
            if devices_flat else None
        if not shards:
            key = f"{li}.0"
            index = [[0, s] for s in leaf.shape]
            meta["shards"].append({"rank": 0, "key": key, "index": index})
            items.append(ShardItem(0, key, index, leaf, _nbytes(leaf), li))
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
                rank = _rank_of_device(sh.device, devices_flat, world_size)
                key = f"{li}.{si}"
                index = [list(t) for t in norm]
                meta["shards"].append({"rank": rank, "key": key,
                                       "index": index})
                items.append(ShardItem(rank, key, index, sh.data,
                                       _nbytes(sh.data), li))
        leaves_meta.append(meta)
    return leaves_meta, items


def batch_plan(items, batch_bytes: int):
    """Group work items into rank-aligned batches of ~``batch_bytes`` raw
    bytes.  Rank alignment lets each batch stream into exactly one rank's
    shard container; a single oversized shard still forms its own batch."""
    batch_bytes = max(int(batch_bytes), _MIN_BATCH_BYTES)
    by_rank: dict[int, list] = {}
    for it in items:
        by_rank.setdefault(it.rank, []).append(it)
    batches: list[tuple[int, list]] = []
    for rank, its in by_rank.items():
        cur, size = [], 0
        for it in its:
            cur.append(it)
            size += it.nbytes
            if size >= batch_bytes:
                batches.append((rank, cur))
                cur, size = [], 0
        if cur:
            batches.append((rank, cur))
    return batches


class HostArena:
    """One reusable host-memory landing zone (half of a double-buffered
    pair).  ``place`` carves dtype-shaped views out of a single backing
    buffer and memcpys the batch in — the bytes are then owned by the
    checkpoint outright.  The buffer grows to the high-water batch size
    and is reused forever.  Acquisition is lock-based: encode tasks on
    multiple pool threads race for the pair, so try_acquire must be
    atomic, not a check-then-clear."""

    def __init__(self):
        self._buf = np.empty(0, np.uint8)
        self._lock = threading.Lock()

    def try_acquire(self) -> bool:
        return self._lock.acquire(blocking=False)

    def place(self, hosts: list) -> list:
        total = sum(h.nbytes for h in hosts)
        if self._buf.nbytes < total:
            self._buf = np.empty(total, np.uint8)
        views, off = [], 0
        for h in hosts:
            # NB: ascontiguousarray promotes 0-d to 1-d — reshape to the
            # ORIGINAL shape, or scalar leaves change identity on disk
            c = np.ascontiguousarray(h)
            v = self._buf[off:off + c.nbytes]
            v[:] = c.view(np.uint8).reshape(-1)
            views.append(v.view(c.dtype).reshape(np.shape(h)))
            off += c.nbytes
        return views

    def release(self):
        self._lock.release()


def _spill(hosts: list) -> list:
    """Fallback landing zone when both arenas are busy: transient copies so
    the producer never stalls behind the writer."""
    return [np.array(h, copy=True) for h in hosts]


class SnapshotPipeline:
    """Drives one pipelined snapshot over a writer pool.

    ``run(items, sink, step=...)`` feeds rank-aligned batches through D2H
    into arena (or spill) buffers and submits ``sink(rank, batch_items,
    host_views)`` to the pool for each batch; it returns as soon as the
    last batch is enqueued, with the futures plus a timing/stat breakdown
    (``snapshot_ms`` / ``enqueue_ms``: the summed ``ckpt.d2h`` /
    ``ckpt.enqueue`` spans of the batches, tagged with ``step``) and a
    ``release`` callable the caller MUST invoke once its blocking window
    closes (sinks hold until then; a 60 s backstop prevents a forgotten
    release from wedging the pool).  The sink is called on pool threads —
    it must be thread-safe across ranks."""

    def __init__(self, pool: ckpt_io.IOPool, *,
                 batch_bytes: int = int(DEFAULT_BATCH_MB * (1 << 20)),
                 arenas: tuple | None = None):
        self.pool = pool
        self.batch_bytes = batch_bytes
        self.arenas = arenas if arenas is not None else (HostArena(),
                                                         HostArena())

    def run(self, items, sink: Callable, *, step: int | None = None) -> dict:
        batches = batch_plan(items, self.batch_bytes)
        # kick off D2H for EVERY batch up front: on accelerators the copies
        # overlap each other and run while earlier batches are being
        # completed.  On the CPU backend host "transfer" is aliasing, so
        # the enqueue loop would be pure blocking-window overhead — skip it.
        if jax.default_backend() != "cpu":
            for _, its in batches:
                for it in its:
                    start = getattr(it.data, "copy_to_host_async", None)
                    if start is not None:
                        try:
                            start()
                        except Exception:  # noqa: BLE001 — optional
                            pass
        # sinks hold until the caller releases them: encode/digest/IO in a
        # GIL world would otherwise steal cycles from the still-open
        # blocking window, which is the one cost this engine exists to
        # minimize.  Enqueued-but-held batches begin the instant the
        # window closes, overlapping training rather than the snapshot.
        # Holding the raw device_get views that long is safe: on the CPU
        # backend the views carry PjRt external references, so a later
        # donation of the source buffer is refused (copied) rather than
        # aliased; on accelerators device_get is a real host copy.
        window_closed = threading.Event()
        counters = {"spills": 0}
        clock = threading.Lock()

        def _acquire_arena(timeout: float = 30.0):
            """First free arena of the pair (encode tasks race for them
            once the window closes — that is what makes the pair CYCLE:
            batch 3 lands the moment batch 1 finishes encoding)."""
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                for cand in self.arenas:
                    if cand.try_acquire():
                        return cand
                time.sleep(0.001)
            return None

        futures = []
        timings = {"snapshot_ms": 0.0, "enqueue_ms": 0.0}
        try:
            for bi, (rank, its) in enumerate(batches):
                # chaos-harness injection site: a raise here fails the
                # checkpoint INSIDE its blocking window, mid-batch
                failpoint("ckpt.snapshot_batch", rank=rank, batch=bi)
                with span("ckpt.d2h", into=timings, key="snapshot_ms",
                          add=True, step=step, batch=bi, rank=rank,
                          bytes=sum(it.nbytes for it in its)):
                    hosts = jax.device_get([it.data for it in its])

                def task(rank=rank, its=its, hosts=hosts):
                    window_closed.wait(timeout=60.0)
                    arena = _acquire_arena()
                    try:
                        if arena is None:    # starved 30 s: degrade, don't die
                            with clock:
                                counters["spills"] += 1
                            views = _spill(hosts)
                        else:
                            views = arena.place(hosts)
                        sink(rank, its, views)
                    finally:
                        if arena is not None:
                            arena.release()

                with span("ckpt.enqueue", into=timings, key="enqueue_ms",
                          add=True, step=step, batch=bi):
                    futures.append(self.pool.submit(task))
        except BaseException:
            # fail CLEAN: open the floodgates so already-enqueued sinks don't
            # camp on the 60 s backstop, and drain them so the caller can
            # abort its writers without racing in-flight appends.  The
            # per-future bound must exceed the 30 s arena-starvation window,
            # or a task still waiting in _acquire_arena outlives the drain
            # and appends into a writer the caller already aborted.
            window_closed.set()
            for f in futures:
                try:
                    f.result(timeout=35.0)
                except BaseException:  # noqa: BLE001 — best-effort drain
                    pass
            raise
        return {"futures": futures,
                "release": window_closed.set,
                "batches": len(batches),
                "counters": counters, **timings}
