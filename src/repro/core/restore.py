"""Restore subsystem: rebuild the lower half under ANY backend flavor and
re-bind every virtual id (paper §4.2, §9) — fast.

This is the restart half of the checkpoint/restart pair (`ckpt.py` +
`ckpt_io` own the write path).  Three planes:

**Capability translation (the backend-pair restart matrix).**  A checkpoint
taken under flavor S must restart under every flavor D.  For each ordered
pair the :class:`PairPlan` resolves, per descriptor kind, how the object is
rebuilt:

  RECORD_REPLAY — replay the logged creation call against the new backend;
  SERIALIZE     — rebuild from the decoded description in the descriptor
                  (works across families: it is pure upper-half state);
  HYBRID        — replay when S and D share an implementation FAMILY
                  (Cray MPI is MPICH-derived) and D natively supports the
                  original call; otherwise deserialize.

Constants (COMM_WORLD, predefined datatypes/ops) always re-bind LAZILY on
first use (§4.3 — ExaMPI's addresses are not even known at startup), and
datatype envelopes are RE-ENCODED through the destination's aliasing
discipline (``Backend.alias_dtype``) so e.g. an MPI_INT8_T checkpointed
under MPICH lands on ExaMPI's shared INT8/CHAR pointer.

**Parallel streaming rebind.**  Descriptor re-binding overlaps `ckpt_io`'s
leaf restore: shard reads (I/O + GIL-releasing decompress) are submitted
to the I/O pool first, then every rank's rebind DAG runs on dedicated
workers — dependency-ordered (a replayed ``comm_split`` needs its parent's
physical handle first), ready-queue scheduled, backend calls serialized
per rank by a lock since lower halves are not thread-safe.  This replaces
the seed's single sorted loop; restart wall time approaches
max(slowest rank DAG, array I/O) instead of their sum.

**Elastic reshape.**  Array state is topology-oblivious: leaves are
reassembled from the per-rank shard entries recorded by the write-side
planner (``ckpt_pipeline.plan_snapshot``) and re-placed onto the NEW mesh
by running that plan in reverse — ``jax.make_array_from_callback`` pulls,
per target device, exactly the slice the new sharding assigns it, so the
device count, mesh shape, and world size may all differ from checkpoint
time.  Rank images wrap around (new rank r restores image r mod old_world).
"""
from __future__ import annotations

import json
import math
import threading
from dataclasses import dataclass, field
from pathlib import Path

import jax
import numpy as np

from repro.core import ckpt_io
from repro.core.backends import BACKENDS, backend_family
from repro.core.faults import failpoint
from repro.core.descriptors import Kind, Strategy
from repro.core.tracing import span
from repro.core.vid import VidTable

_REBIND_TIMEOUT = 60.0


# ---------------------------------------------------------------------------
# capability translation: the backend-pair restart matrix
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PairPlan:
    """Resolved translation rules for one ordered (checkpoint, restart)
    backend pair."""
    src: str
    dst: str
    src_family: str
    dst_family: str
    same_family: bool            # HYBRID resolves to replay iff True
    native_split: bool           # dst implements comm_split natively
    dtype_aliases: dict          # dst aliasing table over predefined names
    reencode_envelopes: bool     # any alias differs -> envelopes re-encoded
    #: canonical-dtype re-encode rules for runtime-state leaves
    #: (``repro.core.runtime_state``): StateLeaf transport dtypes pass
    #: through the same aliasing table as datatype envelopes.
    runtime: dict = field(default_factory=dict)

    @property
    def replay_comm_split(self) -> bool:
        """Split replays only when HYBRID resolves to replay AND the
        destination has the native call; otherwise comm_create serializes."""
        return self.same_family and self.native_split


def translation_plan(src: str, dst: str, dst_backend=None) -> PairPlan:
    """Build the capability-translation plan for restarting a checkpoint
    taken under ``src`` on a lower half of flavor ``dst``.  ``dst_backend``
    (a live instance) supplies capabilities/aliasing; without one a
    throwaway probe instance is constructed."""
    if dst_backend is None:
        from repro.core.backends.fabric import Fabric
        dst_backend = BACKENDS[dst](Fabric(1), 0, 1)
    from repro.core.backends.base import PREDEFINED_DTYPES
    aliases = {nm: dst_backend.alias_dtype(nm)
               for nm, _, _ in PREDEFINED_DTYPES}
    return PairPlan(
        src=src, dst=dst,
        src_family=backend_family(src),
        dst_family=dst_backend.family,
        same_family=backend_family(src) == dst_backend.family,
        native_split="comm_split" in dst_backend.capabilities(),
        dtype_aliases=aliases,
        reencode_envelopes=any(k != v for k, v in aliases.items()),
        runtime={"dtype_aliases": dict(aliases),
                 "reencode": any(k != v for k, v in aliases.items())},
    )


def restart_matrix() -> dict:
    """Every ordered (checkpoint_backend, restart_backend) pair with its
    resolved translation plan — the support matrix documented in
    docs/restart_matrix.md and exercised exhaustively by
    tests/test_restore_matrix.py."""
    return {(s, d): translation_plan(s, d)
            for s in BACKENDS for d in BACKENDS}


def reencode_envelope(env: dict, plan: PairPlan) -> dict:
    """Re-encode a datatype envelope through the destination's aliasing
    discipline: named leaves are mapped via ``alias_dtype`` (recursing into
    derived-type ``base`` envelopes) so the rebuilt handle always lands on
    the destination's canonical constant."""
    if not plan.reencode_envelopes:
        return env
    out = dict(env)
    if out.get("combiner") == "named":
        out["name"] = plan.dtype_aliases.get(out["name"], out["name"])
    base = out.get("base")
    if isinstance(base, dict):
        out["base"] = reencode_envelope(base, plan)
    return out


def resolve_strategy(d, plan: PairPlan) -> str:
    """Per-descriptor reconstruction mode under a pair plan:
    ``lazy`` (constants, §4.3) | ``replay`` | ``serialize``."""
    if d.kind == Kind.COMM and d.meta.get("axis_name") == "world":
        return "lazy"
    if d.kind == Kind.DATATYPE and d.meta.get("envelope", {}).get(
            "combiner") == "named":
        return "lazy"
    if d.kind == Kind.OP and d.meta.get("predefined"):
        return "lazy"
    if d.kind == Kind.COMM:
        use_replay = (d.strategy == Strategy.RECORD_REPLAY or
                      (d.strategy == Strategy.HYBRID and plan.same_family))
        if use_replay and d.meta.get("color") is not None \
                and plan.native_split:
            return "replay"
        return "serialize"
    if d.kind == Kind.OP:
        return "replay"
    if d.kind == Kind.REQUEST:
        return "request"
    return "serialize"          # GROUP, derived DATATYPE


# ---------------------------------------------------------------------------
# rebind engine: dependency-ordered, parallel across and within ranks
# ---------------------------------------------------------------------------

@dataclass
class _RebindPlan:
    """One rank's classified rebind work: descriptor jobs keyed by vid,
    replay dependencies (parent comm before child split), and the
    per-rank lock that serializes lower-half creation calls."""
    mana: object
    plan: PairPlan
    by_vid: dict
    modes: dict                  # vid -> lazy|replay|serialize|request
    deps: dict = field(default_factory=dict)   # vid -> parent vid
    stats: dict = field(default_factory=dict)
    lock: threading.Lock = field(default_factory=threading.Lock)


def _plan_rebind(mana, snap: dict) -> _RebindPlan:
    """Swap the snapshot's vid table into ``mana`` and classify every
    unbound descriptor under the pair plan.  No lower-half calls yet."""
    plan = translation_plan(snap["backend_name"], mana.backend_name,
                            mana.backend)
    table = VidTable.restore(snap["vids"])
    mana.vids = table
    mana.log = list(snap["log"])
    mana.pending_messages = [tuple(p) for p in snap["pending"]]
    _repoint_constants(mana, table)
    # rebuild the legacy shadow tables when running in slow-translation mode
    if mana.legacy is not None:
        from repro.core.legacy_vid import LegacyVidTables
        mana.legacy = LegacyVidTables()
        mana._legacy_of = {}
    from repro.core.callspec import COLL_TAG_MIN
    by_vid = {d.vid: d for d in table.all_descriptors()}
    rp = _RebindPlan(mana=mana, plan=plan, by_vid=by_vid, modes={},
                     stats={"replayed": 0, "serialized": 0, "lazy": 0,
                            "reencoded_envelopes": 0,
                            # drained traffic re-delivered via the buffered
                            # receive once the peers' calls resume —
                            # collective payloads replay like p2p
                            "pending_redelivery": len(mana.pending_messages),
                            "pending_collective": sum(
                                1 for _, t, _ in mana.pending_messages
                                if t >= COLL_TAG_MIN)})
    # two passes: classify EVERYTHING first, then register dependencies.
    # by_vid iterates in vid order, which for comms is ggid (hash) order —
    # a child split can hash below its parent, so a single fused pass would
    # silently drop the parent->child edge and let the parallel engine
    # replay the split against world_comm instead of its parent.
    for d in by_vid.values():
        if d.phys is not None:
            continue
        mode = resolve_strategy(d, plan)
        rp.modes[d.vid] = mode
        if mode == "lazy":
            rp.stats["lazy"] += 1
    for vid, mode in rp.modes.items():
        if mode != "replay":
            continue
        d = by_vid[vid]
        if d.kind != Kind.COMM:
            continue
        parent = d.meta.get("parent")
        # order only matters when the parent itself is being replayed/
        # serialized in this pass (constants bind lazily on first use)
        if parent in rp.modes and rp.modes[parent] in ("replay",
                                                       "serialize"):
            rp.deps[vid] = parent
    return rp


def _repoint_constants(mana, table: VidTable) -> None:
    """Re-aim the upper-half constant accessors (``comm_world()``,
    ``dtype_handles``, ``op_handles``) at the RESTORED table's descriptors.

    ``Mana.__init__`` registered fresh constants before the snapshot's
    table was swapped in; for datatypes/ops the per-kind counters make the
    vids coincide, but COMM vids are ggid hashes of the MEMBER RANKS — an
    elastic restart onto a different world size leaves ``world_handle``
    pointing at a vid the restored table never contained.  A post-recovery
    collective over ``comm_world()`` (the training step's allreduce hot
    path) would then die on a dangling vid."""
    from repro.core.callspec import make_handle
    for d in table.all_descriptors():
        if d.kind == Kind.COMM and d.meta.get("axis_name") == "world":
            mana.world_handle = make_handle(d.vid)
        elif d.kind == Kind.DATATYPE:
            env = d.meta.get("envelope", {})
            if env.get("combiner") == "named":
                mana.dtype_handles[env["name"]] = make_handle(d.vid)
        elif d.kind == Kind.OP and d.meta.get("predefined"):
            mana.op_handles[d.meta["name"]] = make_handle(d.vid)


def repoint_world(mana, members) -> dict:
    """LIVE membership change (no restart): re-aim one rank's COMM_WORLD at
    ``members`` — a possibly-sparse, ordered rank-id list (survivors keep
    their ids; the world is a membership list, not a dense range).

    Three moves, all upper-half except the middle one:

      1. free the old world COMM descriptor (its ggid hashes the OLD member
         list, so it can never be confused with the new one);
      2. rebuild the lower half's world communicator over ``members``
         (``Backend.resize_world`` — works for every flavor);
      3. register a fresh world-axis COMM descriptor bound to the new
         physical handle and re-aim the constant accessors through the
         existing :func:`_repoint_constants`.

    Because the new vid is a ggid of the identical member list, every
    member computes the SAME world vid without coordination — the property
    live collectives rely on.  Buffered internal messages whose tag embeds
    the old world vid are purged (their collective round died with the old
    membership); buffered USER p2p traffic is untouched — redelivery of a
    departed rank's user traffic is the elastic layer's job, not this one's.
    """
    from repro.core.callspec import COLL_TAG_MIN, handle_vid
    from repro.core.descriptors import comm_desc
    members = list(members)
    old_vid = handle_vid(mana.world_handle)
    mana.vids.free(old_vid)
    # vid coherence across DIFFERENT insert histories: a joiner's init
    # world may already be this exact member tuple (bumping its probe
    # counter), so reset the counter and let slot-occupancy probing alone
    # pick the seq — a pure function of live table content, which is
    # symmetric across ranks under MPI's collective-creation discipline
    mana.vids._ggid_seq.pop((Kind.COMM, tuple(sorted(members))), None)
    phys = mana.backend.resize_world(members)
    mana.world_size = len(members)
    d = comm_desc(members, axis_name="world", strategy=Strategy.SERIALIZE)
    new_vid = mana._register(d, phys)
    _repoint_constants(mana, mana.vids)
    kept, purged = [], 0
    for s, t, payload in mana.pending_messages:
        if t >= COLL_TAG_MIN and (t & 0xFFFFFFFF) == old_vid:
            purged += 1
            continue
        kept.append((s, t, payload))
    mana.pending_messages = kept
    return {"old_vid": old_vid, "new_vid": new_vid,
            "members": members, "purged_internal": purged}


def _bind_one(rp: _RebindPlan, vid: int) -> None:
    """Bind one descriptor's physical handle.  Creation calls serialize on
    the rank's lock — lower halves are not thread-safe — but run
    concurrently ACROSS ranks and with leaf-restore I/O."""
    d = rp.by_vid[vid]
    mode = rp.modes[vid]
    backend = rp.mana.backend
    plan = rp.plan
    with rp.lock:
        if mode == "replay" and d.kind == Kind.COMM:
            parent = rp.by_vid.get(d.meta.get("parent"))
            pphys = parent.phys if parent and parent.phys is not None \
                else backend.world_comm()
            d.phys = backend.comm_split(
                pphys, d.meta["color"], d.meta["key"], d.meta["ranks"])
            rp.stats["replayed"] += 1
        elif d.kind == Kind.COMM:
            d.phys = backend.comm_create(d.meta["ranks"])
            rp.stats["serialized"] += 1
        elif d.kind == Kind.GROUP:
            d.phys = backend.comm_group(
                backend.comm_create(d.meta["ranks"]))
            rp.stats["serialized"] += 1
        elif d.kind == Kind.DATATYPE:
            env = reencode_envelope(d.meta["envelope"], plan)
            if env != d.meta["envelope"]:
                d.meta["envelope"] = env
                rp.stats["reencoded_envelopes"] += 1
            d.phys = backend.type_create(env)
            rp.stats["serialized"] += 1
        elif d.kind == Kind.OP:
            d.phys = backend.op_create(d.meta["name"],
                                       d.meta.get("commutative", True))
            rp.stats["replayed"] += 1
        elif d.kind == Kind.REQUEST:
            # completed during drain; re-materialize as a done request
            d.phys = backend.request_create(dict(d.meta))
            d.state["done"] = True


def _finalize_rebind(rp: _RebindPlan) -> None:
    """Post-bind bookkeeping that needs every handle in place (legacy
    shadow tables mirror physical handles)."""
    mana = rp.mana
    if mana.legacy is not None:
        from repro.core.interpose import _KIND_NAME
        for d in mana.vids.all_descriptors():
            lvid = mana.legacy.insert(_KIND_NAME[d.kind], d.phys)
            mana._legacy_of[d.vid] = lvid


def _execute_rebind(plans: list, pool=None) -> None:
    """Run every rank's rebind DAG.  With a pool: one combined ready-queue —
    a job is submitted the moment its parent resolves, so independent
    descriptors of ALL ranks interleave with whatever else (leaf reads) the
    pool is chewing on.  Without: the seed-equivalent sequential walk in
    creation order (kept as the measured baseline and zero-thread path)."""
    if pool is None:
        for rp in plans:
            order = sorted((vid for vid, m in rp.modes.items() if m != "lazy"),
                           key=lambda v: rp.by_vid[v].meta.get("order", 0))
            for vid in order:
                _bind_one(rp, vid)
            _finalize_rebind(rp)
        return

    lock = threading.Lock()
    done = threading.Event()
    errors: list[BaseException] = []
    waiting: dict[tuple, list] = {}      # (plan_i, parent) -> [(plan_i, vid)]
    ready: list[tuple] = []
    pending = 0
    completed = 0
    for i, rp in enumerate(plans):
        for vid, mode in rp.modes.items():
            if mode == "lazy":
                continue
            pending += 1
            parent = rp.deps.get(vid)
            if parent is None:
                ready.append((i, vid))
            else:
                waiting.setdefault((i, parent), []).append((i, vid))
    if pending == 0:
        for rp in plans:
            _finalize_rebind(rp)
        return

    def run(node):
        nonlocal pending, completed
        i, vid = node
        try:
            _bind_one(plans[i], vid)
        except BaseException as e:  # noqa: BLE001
            with lock:
                errors.append(e)
        with lock:
            for child in waiting.pop((i, vid), ()):
                pool.submit(run, child)
            pending -= 1
            completed += 1
            if pending == 0:
                done.set()

    for node in ready:
        pool.submit(run, node)
    # progress-aware wait: raise only when a whole timeout slice passes
    # with ZERO descriptors resolved — a genuine wedge — rather than
    # capping total rebind time (a big world legitimately takes a while)
    last = 0
    while not done.wait(_REBIND_TIMEOUT):
        with lock:
            now, left = completed, pending
        if now == last:
            raise TimeoutError(f"rebind stalled: {left} descriptor(s) "
                               f"unresolved with no progress for "
                               f"{_REBIND_TIMEOUT}s")
        last = now
    if errors:
        raise errors[0]
    for rp in plans:
        _finalize_rebind(rp)


def rebind_objects(mana, snap: dict, *, pool=None) -> dict:
    """Replace ``mana``'s fresh vid table with the snapshot's and bind
    physical handles for every descriptor under the pair plan (checkpoint
    flavor -> ``mana``'s flavor).  ``pool`` (a ``ckpt_io.IOPool``) enables
    the dependency-ordered parallel engine; ``None`` is the sequential
    baseline.  Returns the rebind stats, including the resolved pair."""
    rp = _plan_rebind(mana, snap)
    _execute_rebind([rp], pool)
    rp.stats["pair"] = f"{rp.plan.src}->{rp.plan.dst}"
    return rp.stats


def rebind_world(pairs, *, pool=None) -> list:
    """Rebind MANY ranks' snapshots concurrently over one pool (the restart
    path: every rank's DAG plus the leaf-restore reads share the workers).
    ``pairs`` is [(mana, snap), ...]; returns per-rank stats in order."""
    failpoint("restore.rebind_world", ranks=len(pairs))
    plans = [_plan_rebind(m, s) for m, s in pairs]
    _execute_rebind(plans, pool)
    for rp in plans:
        rp.stats["pair"] = f"{rp.plan.src}->{rp.plan.dst}"
    return [rp.stats for rp in plans]


# ---------------------------------------------------------------------------
# array state: topology-oblivious load + elastic reshape
# ---------------------------------------------------------------------------

class _NpzCache:
    """Bounded LRU of open ``np.load`` handles (legacy v1 images).  The seed
    loader kept every handle open forever; this evicts + closes past ``cap``
    and closes everything on exit."""

    def __init__(self, cap: int = 8):
        from collections import OrderedDict
        self.cap = cap
        self._od = OrderedDict()

    def get(self, path):
        if path in self._od:
            self._od.move_to_end(path)
            return self._od[path]
        npz = np.load(path)
        self._od[path] = npz
        while len(self._od) > self.cap:
            _, old = self._od.popitem(last=False)
            old.close()
        return npz

    def close(self):
        for npz in self._od.values():
            npz.close()
        self._od.clear()


def _load_leaves_v1(ckpt_dir: Path, leaves_meta: list) -> list:
    """Legacy (format 1) loader: monolithic per-rank ``arrays.npz`` files."""
    cache = _NpzCache()
    leaves = []
    try:
        for meta in leaves_meta:
            arr = np.zeros(meta["shape"],
                           dtype=ckpt_io.resolve_dtype(meta["dtype"]))
            for sh in meta["shards"]:
                data = cache.get(ckpt_dir / sh["file"])[sh["key"]]
                idx = tuple(slice(a, b) for a, b in sh["index"])
                arr[idx] = data
            leaves.append(arr)
    finally:
        cache.close()
    return leaves


def plan_leaf_reads(manifest: dict) -> dict:
    """Group every shard entry by the (step, rank) container that physically
    holds its bytes — delta checkpoints point clean shards at a prior step —
    so each read task opens exactly one shard file.  The write-side planner
    (``ckpt_pipeline.plan_snapshot``) decided these locations; this is that
    plan read back in reverse."""
    groups: dict[tuple, list] = {}
    for li, meta in enumerate(manifest["leaves"]):
        for sh in meta["shards"]:
            step = sh.get("step", manifest["step"])
            groups.setdefault((step, sh["rank"]), []).append((li, sh))
    return groups


def _full_cover(sh: dict, shape: list) -> bool:
    """True when one shard entry spans the entire leaf — the common case
    (replicated or unsharded leaves), where the decoded bytes can BE the
    leaf instead of being copied into a preallocated buffer."""
    return sh["index"] == [[0, s] for s in shape]


class ArrayRestoreJob:
    """Leaf restore in flight on a pool of reader threads.

    Constructing the job opens each container's reader, preallocates the
    leaves and submits the read tasks at once.  A task covers one chunk
    range of one shard entry: an entry larger than
    ``ckpt_io.READ_SPAN_BYTES`` splits into ranges of whole chunks, so one
    large leaf's bytes are read by several workers, and entries of one file
    are read concurrently over a shared positioned-read descriptor.  Where
    an entry's bytes need no array untransform or dtype conversion and its
    slice of the leaf is one C-contiguous byte range (a full-cover shard, a
    shard split along the leading axis), each task decodes its chunks
    into the leaf (``read_into``: a stored-raw chunk is one positioned read
    through the thread's reused staging buffer, then one copy into place).  Other entries are decoded whole and copied into the
    leaf's slice, or become the leaf when they cover it.  The reads overlap
    descriptor rebinding scheduled on another pool; ``result()`` waits for
    them and performs the elastic reshape placement.

    ``pool`` is shared and left open; without one the job makes and closes
    its own, of ``workers`` threads (0: ``ckpt_io.read_workers``, sized by
    the host's CPUs and the task count).  Each task's read is a
    ``restore.read`` span (``restore``, ``leaf``, ``part``, ``bytes``) and
    the placement a ``restore.place`` span, tagged with ``restore_id``.
    ``timings`` holds ``read_workers`` (the pool's size) and, after
    ``result()``, ``read_ms`` (first read's start to last read's end),
    ``read_direct_share`` (bytes of stored-raw chunks read into their place
    in the leaves, over all bytes read) and ``place_ms``: the dispatch of every leaf's placement, whose
    host-to-device copies land after it, behind later host work."""

    def __init__(self, source, manifest: dict, shardings, pool=None, *,
                 workers: int = 0, restore_id: str | None = None):
        self.source = as_source(source)
        self.restore_id = restore_id
        self._read_extent = [float("inf"), float("-inf")]
        self._direct = self._total = 0
        self.manifest = manifest
        self._meta = manifest["leaves"]
        flat_sh, self._treedef = jax.tree.flatten(
            shardings, is_leaf=lambda x: x is None)
        if len(flat_sh) != len(self._meta):
            raise ValueError(f"checkpoint has {len(self._meta)} leaves, "
                             f"target tree has {len(flat_sh)}")
        self._flat_sh = flat_sh
        # a full-cover entry that must be decoded whole BECOMES its leaf;
        # every other leaf is preallocated here as the reads' destination
        self._leaves: list = [None] * len(self._meta)
        self._readers: dict[tuple, object] = {}
        self._lock = threading.Lock()
        self._pool = None
        try:
            tasks = [task for (step, rank), shards
                     in plan_leaf_reads(manifest).items()
                     for li, sh in shards
                     for task in self._plan(self._reader(step, rank), li, sh)]
        except BaseException:
            self.close()
            raise
        if pool is None:
            pool = self._pool = ckpt_io.IOPool(
                workers or ckpt_io.read_workers(len(tasks)))
        self.timings: dict = {"read_workers": pool.workers}
        self._futures = [pool.submit(*task) for task in tasks]

    def _reader(self, step, rank):
        key = (step, rank)
        r = self._readers.get(key)
        if r is None:
            r = self._readers[key] = self.source.reader(step, rank)
        return r

    def _plan(self, r, li: int, sh: dict) -> list:
        """The read tasks, ``(fn, *args)``, of one shard entry."""
        meta = self._meta[li]
        entry = r.entry(sh["key"])
        idx = tuple(slice(a, b) for a, b in sh["index"])
        nbytes = ckpt_io.resolve_dtype(meta["dtype"]).itemsize * math.prod(
            b - a for a, b in sh["index"])
        same_bytes = (entry.get("qmeta") is None
                      and entry["enc_dtype"] == entry["dtype"]
                      == meta["dtype"])
        if not same_bytes and _full_cover(sh, meta["shape"]):
            # a full-cover shard is by construction the leaf's ONLY shard
            return [(self._read_copy, r, sh["key"], li, None, nbytes)]
        if self._leaves[li] is None:
            self._leaves[li] = np.empty(
                meta["shape"], dtype=ckpt_io.resolve_dtype(meta["dtype"]))
        view = self._leaves[li][idx + (...,)]
        if not (same_bytes and view.flags.c_contiguous
                and nbytes == entry["nbytes"]):
            return [(self._read_copy, r, sh["key"], li, idx, nbytes)]
        out = view.reshape(-1).view(np.uint8)
        return [(self._read_into, r, sh["key"], li, part, out[lo:hi],
                 first, last)
                for part, (first, last, lo, hi) in enumerate(
                    ckpt_io.chunk_spans(entry, ckpt_io.READ_SPAN_BYTES))]

    def _read_into(self, r, key, li, part, out, first, last) -> None:
        # disjoint byte ranges of the leaf: concurrent writers never overlap
        with span("restore.read", restore=self.restore_id, leaf=li,
                  part=part, bytes=out.nbytes) as sp:
            direct = r.read_into(key, out, first, last)
        self._done(sp, direct, out.nbytes)

    def _read_copy(self, r, key, li, idx, nbytes) -> None:
        with span("restore.read", restore=self.restore_id, leaf=li,
                  part=0, bytes=nbytes) as sp:
            if idx is None:
                self._leaves[li] = r.read(key)
            else:
                self._leaves[li][idx] = r.read(key)
        self._done(sp, 0, nbytes)

    def _done(self, sp, direct: int, nbytes: int) -> None:
        with self._lock:
            ext = self._read_extent
            ext[0], ext[1] = min(ext[0], sp.t0), max(ext[1], sp.t1)
            self._direct += direct
            self._total += nbytes

    def result(self, timeout: float = 300.0):
        first_err = None
        for f in self._futures:
            try:
                f.result(timeout=timeout)
            except BaseException as e:  # noqa: BLE001
                if first_err is None:
                    first_err = e
        self.close()
        if first_err is not None:
            raise first_err
        if self._futures:
            lo, hi = self._read_extent
            self.timings["read_ms"] = round((hi - lo) * 1e3, 3)
            self.timings["read_direct_share"] = round(
                self._direct / max(self._total, 1), 4)
        with span("restore.place", into=self.timings, key="place_ms",
                  restore=self.restore_id,
                  bytes=sum(a.nbytes for a in self._leaves)):
            out = [place_leaf(arr, sh)
                   for arr, sh in zip(self._leaves, self._flat_sh)]
        return jax.tree.unflatten(self._treedef, out)

    def close(self) -> None:
        """Release the readers and the job's own pool (idempotent;
        ``result()`` calls it).  Callers that abandon the job after a
        failure elsewhere in the restart MUST close it, or the pread fds
        leak."""
        with self._lock:
            for r in self._readers.values():
                r.close()
        if self._pool is not None:
            self._pool.close()


def place_leaf(arr: np.ndarray, sharding):
    """Put one reassembled host leaf onto devices under the NEW sharding —
    the write-side shard planner run in reverse: each target device pulls
    exactly the slice the new layout assigns it (``devices_indices_map``
    via ``make_array_from_callback``), however the leaf was sharded at
    checkpoint time.  ``None`` sharding (single-device run) is a plain
    host->device transfer."""
    if sharding is None:
        return jax.numpy.asarray(arr)
    try:
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])
    except (TypeError, ValueError):
        # exotic shardings (e.g. bare SingleDeviceSharding wrappers that
        # reject the callback protocol): whole-leaf put, XLA reshards
        return jax.device_put(arr, sharding)


def _load_leaves_v2_seq(source, manifest: dict) -> list:
    """Sequential v2 loader: same format, same group plan, same zero-copy
    full-cover path, ZERO threads — the measured baseline for the
    parallel-restore gate in benchmarks/bench_restart.py (and the fallback
    when a caller cannot afford a pool)."""
    leaves_meta = manifest["leaves"]
    leaves: list = [None] * len(leaves_meta)
    for (step, rank), shards in plan_leaf_reads(manifest).items():
        with source.reader(step, rank) as r:
            for li, sh in shards:
                meta = leaves_meta[li]
                if _full_cover(sh, meta["shape"]):
                    leaves[li] = r.read(sh["key"])
                    continue
                if leaves[li] is None:
                    leaves[li] = np.empty(
                        meta["shape"],
                        dtype=ckpt_io.resolve_dtype(meta["dtype"]))
                idx = tuple(slice(a, b) for a, b in sh["index"])
                leaves[li][idx] = r.read(sh["key"])
    return leaves


def load_arrays(ckpt, shardings, *, io_workers=None, parallel=True,
                pool=None):
    """Reassemble every leaf from per-rank shard containers and place it
    with the NEW shardings (tree matching the manifest leaf order) — the new
    mesh / device count may differ from checkpoint time (elastic reshape).

    ``ckpt`` is a committed step directory OR any checkpoint source (see
    :func:`as_source` — e.g. a RAM-tier ``TierImage``).  ``parallel=True``
    fans chunk-range reads out over ``pool`` (or a transient pool of
    ``io_workers``, by default sized by the host: ``ckpt_io.read_workers``);
    ``parallel=False`` is the sequential baseline.  Handles
    both the v2 chunked/compressed/incremental format and legacy v1 npz
    images (v1 requires a directory source)."""
    src = as_source(ckpt)
    manifest = src.manifest()
    if manifest.get("format", 1) >= 2:
        if parallel:
            return ArrayRestoreJob(src, manifest, shardings, pool,
                                   workers=io_workers or 0).result()
        leaves = _load_leaves_v2_seq(src, manifest)
    else:
        step_dir = getattr(src, "path", None)
        if step_dir is None:
            raise ValueError("legacy format-1 images need a directory "
                             "checkpoint source")
        leaves = _load_leaves_v1(Path(step_dir), manifest["leaves"])
    flat_sh, treedef = jax.tree.flatten(shardings, is_leaf=lambda x: x is None)
    if len(flat_sh) != len(leaves):
        raise ValueError(f"checkpoint has {len(leaves)} leaves, "
                         f"target tree has {len(flat_sh)}")
    out = [place_leaf(arr, sh) for arr, sh in zip(leaves, flat_sh)]
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# checkpoint directory scanning: manifests, rank images, resume chains
# ---------------------------------------------------------------------------

def load_manifest(ckpt_dir) -> dict:
    return json.loads((Path(ckpt_dir) / "manifest.json").read_text())


def load_rank_state(ckpt_dir, rank: int) -> dict:
    p = Path(ckpt_dir) / f"rank{rank:05d}" / "state.json"
    return json.loads(p.read_text())


# ---------------------------------------------------------------------------
# checkpoint sources: where an image's bytes live (disk dir, RAM tier, ...)
# ---------------------------------------------------------------------------

class DirCheckpointSource:
    """The canonical checkpoint source: one committed ``step_XXXXXXXX``
    directory on disk.

    A checkpoint *source* is the restore engine's storage abstraction —
    anything exposing ``name`` / ``manifest()`` / ``rank_state(rank)`` /
    ``reader(step, rank)`` can serve a restore: this class for the disk
    tier, ``ckpt_tiers.TierImage`` for the peer-replicated RAM tier.
    ``reader`` takes an explicit step because delta manifests point clean
    shards at PRIOR steps' containers (``plan_leaf_reads``), which for a
    directory source live under sibling step dirs of the same base."""

    def __init__(self, step_dir):
        self.path = Path(step_dir)
        self._root = self.path.parent
        self._state_texts: dict[int, str] = {}

    @property
    def name(self) -> str:
        return self.path.name

    def manifest(self) -> dict:
        return load_manifest(self.path)

    def rank_state(self, rank: int) -> dict:
        # cache the TEXT, parse per call: rebinding mutates descriptor meta
        # in place, so parsed state must never be shared between ranks
        text = self._state_texts.get(rank)
        if text is None:
            p = self.path / f"rank{rank:05d}" / "state.json"
            text = self._state_texts[rank] = p.read_text()
        return json.loads(text)

    def reader(self, step: int, rank: int) -> ckpt_io.RankShardReader:
        return ckpt_io.RankShardReader(
            self._root / f"step_{step:08d}" / f"rank{rank:05d}")


def as_source(ckpt):
    """Coerce ``ckpt`` (a step-dir path, or any object already satisfying
    the checkpoint-source protocol) into a source."""
    if callable(getattr(ckpt, "reader", None)) \
            and callable(getattr(ckpt, "manifest", None)):
        return ckpt
    return DirCheckpointSource(ckpt)


def completed_steps(base_dir) -> list:
    """Sorted committed step dirs under a checkpoint base dir (``.tmp`` and
    uncommitted dirs are invisible: half-written checkpoints can never be
    restored from)."""
    base = Path(base_dir)
    if not base.is_dir():
        return []
    return sorted(d for d in base.iterdir()
                  if d.name.startswith("step_")
                  and not d.name.endswith(".tmp")
                  and (d / "COMMIT").exists())


def verify_checkpoint(step_dir, *, deep: bool = True) -> list:
    """Integrity-check one committed checkpoint dir.  Returns a list of
    problems (empty = the checkpoint verifies):

      * manifest / per-rank ``index.json`` / ``state.json`` must parse;
      * every entry's chunk extents must fit inside ``shards.bin`` (catches
        truncation — a torn write at power loss);
      * with ``deep=True`` every entry is decoded (corrupt compressed
        streams fail here) and, where the index records a content digest
        and the codec is lossless, re-hashed against it (catches silent
        bit-flips in raw chunks).

    Raw (``none``-codec) entries written without digests are structurally
    checked only — write with ``incremental=True`` or a compressed codec
    when corruption detection matters (the chaos harness does)."""
    step_dir = Path(step_dir)
    problems: list[str] = []
    try:
        manifest = load_manifest(step_dir)
    except (OSError, ValueError) as e:
        return [f"manifest unreadable: {e}"]
    # every rank the manifest promises must have its container: restart
    # reads rank{r}/state.json for r in range(world_size), so a lost rank
    # dir (partial copy, operator rm) makes the image unrestorable even
    # though everything still present verifies
    for r in range(manifest.get("world_size", 0)):
        if not (step_dir / f"rank{r:05d}").is_dir():
            problems.append(f"rank{r:05d}: container missing")
    for rdir in sorted(step_dir.iterdir()):
        if not rdir.is_dir() or not rdir.name.startswith("rank"):
            continue
        try:
            json.loads((rdir / "state.json").read_text())
        except (OSError, ValueError) as e:
            problems.append(f"{rdir.name}/state.json unreadable: {e}")
        try:
            index = ckpt_io.read_rank_index(rdir)
        except (OSError, ValueError) as e:
            problems.append(f"{rdir.name}/index.json unreadable: {e}")
            continue
        try:
            bin_size = (rdir / ckpt_io.BIN_NAME).stat().st_size
        except OSError as e:
            problems.append(f"{rdir.name}/{ckpt_io.BIN_NAME} missing: {e}")
            continue
        entries = index.get("entries", {})
        torn = False
        for key, ent in entries.items():
            end = ent["offset"] + sum(c[0] for c in ent["chunks"])
            if end > bin_size:
                problems.append(
                    f"{rdir.name}/{key}: entry extends to byte {end} but "
                    f"{ckpt_io.BIN_NAME} holds {bin_size} (truncated)")
                torn = True
        if torn or not deep or not entries:
            continue
        try:
            codec = ckpt_io.get_codec(index["codec"])
        except KeyError as e:
            problems.append(f"{rdir.name}: unknown codec: {e}")
            continue
        with ckpt_io.RankShardReader(rdir, codec) as r:
            for key, ent in entries.items():
                try:
                    arr = r.read(key)
                except Exception as e:  # noqa: BLE001 — any decode failure
                    problems.append(f"{rdir.name}/{key}: undecodable: {e}")
                    continue
                # lossy codecs round-trip to different bytes by design, so
                # their recorded (pre-quantization) digests cannot re-verify
                if ent.get("digest") and not codec.lossy:
                    if ckpt_io.shard_digest(arr) != ent["digest"]:
                        problems.append(
                            f"{rdir.name}/{key}: content digest mismatch")
    return problems


def find_resumable(base_dir, *, verify: bool = True, deep: bool = True):
    """Newest committed checkpoint that is actually RESTORABLE:

      * its delta chain fully resolves — every ``base_steps`` entry a delta
        manifest references must itself still be a committed step dir (GC
        protects live chains, but an operator rm / a partial copy can
        orphan one);
      * with ``verify=True`` (default) the checkpoint AND every base step
        its clean shards point at pass :func:`verify_checkpoint` — a torn
        or corrupted image that still carries its COMMIT marker is skipped,
        so recovery lands on the previous good checkpoint instead of
        failing mid-restore.

    Walks newest-to-oldest and returns the first intact checkpoint, or
    ``None`` — resume-from-latest must never pick an image whose shards
    have no (valid) backing bytes."""
    steps = completed_steps(base_dir)
    have: dict[int, Path] = {}
    for d in steps:
        try:
            have[int(d.name[len("step_"):])] = d
        except ValueError:
            continue
    verified: dict[str, bool] = {}

    def _ok(d: Path) -> bool:
        if d.name not in verified:
            verified[d.name] = not verify_checkpoint(d, deep=deep)
        return verified[d.name]

    for d in reversed(steps):
        try:
            man = load_manifest(d)
        except (OSError, ValueError):
            continue
        bases = man.get("base_steps", [])
        if not all(b in have for b in bases):
            continue
        if verify and not all(_ok(x) for x in [d] + [have[b] for b in bases]):
            continue
        return d
    return None
