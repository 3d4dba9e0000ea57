"""Replay shard server: one process (or thread) owning a host-memory ring
— the reference's sharded-replay role (ExperienceSender -> ShardedReplay,
SURVEY.md §2.1) rebuilt on the experience wire.

The ring is a NumPy mirror of ``replay/base.py``'s semantics: vectorized
cursor-wraparound insert (FIFO evict), uniform sampling via the SAME
``jax.random.randint`` draw the in-process ``UniformReplay`` makes (the
shard reconstructs the caller's key from its raw key data), and
prioritized sampling mirroring ``replay/prioritized.py``'s
cumsum+searchsorted form in float32. Uniform sampling is therefore
BIT-EQUAL to the in-process replay for the same insert stream and keys
(tested); prioritized sampling matches within a documented float32
reduction-order tolerance. Sampling-near-the-data is the scaling move
once actor traffic outgrows one box (arXiv:2110.13506) — the learner
ships ~40-byte sample requests and receives batches, never the ring.

Consistency: sample requests carry a *watermark* (rows the requester
knows were routed here). The shard defers a sample until its ingestion
count reaches the watermark — in-order ingestion per sender plus
watermark deferral makes strict-mode training records deterministic —
bounded by ``watermark_timeout_s`` so a dead sender (or a respawned,
empty shard) degrades to sampling what exists instead of deadlocking the
learner.

Faults (chaos harness, utils/faults.py): ``experience.shard`` fires once
per loop pass (``kill_shard`` raises FaultInjected — the plane supervisor
must respawn; ``delay`` sleeps); ``experience.sample`` fires per served
sample (``delay_sample``). A SIGKILLed shard leaks nothing: slab cleanup
is CLIENT-owned (see ``wire.create_slab``), and the respawned shard binds
the same address so senders/samplers re-negotiate in place.
"""

from __future__ import annotations

import time
from typing import Any, Mapping

import numpy as np

from surreal_tpu.experience import wire
from surreal_tpu.utils import faults

_JAX_FLAGS: dict = {"force_cpu": False, "threefry_partitionable": None,
                    "applied": False}


def _jax():
    """Import jax lazily with the shard's platform pinned. A shard server
    spawned on a TPU host must NOT grab the chip — it is a host-memory
    service; ``force_cpu`` pins the platform before the first backend
    touch. ``threefry_partitionable`` is forwarded from the trainer so
    both processes draw identical random streams."""
    import jax

    if not _JAX_FLAGS["applied"]:
        if _JAX_FLAGS["force_cpu"]:
            jax.config.update("jax_platforms", "cpu")
        if _JAX_FLAGS["threefry_partitionable"] is not None:
            jax.config.update(
                "jax_threefry_partitionable",
                bool(_JAX_FLAGS["threefry_partitionable"]),
            )
        _JAX_FLAGS["applied"] = True
    return jax


def keys_from_bytes(buf: bytes, nkeys: int):
    """Reconstruct a [nkeys] typed jax PRNG key array from concatenated
    raw key data (the sampler ships ``jax.random.key_data(key)`` bytes
    per key)."""
    jax = _jax()
    data = np.frombuffer(buf, np.uint32).reshape(nkeys, -1)
    return jax.random.wrap_key_data(jax.numpy.asarray(data))


class HostRing:
    """NumPy mirror of ``replay/base.py``'s ring: same cursor/size
    bookkeeping, same wraparound scatter, same uniform index draw."""

    def __init__(self, spec: wire.PlaneSpec, capacity: int):
        self.spec = spec
        self.capacity = int(capacity)
        self.storage = {
            name: np.zeros((self.capacity, *shape), dtype)
            for name, shape, dtype in spec.fields
        }
        self.cursor = 0
        self.size = 0

    def insert_positions(self, n: int) -> np.ndarray:
        return (self.cursor + np.arange(n, dtype=np.int64)) % self.capacity

    def insert(self, rows: Mapping[str, np.ndarray], n: int) -> np.ndarray:
        idx = self.insert_positions(n)
        for name, _, dtype in self.spec.fields:
            # assignment casts to the storage dtype, matching
            # ring_insert's ``new.astype(buf.dtype)``
            self.storage[name][idx] = rows[name][:n]
        self.cursor = int((self.cursor + n) % self.capacity)
        self.size = int(min(self.size + n, self.capacity))
        return idx

    def gather(self, idx: np.ndarray) -> dict[str, np.ndarray]:
        return {name: buf[idx] for name, buf in self.storage.items()}

    def sample_many(self, keys, bs: int, beta: float | None = None):
        """Uniform with replacement, ALL key sets drawn in one vmapped
        ``jax.random.randint`` — PR 4's ``sample_many`` discipline, whose
        record-equivalence contract (set k bit-equal to a sequential
        ``sample(keys[k])``, itself bit-equal to the in-process
        ``UniformReplay.sample``) is what makes the remote plane's
        uniform batches exactly reproduce the in-process replay's."""
        jax = _jax()
        idx = np.asarray(
            jax.vmap(
                lambda k: jax.random.randint(k, (bs,), 0, max(self.size, 1))
            )(keys),
            np.int64,
        )
        return [(idx[u], self.gather(idx[u]), None)
                for u in range(idx.shape[0])]

    def gauges(self) -> dict:
        return {
            "size": self.size,
            "fill": self.size / self.capacity,
            "capacity": self.capacity,
        }


class HostPrioritized(HostRing):
    """Prioritized twin on the host (Schaul et al. 2016, no sum-tree): the
    same stratified proportional draw as the device's
    (replay/prioritized.py), but float32 throughout and by a flat
    cumsum+searchsorted, where the device goes in two levels (block sums,
    then one block) and carries the block level in double-float. The two
    add the mass up in different orders and to different precision, so the
    cdfs differ by ulps and cross-implementation equivalence is
    *convergence within tolerance*, not bit-equality
    (tests/test_experience.py documents the budget)."""

    def __init__(self, spec, capacity, alpha=0.6, beta0=0.4, eps=1e-6):
        super().__init__(spec, capacity)
        self.alpha = np.float32(alpha)
        self.beta0 = float(beta0)
        self.eps = np.float32(eps)
        self.priorities = np.zeros(self.capacity, np.float32)
        self.max_priority = np.float32(1.0)

    def insert(self, rows, n):
        idx = super().insert(rows, n)
        self.priorities[idx] = self.max_priority
        return idx

    def sample_many(self, keys, bs: int, beta: float | None = None):
        """Stratified prioritized draws for every key against the SAME
        priority state (exactly what the remote contract already implies:
        an iteration's priority refresh lands as one batched frame AFTER
        its learns) — the stratifying uniforms come from one vmapped
        draw, the cdf math is a flat float32 numpy cumsum (the device's
        draw is the two-level one; see the class docstring)."""
        jax = _jax()
        beta = np.float32(self.beta0 if beta is None else beta)
        p = self.priorities ** self.alpha
        total = p.sum(dtype=np.float32)
        cdf = np.cumsum(p, dtype=np.float32)
        uniforms = np.asarray(
            jax.vmap(lambda k: jax.random.uniform(k, (bs,)))(keys),
            np.float32,
        )
        out = []
        n_f = np.float32(max(self.size, 1))
        for u_row in uniforms:
            u = (
                (np.arange(bs, dtype=np.float32) + u_row)
                / np.float32(bs) * total
            )
            idx = np.clip(
                np.searchsorted(cdf, u), 0, self.capacity - 1
            ).astype(np.int64)
            probs = p[idx] / max(float(total), 1e-12)
            weights = (n_f * np.maximum(probs, 1e-12)) ** (-beta)
            weights = (weights / max(float(weights.max()), 1e-12)).astype(
                np.float32
            )
            out.append((idx, self.gather(idx), weights))
        return out

    def update_priorities(self, idx: np.ndarray, prio: np.ndarray) -> None:
        prio = np.abs(prio.astype(np.float32)) + self.eps
        self.priorities[idx % self.capacity] = prio
        self.max_priority = np.float32(
            max(float(self.max_priority), float(prio.max()))
        )

    def gauges(self) -> dict:
        return dict(
            super().gauges(), max_priority=float(self.max_priority)
        )


class HostFifo:
    """Bounded FIFO chunk relay (the SEED arm): whole trajectory chunks
    in arrival order, oldest evicted when the learner lags — the same
    freshest-data-survives rule as the inference server's chunk queue."""

    def __init__(self, depth: int = 64):
        from collections import deque

        self.chunks: deque = deque()
        self.depth = int(depth)
        self.evicted = 0
        self.rows = 0

    def insert(self, spec: wire.PlaneSpec, rows: dict, n: int) -> None:
        if len(self.chunks) >= self.depth:
            _, _, old_n = self.chunks.popleft()
            self.evicted += 1
            self.rows -= old_n
        # copy: the decoded rows view a transient wire frame / slab slot
        self.chunks.append(
            (spec, {k: np.array(v[:n]) for k, v in rows.items()}, n)
        )
        self.rows += n

    def pop(self):
        if not self.chunks:
            return None
        spec, rows, n = self.chunks.popleft()
        self.rows -= n
        return spec, rows, n

    def gauges(self) -> dict:
        return {
            "size": self.rows, "fill": len(self.chunks) / self.depth,
            "queue_depth": len(self.chunks), "evicted_chunks": self.evicted,
        }


class _Peer:
    __slots__ = ("role", "transport", "spec", "slab", "views", "floor",
                 "applied", "trace", "slot_rows", "slots", "caps")

    def __init__(self):
        self.role = "sender"
        self.transport = "pickle"
        self.spec: wire.PlaneSpec | None = None
        self.slab = None
        self.views: list[dict] = []
        # exactly-once ingestion bookkeeping: ``floor`` is the highest
        # seq below which EVERYTHING applied; ``applied`` holds applied
        # seqs above it. A plain last-seq watermark would silently drop
        # the resend of a frame whose ORIGINAL was lost/corrupted while a
        # later frame already applied (the redelivery is out of order by
        # construction).
        self.floor = 0
        self.applied: set[int] = set()
        self.trace = None
        self.slot_rows = 0
        self.slots = 0
        # negotiated capability set from the hello (ISSUE 14): additive
        # and advisory — a pre-caps hello leaves it empty and everything
        # still works (lineage columns are ordinary spec fields)
        self.caps: set[str] = set()

    def seen(self, seq: int) -> bool:
        return seq <= self.floor or seq in self.applied

    def mark_applied(self, seq: int) -> None:
        self.applied.add(seq)
        while self.floor + 1 in self.applied:
            self.floor += 1
            self.applied.discard(self.floor)


def build_ring(cfg: Mapping[str, Any], spec: wire.PlaneSpec | None):
    kind = cfg.get("kind", "uniform")
    if kind == "fifo":
        return HostFifo(depth=int(cfg.get("fifo_depth", 64)))
    if spec is None:
        return None  # ring kinds allocate lazily at the first sender hello
    if kind == "prioritized":
        return HostPrioritized(
            spec, cfg["capacity"],
            alpha=cfg.get("priority_alpha", 0.6),
            beta0=cfg.get("priority_beta0", 0.4),
            eps=cfg.get("priority_eps", 1e-6),
        )
    if kind == "uniform":
        return HostRing(spec, cfg["capacity"])
    raise ValueError(f"shard kind {kind!r} not in uniform|prioritized|fifo")


def run_shard_server(
    cfg: dict,
    bind_address: str,
    shard_id: int,
    stop_event=None,
    fault_plan: list | None = None,
    trace_id: str | None = None,
    force_cpu: bool = False,
    threefry_partitionable: bool | None = None,
    untrack_slabs: bool = False,
    ops_address: str | None = None,
) -> int:
    """Serve one replay shard until ``stop_event`` (thread mode) or
    process death. Returns rows ingested.

    Runs unchanged as a thread or a spawned subprocess; ``cfg`` is a
    plain dict (kind/capacity/priority knobs/watermark_timeout_s/
    fifo_depth). ``untrack_slabs`` is set for PROCESS shards so the
    trainer-side plane owns every unlink (wire.create_slab's rule).
    """
    import zmq

    if fault_plan:
        faults.configure(fault_plan)
    _JAX_FLAGS["force_cpu"] = bool(force_cpu)
    _JAX_FLAGS["threefry_partitionable"] = threefry_partitionable
    _JAX_FLAGS["applied"] = False

    kind = cfg.get("kind", "uniform")
    watermark_timeout_s = float(cfg.get("watermark_timeout_s", 5.0))
    ring = build_ring(cfg, None) if kind == "fifo" else None
    # disk spill tier (ISSUE 18): every ingested insert also appends to a
    # per-shard write-ahead-log segment file; created with the ring at
    # the first sender hello (the spec arrives there). Ring kinds only —
    # fifo chunks carry per-chunk specs, the WAL frames one spec per log.
    spill_writer = None

    ctx = zmq.Context.instance()
    sock = ctx.socket(zmq.ROUTER)
    # a respawned sender/sampler reuses its identity; without handover the
    # ROUTER silently drops the new connection (shm_transport's rule)
    sock.setsockopt(zmq.ROUTER_HANDOVER, 1)
    peers: dict[bytes, _Peer] = {}
    ingested_rows = 0
    stats = {
        "shard": int(shard_id), "kind": kind,
        "wire_bytes_in": 0, "wire_bytes_out": 0,
        "samples_served": 0, "prio_updates": 0, "decode_errors": 0,
        "watermark_timeouts": 0, "ingest_rows_per_s": 0.0,
    }
    transit_ms: list[float] = []  # rolling ingest-transit samples
    deferred: list[tuple[bytes, dict, float]] = []  # (ident, req, arrived)
    ingest_t0 = None

    def send_to(ident: bytes, payload: bytes) -> None:
        stats["wire_bytes_out"] += len(payload)
        sock.send_multipart([ident, payload])

    def grant(ident: bytes, info: dict) -> None:
        nonlocal ring, spill_writer
        peer = peers.setdefault(ident, _Peer())
        if peer.applied:
            # re-hello compaction: a sender only re-helloes after clearing
            # its inflight window (death drops + counts those rows; a spec
            # change invalidates them), and it never reuses a seq — so
            # everything at or below the highest applied seq is settled.
            # Without this a permanently lost seq pins ``floor`` and
            # ``applied`` grows one entry per INSERT for the rest of the
            # run on the drop-and-revive path.
            peer.floor = max(peer.applied)
            peer.applied.clear()
        # the hello's seq_base covers the case compaction can't: a
        # RESPAWNED shard's fresh peer starts at floor=0 while the sender's
        # seqs continue from ~N — without re-basing, ``applied`` would
        # grow one entry per post-respawn INSERT forever
        base = int(info.get("seq_base", 0))
        if base > peer.floor:
            peer.floor = base
        peer.role = info.get("role", "sender")
        peer.trace = info.get("trace")
        peer.caps = set(info.get("caps") or ())
        peer.slot_rows = int(info.get("slot_rows", 0))
        peer.slots = int(info.get("slots", 0))
        token = info.get("token")
        spec = (
            wire.PlaneSpec.from_json(info["spec"])
            if info.get("spec") else None
        )
        peer.spec = spec
        if ring is None and spec is not None and kind != "fifo":
            ring = build_ring(cfg, spec)
        if spill_writer is None and spec is not None and kind != "fifo":
            from surreal_tpu.experience import spill

            spill_writer = spill.build_writer(
                cfg.get("spill"), spec, shard_id
            )
        requested = info.get("transport", "tcp")
        if requested == "pickle":
            peer.transport = "pickle"
            send_to(ident, wire.encode_hello_reply(
                "pickle", ingested_rows=ingested_rows, token=token))
            return
        if requested == "shm" and spec is not None and peer.slot_rows > 0:
            # an old slab for this identity belongs to a superseded
            # negotiation: UNLINK it here. Cleanup is normally the
            # client's (a SIGKILLed shard can't unlink), but a grant the
            # client abandoned (retried hello; the token mismatch makes
            # it drop the stale reply) is one the client may never have
            # attached — both sides unlinking is safe, unlink_slab
            # tolerates already-gone segments.
            if peer.slab is not None:
                peer.views = []
                wire.unlink_slab(peer.slab)
                peer.slab = None
            extras = wire.SAMPLE_EXTRAS if peer.role == "sampler" else ()
            layout = wire.PlaneSlab(
                spec, peer.slot_rows, max(peer.slots, 1), extras=extras
            )
            try:
                shm = wire.create_slab(layout, tag=f"s{shard_id}")
            except OSError as e:
                peer.transport = "tcp"
                send_to(ident, wire.encode_hello_reply(
                    "tcp", reason=f"shm create failed: {e}",
                    ingested_rows=ingested_rows, token=token,
                ))
                return
            if untrack_slabs:
                wire.untrack_slab(shm)
            peer.slab = shm
            peer.views = layout.views(shm.buf)
            peer.transport = "shm"
            send_to(ident, wire.encode_hello_reply(
                "shm", name=shm.name, slab=layout,
                ingested_rows=ingested_rows, token=token,
            ))
            return
        peer.transport = "tcp"
        send_to(ident, wire.encode_hello_reply(
            "tcp", ingested_rows=ingested_rows, token=token))

    def ingest(ident: bytes, peer: _Peer, req: dict) -> None:
        nonlocal ingested_rows, ingest_t0
        seq, n = int(req["seq"]), int(req["n"])
        if peer.seen(seq):
            # duplicate of an applied frame (sender retry after a lost
            # ack): re-ack, never re-apply — exactly-once ingestion
            send_to(ident, wire.encode_insert_ok(seq, ingested_rows))
            return
        if peer.transport == "shm" and "body" in req and not len(req["body"]):
            rows = {
                k: v for k, v in peer.views[int(req["slot"])].items()
            }
        elif req.get("rows") is not None:  # pickle fallback dict
            rows = wire.flatten_fields(req["rows"])
        else:
            rows = peer.spec.unpack(req["body"], n)
        if isinstance(ring, HostFifo):
            ring.insert(peer.spec, rows, n)
        else:
            ring.insert(rows, n)
        if spill_writer is not None:
            # WAL append AFTER the ring: the warm tier is the availability
            # tier — a failing disk degrades (counted) without stalling
            # ingest. Rows may view a transient frame/slab slot; the
            # writer's codec copies during encode.
            spill_writer.append(rows, n)
        peer.mark_applied(seq)
        ingested_rows += n
        now = time.monotonic()
        if ingest_t0 is None:
            ingest_t0 = now
        elif now > ingest_t0:
            stats["ingest_rows_per_s"] = ingested_rows / (now - ingest_t0)
        t_send = float(req.get("t_send", 0.0))
        if t_send > 0:
            transit_ms.append(max(0.0, (time.time() - t_send) * 1e3))
            del transit_ms[:-256]
        send_to(ident, wire.encode_insert_ok(seq, ingested_rows))

    def serve_sample(ident: bytes, peer: _Peer, req: dict) -> None:
        f = faults.fire("experience.sample")
        if f is not None and f["kind"] == "delay_sample":
            faults.sleep_ms(f)
        nk = max(1, int(req.get("nkeys", 1)))
        keys = keys_from_bytes(req["key"], nk)
        bs = int(req["bs"])
        results = ring.sample_many(keys, bs, beta=req.get("beta"))
        stats["samples_served"] += nk
        seq, slot = int(req["seq"]), int(req["slot"])
        has_w = results[0][2] is not None  # (idx, rows, weights)
        flags = wire.F_HAS_WEIGHTS if has_w else 0
        if peer.transport == "shm" and peer.views:
            for u, (idx, batch, weights) in enumerate(results):
                v = peer.views[(slot + u) % len(peer.views)]
                for name in peer.spec.names():
                    v[name][:bs] = batch[name]
                v["_idx"][:bs] = idx.astype(np.uint32)
                if weights is not None:
                    v["_is_weights"][:bs] = weights
            send_to(ident, wire.encode_sample_ok(
                seq, bs, nk, slot, flags | wire.F_SHM))
        elif peer.transport == "pickle":
            send_to(ident, wire.encode_pickle_msg({
                "kind": "sample_ok", "seq": seq, "bs": bs, "nkeys": nk,
                "many": [
                    {"idx": idx, "is_weights": w, "rows": batch}
                    for idx, batch, w in results
                ],
            }))
        else:
            body = wire.pack_sample_body(
                peer.spec,
                [(idx.astype(np.uint32), w, batch)
                 for idx, batch, w in results],
            )
            send_to(ident, wire.encode_sample_ok(seq, bs, nk, 0, flags, body))

    def serve_pop(ident: bytes, peer: _Peer, req: dict) -> None:
        f = faults.fire("experience.sample")
        if f is not None and f["kind"] == "delay_sample":
            faults.sleep_ms(f)
        item = ring.pop() if isinstance(ring, HostFifo) else None
        seq = int(req["seq"])
        if item is None:
            send_to(ident, wire.encode_pop_reply(seq, 0, None))
            return
        spec, rows, n = item
        stats["samples_served"] += 1
        if peer.transport == "pickle":
            send_to(ident, wire.encode_pickle_msg({
                "kind": "pop_ok", "seq": seq, "n": n,
                "spec": spec.to_json(), "rows": rows,
            }))
        else:
            send_to(ident, wire.encode_pop_reply(
                seq, n, spec, spec.pack(rows, n)))

    def handle(ident: bytes, payload: bytes) -> None:
        stats["wire_bytes_in"] += len(payload)
        try:
            kind_s, obj = wire.decode_payload(payload)
        except Exception:
            # a corrupt wire frame (chaos corrupt_wire_frame, or a
            # half-dead peer) is counted and dropped — the sender's
            # bounded retry redelivers inserts; samples are re-requested
            stats["decode_errors"] += 1
            return
        if kind_s == "msg":  # pickle fallback: route by the dict's kind
            obj = dict(obj)
            kind_s = obj.get("kind", "?")
            if kind_s == "hello":
                grant(ident, obj)
                return
        if kind_s == "hello":
            grant(ident, obj)
            return
        # prio/stats need no per-peer transport state (priority frames may
        # arrive on a dedicated main-thread socket — zmq sockets are not
        # thread-safe, so the sampler keeps its sample socket on the
        # prefetch thread and its priority socket on the trainer thread)
        if kind_s == "prio":
            if isinstance(ring, HostPrioritized):
                ring.update_priorities(
                    np.asarray(obj["idx"]), np.asarray(obj["prio"])
                )
                stats["prio_updates"] += int(obj["n"])
            return
        if kind_s == "stats":
            # telemetry traffic is NOT experience wire: the stats poll
            # scales with the metrics cadence, and counting it would let
            # a cadence change move the gated wire-B/step metric with
            # zero change to the data path
            stats["wire_bytes_in"] -= len(payload)
            out = dict(stats)
            out["ingested_rows"] = ingested_rows
            out["sample_queue_depth"] = len(deferred)
            if ring is not None:
                out.update(ring.gauges())
            if spill_writer is not None:
                out.update(spill_writer.stats())
            from surreal_tpu.session.telemetry import latency_percentiles

            p = latency_percentiles(transit_ms)
            if p is not None:
                out["ingest_transit_ms"] = p
            # bypasses send_to: the reply is telemetry too (uncounted)
            sock.send_multipart(
                [ident, wire.encode_stats_reply(int(obj["seq"]), out)]
            )
            return
        peer = peers.get(ident)
        if peer is None:
            return  # stale frame from before a respawn; peer will re-hello
        if kind_s == "insert":
            ingest(ident, peer, obj)
        elif kind_s == "sample":
            if ring is None or isinstance(ring, HostFifo):
                return  # ring samples need a ring (fifo peers use POP)
            if int(obj.get("watermark", 0)) > ingested_rows:
                deferred.append((ident, obj, time.monotonic()))
            else:
                serve_sample(ident, peer, obj)
        elif kind_s == "pop":
            serve_pop(ident, peer, obj)

    def flush_deferred() -> None:
        if not deferred:
            return
        now = time.monotonic()
        still: list = []
        for ident, req, arrived in deferred:
            timed_out = now - arrived >= watermark_timeout_s
            if int(req.get("watermark", 0)) <= ingested_rows or timed_out:
                if timed_out and int(req.get("watermark", 0)) > ingested_rows:
                    # sender died / shard respawned empty: serve what
                    # exists rather than deadlock the learner
                    stats["watermark_timeouts"] += 1
                peer = peers.get(ident)
                if peer is not None:
                    serve_sample(ident, peer, req)
            else:
                still.append((ident, req, arrived))
        deferred[:] = still

    # ops plane (ISSUE 13): each shard pushes its own gauge row to the
    # run aggregator — its OWN PUSH socket in this serve loop (zmq
    # sockets are not thread-safe), cadence-bounded by the pusher.
    # Process shards inherit ``ops_address`` via spawn kwargs, exactly
    # like the fault plan and the trace id.
    ops = None
    if ops_address:
        from surreal_tpu.session.opsplane import OpsPusher

        ops = OpsPusher(
            ops_address, f"experience.shard{shard_id}", trace_id=trace_id
        )

    def ops_push() -> None:
        if ops is None:
            return
        gauges = {
            k: v for k, v in stats.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        }
        gauges["ingested_rows"] = ingested_rows
        gauges["sample_queue_depth"] = len(deferred)
        if ring is not None:
            gauges.update(ring.gauges())
        if spill_writer is not None:
            gauges.update(spill_writer.stats())
        from surreal_tpu.session.telemetry import latency_percentiles

        p = latency_percentiles(transit_ms)
        ops.push(
            gauges=gauges,
            hops={"ingest_transit_ms": p} if p is not None else None,
        )

    try:
        sock.bind(bind_address)
        while not (stop_event is not None and stop_event.is_set()):
            ops_push()
            f = faults.fire("experience.shard")
            if f is not None:
                if f["kind"] == "kill_shard":
                    raise faults.FaultInjected(
                        f"chaos: kill_shard (shard {shard_id})"
                    )
                if f["kind"] == "delay":
                    faults.sleep_ms(f)
            if sock.poll(100):
                while True:
                    try:
                        ident, payload = sock.recv_multipart(zmq.NOBLOCK)
                    except zmq.Again:
                        break
                    handle(ident, payload)
            flush_deferred()
        return ingested_rows
    finally:
        # Crash path (kill_shard, SIGKILL never gets here): release OUR
        # mappings only — the client owns the unlink (it renegotiates or
        # closes). GRACEFUL stop additionally unlinks: a granted slab the
        # client never attached (its hello attempt timed out) has no
        # other reaper; a client that DID attach unlinks too, which
        # unlink_slab tolerates (ENOENT is a no-op).
        if ops is not None:
            ops.close()
        if spill_writer is not None:
            spill_writer.close()
        graceful = stop_event is not None and stop_event.is_set()
        for peer in peers.values():
            peer.views = []
            if peer.slab is not None:
                if graceful:
                    wire.unlink_slab(peer.slab)
                else:
                    try:
                        peer.slab.close()
                    except OSError:
                        pass
        sock.close(100)
