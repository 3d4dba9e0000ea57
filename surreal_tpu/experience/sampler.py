"""ShardedSampler: the learner-side fan-in of the experience plane.

One DEALER link per shard; each training iteration's ``updates_per_iter``
batches are fetched from ALL shards (``batch_size / num_shards`` rows
each, concatenated in shard order) on the staging thread of a
``learners/prefetch.py::Prefetcher`` — while the learner drains iteration
k's SGD updates, the sampler is already fan-ing in iteration k+1's
batches and paying their host->device transfer, so the learner never
waits on experience ingest (the sample-wait gauge measures the residue).

Determinism: the sampler owns its key chain (one ``jax.random.split``
per update, ``fold_in(key, shard)`` per shard), and every sample request
carries the caller's per-shard watermark — under the strict off-policy
loop the training record is exactly reproducible run-to-run (tested).

Resilience (the PR-5 discipline): sample requests are idempotent reads,
so a silent shard costs bounded, backed-off re-requests; an exhausted
budget marks the shard dead (revived under the same exponential backoff
as the sender) and its share of the batch is refetched from a surviving
shard with a folded key — the learner keeps training on surviving shards
(chaos-tested), degrading batch composition instead of availability.

Priority updates ride a DEDICATED main-thread socket (zmq sockets are
not thread-safe; the sample socket lives on the prefetch thread) as ONE
batched PRIO frame per shard per iteration — all ``updates_per_iter``
index sets in one frame, extending PR 4's ``sample_many`` batched
discipline to the wire.
"""

from __future__ import annotations

import queue
import time
from typing import Any, Sequence

import numpy as np

from surreal_tpu.experience import wire
from surreal_tpu.experience.link import ShardLinkBase, negotiate_link


class _SampleLink(ShardLinkBase):
    """Sampler-side shard link: the shared base plus the reply-slot
    cursor and the lazy main-thread priority/stats channel."""

    def __init__(self, address: str, shard_id: int, identity: str):
        super().__init__(address, shard_id, identity)
        self.prio_sock = None  # lazy: main-thread priority/stats channel
        self.slots = 1
        self.next_slot = 0

    def on_slab(self, layout: wire.PlaneSlab) -> None:
        self.slots = layout.slots

    def prio_channel(self):
        import zmq

        if self.prio_sock is None:
            self.prio_sock = zmq.Context.instance().socket(zmq.DEALER)
            self.prio_sock.setsockopt(zmq.SNDTIMEO, 10_000)
            self.prio_sock.connect(self.address)
        return self.prio_sock

    def close(self) -> None:
        super().close()  # client-owned slab cleanup + sample socket
        if self.prio_sock is not None:
            self.prio_sock.close(100)


def partition_shards(num_shards: int, members: int) -> list[list[int]]:
    """Shard-major partition of ``num_shards`` shard indices into
    ``members`` disjoint, covering, contiguous subsets — the learner
    group's draining seam (parallel/learner_group.py). Contiguity keeps
    the group's concatenated batch in GLOBAL shard order (each member's
    fan-in concatenates its sub-batches in local = global order), so
    priority routing and lineage columns stay position-stable across
    membership changes. Earlier members absorb the remainder shards."""
    if members < 1:
        raise ValueError(f"learner_group members={members} must be >= 1")
    if members > num_shards:
        raise ValueError(
            f"learner_group members={members} exceeds num_shards="
            f"{num_shards}: a member with no shard subset would drain "
            "nothing (shrink the group or add shards)"
        )
    base, extra = divmod(num_shards, members)
    out, start = [], 0
    for m in range(members):
        n = base + (1 if m < extra else 0)
        out.append(list(range(start, start + n)))
        start += n
    return out


class ShardedSampler:
    def __init__(
        self,
        addresses: Sequence[str],
        spec: wire.PlaneSpec | None,
        batch_size: int,
        kind: str = "uniform",
        base_key=None,
        updates_per_iter: int = 1,
        transport: str = "auto",
        trace: str | None = None,
        prefetch: bool = True,
        retries: int = 2,
        backoff_s: float = 0.25,
        sample_timeout_s: float = 10.0,
        hello_timeout_s: float = 60.0,
        respawn_backoff_s: float = 0.5,
        respawn_backoff_cap_s: float = 30.0,
        device_put: bool = True,
        stop_event=None,
    ):
        S = len(addresses)
        if kind != "fifo" and batch_size % S:
            raise ValueError(
                f"replay.batch_size={batch_size} must divide across "
                f"{S} experience shards"
            )
        self.spec = spec
        self.kind = kind
        self.prioritized = kind == "prioritized"
        self.batch_size = int(batch_size)
        self.bs_shard = self.batch_size // S if kind != "fifo" else 0
        self.updates_per_iter = max(1, int(updates_per_iter))
        self.mode = transport
        self.trace = trace
        self.retries = max(0, int(retries))
        self.backoff_s = float(backoff_s)
        self.sample_timeout_s = float(sample_timeout_s)
        self.hello_timeout_s = float(hello_timeout_s)
        self._respawn_base = float(respawn_backoff_s)
        self._respawn_cap = float(respawn_backoff_cap_s)
        # set at plane shutdown: bounded waits on the prefetch thread bail
        # so it can be joined before the plane closes its sockets (zmq
        # sockets are not thread-safe — use+close is undefined)
        self._stop = stop_event
        self._device_put = bool(device_put)
        self.links = [
            _SampleLink(a, s, f"xp-sampler-{s}")
            for s, a in enumerate(addresses)
        ]
        self._key = base_key
        self._rr = 0  # fifo pop round-robin
        self.refetches = 0
        self.wire_bytes = 0
        self.sample_wait_ms = 0.0  # EWMA of get_iteration wait (the
        #                            "learner never waits" gauge)
        self._closed = False
        self._jobs: queue.Queue = queue.Queue()
        self._prefetch = None
        if prefetch:
            from surreal_tpu.learners.prefetch import Prefetcher

            self._prefetch = Prefetcher(self._produce, name="xp-sample")

    # -- negotiation (sample channel; prefetch thread) -----------------------
    def _negotiate(self, link: _SampleLink, timeout_s: float) -> bool:
        """Hello handshake — the shared ``experience/link.py`` routine.
        2x updates_per_iter sample slots: the burst fan-out keeps K
        outstanding, and a retried straggler must land in a slot no
        in-flight duplicate serve can still write. The FIFO arm forces
        the raw tcp codec (chunk layouts are only known to the shard
        after its first insert — replies carry their spec in-frame)."""
        def send(payload: bytes) -> None:
            self.wire_bytes += len(payload)
            link.sock.send(payload)

        obj = negotiate_link(
            link, send,
            role="sampler", spec=self.spec, slot_rows=self.bs_shard,
            slots=2 * self.updates_per_iter, mode=self.mode,
            timeout_s=timeout_s, trace=self.trace, stop_event=self._stop,
            force_tcp=self.kind == "fifo",
        )
        if obj is None:
            return self._mark_dead(link)
        return True

    def _mark_dead(self, link: _SampleLink) -> bool:
        return link.schedule_backoff(self._respawn_base, self._respawn_cap)

    def _revive(self, link: _SampleLink) -> bool:
        if link.negotiated and not link.dead:
            return True
        if not link.revive_due():
            return False
        return self._negotiate(
            link, self.hello_timeout_s if not link.dead else 2.0
        )

    # -- one batch (prefetch thread) -----------------------------------------
    def _request(self, link: _SampleLink, keys, beta: float,
                 watermark: int, bs: int) -> tuple[int, int]:
        """Send ONE sample request carrying every key in ``keys`` — the
        sample_many discipline on-wire: the shard draws all index sets in
        one vmapped call and replies once."""
        import jax

        nk = len(keys)
        link.seq += 1
        slot = link.next_slot
        link.next_slot = (link.next_slot + nk) % max(link.slots, 1)
        key_bytes = b"".join(
            np.asarray(jax.random.key_data(k), np.uint32).tobytes()
            for k in keys
        )
        t_send = time.time() if wire.local_address(link.address) else 0.0
        if link.transport == "pickle":
            payload = wire.encode_pickle_msg({
                "kind": "sample", "seq": link.seq, "bs": bs, "nkeys": nk,
                "watermark": int(watermark), "beta": float(beta),
                "slot": slot, "key": key_bytes, "t_send": t_send,
            })
        else:
            payload = wire.encode_sample(
                link.seq, bs, int(watermark), float(beta), slot, key_bytes,
                nkeys=nk, t_send=t_send,
            )
        self.wire_bytes += len(payload)
        link.sock.send(payload)
        return link.seq, slot

    def _collect(self, link: _SampleLink, want_seq: int,
                 deadline: float) -> dict | None:
        """Wait for one sample reply on ``link`` (older seqs from retries
        are drained and ignored)."""
        import zmq

        while time.monotonic() < deadline:
            if self._stop is not None and self._stop.is_set():
                return None
            if not link.sock.poll(100):
                continue
            try:
                kind, obj = wire.decode_payload(link.sock.recv(zmq.NOBLOCK))
            except zmq.Again:
                continue
            if kind == "msg":
                kind = obj.get("kind", "?")
            if kind == "sample_ok" and int(obj["seq"]) == want_seq:
                return obj
        return None

    def _decode(self, link: _SampleLink, obj: dict):
        """One sample reply -> list of (idx, weights, rows) per key."""
        if "many" in obj:  # pickle fallback
            out = []
            for seg in obj["many"]:
                w = seg.get("is_weights")
                out.append((
                    np.asarray(seg["idx"], np.int64),
                    None if w is None else np.asarray(w, np.float32),
                    {k: np.asarray(v) for k, v in seg["rows"].items()},
                ))
            return out
        bs, nk = int(obj["bs"]), max(1, int(obj.get("nkeys", 1)))
        if obj.get("flags", 0) & wire.F_SHM:
            out = []
            base = int(obj["slot"])
            for u in range(nk):
                v = link.views[(base + u) % max(link.slots, 1)]
                rows = {
                    name: np.array(v[name][:bs])
                    for name in self.spec.names()
                }
                idx = np.array(v["_idx"][:bs], np.int64)
                weights = (
                    np.array(v["_is_weights"][:bs])
                    if obj["flags"] & wire.F_HAS_WEIGHTS else None
                )
                out.append((idx, weights, rows))
            return out
        segs = wire.unpack_sample_body(
            self.spec, obj["body"], bs, nk,
            bool(obj["flags"] & wire.F_HAS_WEIGHTS),
        )
        # copy out of the transient frame
        return [
            (
                np.asarray(idx, np.int64).copy(),
                None if weights is None else np.array(weights),
                {k: np.array(v) for k, v in rows.items()},
            )
            for idx, weights, rows in segs
        ]

    def _fetch_shard(self, link: _SampleLink, keys, beta, watermark, bs):
        """Bounded-retry fetch of one shard's sub-batches (one request,
        ``len(keys)`` drawn sets); None = dead."""
        if not self._revive(link):
            return None
        for attempt in range(self.retries + 1):
            seq, _slot = self._request(link, keys, beta, watermark, bs)
            obj = self._collect(
                link, seq, time.monotonic() + self.sample_timeout_s
            )
            if obj is not None:
                return self._decode(link, obj)
            if self._stop is not None and self._stop.is_set():
                break
            if attempt < self.retries:
                time.sleep(self.backoff_s * 2.0 ** attempt)
        self._mark_dead(link)
        return None

    def fetch_batch(self, key, beta: float, watermarks: Sequence[int]):
        """One fan-in batch: per-shard keys fold the shard id (a single
        shard uses the caller's key verbatim — the bit-equality contract
        with the in-process replay); sub-batches concatenate in shard
        order. Dead shards' shares are refetched from the first surviving
        shard with a distinct folded key."""
        return self._fetch_iteration([key], beta, watermarks)[0]

    def _fetch_iteration(self, keys, beta: float, watermarks):
        """Fan out one iteration's samples: ONE request per shard carries
        every update's folded key (the shard draws all index sets in one
        vmapped call — sample_many on-wire), replies drain in arrival
        order, so the whole iteration costs ~one round trip. A silent
        shard gets bounded re-requests (idempotent reads), then is marked
        dead and its share refetched from a survivor."""
        import jax
        import zmq

        S = len(self.links)
        K = len(keys)
        shard_keys = {
            s: [
                keys[u] if S == 1 else jax.random.fold_in(keys[u], s)
                for u in range(K)
            ]
            for s in range(S)
        }
        results: dict[int, list] = {}   # shard -> K decoded sets
        pending: dict[int, int] = {}    # shard -> awaited seq
        for s, link in enumerate(self.links):
            if not self._revive(link):
                continue
            seq, _slot = self._request(
                link, shard_keys[s], beta,
                int(watermarks[s]) if watermarks else 0, self.bs_shard,
            )
            pending[s] = seq
        for attempt in range(self.retries + 1):
            deadline = time.monotonic() + self.sample_timeout_s
            while pending and time.monotonic() < deadline:
                if self._stop is not None and self._stop.is_set():
                    # plane shutdown: bail so the prefetch thread joins
                    # before sockets close; pending shards mark dead below
                    # (nobody consumes the result at this point)
                    break
                progress = False
                for s in list(pending):
                    link = self.links[s]
                    while s in pending and link.sock.poll(0):
                        try:
                            kind, obj = wire.decode_payload(
                                link.sock.recv(zmq.NOBLOCK)
                            )
                        except zmq.Again:
                            break
                        if kind == "msg":
                            kind = obj.get("kind", "?")
                        if (
                            kind == "sample_ok"
                            and int(obj["seq"]) == pending[s]
                        ):
                            results[s] = self._decode(link, obj)
                            del pending[s]
                            progress = True
                if not progress and pending:
                    # nothing readable: block briefly on one pending link
                    # instead of spinning
                    self.links[next(iter(pending))].sock.poll(20)
            if not pending:
                break
            if self._stop is not None and self._stop.is_set():
                break
            if attempt < self.retries:
                for s in list(pending):
                    nseq, _ = self._request(
                        self.links[s], shard_keys[s], beta,
                        int(watermarks[s]) if watermarks else 0,
                        self.bs_shard,
                    )
                    pending[s] = nseq
                time.sleep(self.backoff_s * 2.0 ** attempt)
        for s in pending:
            self._mark_dead(self.links[s])
        alive = sorted(results)
        # batch segment -> the shard whose ring actually served it: a dead
        # shard's refetched share carries the SURVIVOR's local ring indices,
        # so priority updates must route there (keying them under the dead
        # shard would corrupt its ring after a respawn)
        srcs = {s: s for s in results}
        for s in range(S):
            if s in results:
                continue
            if not alive:
                raise TimeoutError(
                    "every experience shard is unreachable — the plane "
                    "supervisor should have respawned them"
                )
            # degrade composition, not availability: a surviving shard
            # covers the dead shard's share under distinct folded keys
            self.refetches += 1
            got = self._fetch_shard(
                self.links[alive[0]],
                [jax.random.fold_in(k, 0x5EED) for k in shard_keys[s]],
                beta, 0, self.bs_shard,
            )
            if got is None:
                raise TimeoutError("experience shard refetch failed")
            results[s] = got
            srcs[s] = alive[0]
        out = []
        for u in range(K):
            parts = [(s, results[s][u]) for s in range(S)]
            batch = {
                name: np.concatenate(
                    [p[1][2][name] for p in parts], axis=0
                )
                for name in self.spec.names()
            }
            info: dict[str, Any] = {
                "shard_idx": {p[0]: p[1][0] for p in parts},
                "shard_src": dict(srcs),
            }
            if self.prioritized:
                ws = [
                    p[1][1] if p[1][1] is not None
                    else np.ones(self.bs_shard, np.float32)
                    for p in parts
                ]
                batch["is_weights"] = np.concatenate(ws, axis=0)
            out.append((wire.unflatten_fields(batch), info))
        return out

    def _produce(self):
        """Prefetcher body: wait for the next iteration job, burst-fetch
        all its update batches, and pay the host->device transfer here —
        the learner thread only ever picks up finished device batches."""
        import jax

        while True:
            try:
                job = self._jobs.get(timeout=0.2)
                break
            except queue.Empty:
                if self._closed:
                    return None
        if job is None:
            return None
        watermarks, beta, keys = job
        if keys is None:
            # the sampler owns the key chain (the tiers-off default)
            keys = []
            for _ in range(self.updates_per_iter):
                self._key, sub = jax.random.split(self._key)
                keys.append(sub)
        fetched = self._fetch_iteration(keys, beta, watermarks)
        out = []
        for key, (batch, info) in zip(keys, fetched):
            if self._device_put:
                batch = jax.device_put(batch)
            out.append((batch, key, info))
        return out

    # -- iteration API (trainer thread) --------------------------------------
    def request_iteration(self, watermarks: Sequence[int],
                          beta: float = 0.0, keys=None) -> None:
        """``keys`` (one per update) lets a tier wrapper own the key
        chain — the warm fall-back then draws the EXACT keys a hot hit
        would have used. None keeps this sampler's own chain, byte-for-
        byte the pre-tiers behavior."""
        self._jobs.put((list(watermarks), float(beta), keys))

    def get_iteration(self):
        t0 = time.perf_counter()
        if self._prefetch is not None:
            item = self._prefetch.get()
        else:
            item = self._produce()
        wait_ms = (time.perf_counter() - t0) * 1e3
        self.sample_wait_ms = 0.2 * wait_ms + 0.8 * self.sample_wait_ms
        return item

    def update_priorities(self, infos: Sequence[dict],
                          prios: Sequence[np.ndarray]) -> None:
        """Batched priority refresh: ONE PRIO frame per shard carrying
        every update's (local idx, |td|) pairs — fire-and-forget on the
        main-thread channel."""
        per_shard_idx: dict[int, list] = {}
        per_shard_prio: dict[int, list] = {}
        for info, prio in zip(infos, prios):
            prio = np.asarray(prio, np.float32)
            off = 0
            for s in sorted(info["shard_idx"]):
                idx = info["shard_idx"][s]
                # route to the shard that SERVED the segment (a refetched
                # share's indices live in the survivor's ring, not the
                # dead shard's)
                dst = info.get("shard_src", {}).get(s, s)
                per_shard_idx.setdefault(dst, []).append(idx)
                per_shard_prio.setdefault(dst, []).append(
                    prio[off:off + len(idx)]
                )
                off += len(idx)
        import zmq

        for s, idx_list in per_shard_idx.items():
            link = self.links[s]
            if link.dead:
                continue
            frame = wire.encode_prio(
                0,
                np.concatenate(idx_list).astype(np.uint32),
                np.concatenate(per_shard_prio[s]),
            )
            self.wire_bytes += len(frame)
            try:
                link.prio_channel().send(frame, zmq.NOBLOCK)
            except zmq.ZMQError:
                pass  # advisory refresh; the next batch's frame retries

    # -- FIFO arm (SEED) -----------------------------------------------------
    def pop_chunk(self, timeout_s: float = 2.0):
        """Round-robin pop of one trajectory chunk, or None when every
        shard is empty within the budget. The reply carries its own spec
        (chunk layouts aren't known at hello time)."""
        deadline = time.monotonic() + timeout_s
        S = len(self.links)
        while time.monotonic() < deadline:
            link = self.links[self._rr % S]
            self._rr += 1
            if not self._revive(link):
                continue
            link.seq += 1
            if link.transport == "pickle":
                payload = wire.encode_pickle_msg(
                    {"kind": "pop", "seq": link.seq, "slot": 0}
                )
            else:
                payload = wire.encode_pop(link.seq)
            self.wire_bytes += len(payload)
            import zmq

            try:
                link.sock.send(payload, zmq.NOBLOCK)
            except zmq.ZMQError:
                self._mark_dead(link)
                continue
            obj = self._pop_collect(link, link.seq, deadline)
            if obj is None:
                continue
            n = int(obj["n"])
            if n == 0:
                time.sleep(0.02)  # all caught up; don't spin the wire
                continue
            if "rows" in obj:
                rows = {k: np.asarray(v) for k, v in obj["rows"].items()}
            else:
                rows = {
                    k: np.array(v)
                    for k, v in obj["spec"].unpack(obj["body"], n).items()
                }
            return wire.unflatten_fields(rows), n
        return None

    def _pop_collect(self, link, want_seq, deadline):
        import zmq

        stop = min(deadline, time.monotonic() + 0.5)
        while time.monotonic() < stop:
            if self._stop is not None and self._stop.is_set():
                return None
            if not link.sock.poll(50):
                continue
            try:
                kind, obj = wire.decode_payload(link.sock.recv(zmq.NOBLOCK))
            except zmq.Again:
                continue
            if kind == "msg":
                kind = obj.get("kind", "?")
            # accept STALE pop_ok replies too (seq < want): POP is not
            # idempotent — the shard already popped the chunk when it
            # replied, so discarding a reply that missed an earlier
            # collect window would silently lose that trajectory
            if kind == "pop_ok" and int(obj["seq"]) <= want_seq:
                if "spec" in obj and obj.get("spec") is not None and not isinstance(obj["spec"], wire.PlaneSpec):
                    obj["spec"] = wire.PlaneSpec.from_json(obj["spec"])
                return obj
        return None

    def gauges(self) -> dict[str, float]:
        return {
            "sample_wait_ms": float(self.sample_wait_ms),
            "refetches": float(self.refetches),
            "wire_bytes_out": float(self.wire_bytes),
            "dead_links": float(sum(1 for l in self.links if l.dead)),
        }

    def close(self) -> None:
        self._closed = True
        self._jobs.put(None)
        if self._prefetch is not None:
            self._prefetch.close()
        for link in self.links:
            link.close()


class TieredSampler:
    """Hot-tier front of the shard fan-in (replay tiers, ISSUE 18).

    Wraps the warm :class:`ShardedSampler` with a device-resident
    :class:`surreal_tpu.replay.tiers.HotTier`: while the hot ring is
    warm enough (``ready()``), an iteration's uniform batches are drawn
    ON DEVICE at *request* time — the jitted draw+gather dispatches
    async and overlaps the learner, so ``get_iteration`` returns already-
    resident batches with ~zero wait (the mechanism behind the hot-hit
    ``experience/sample_wait_ms`` gauge). A miss —
    hot ring still filling — falls back to the PR-8 shard-major fan-in
    with the SAME keys, counted in ``tier/hot_misses``, never silent.

    This wrapper owns the key chain the warm sampler otherwise owns (one
    split per update, handed down through ``request_iteration(keys=)``),
    so hot hits and warm misses consume the same key sequence the
    tiers-off path would.

    Uniform-only by construction: prioritized sampling needs the shard's
    priority state between draws, which a device-resident snapshot
    cannot see — the constructor refuses rather than skewing silently.
    """

    def __init__(self, warm: ShardedSampler, hot, base_key=None):
        if warm.prioritized:
            raise ValueError(
                "replay.tiers.hot requires uniform replay: prioritized "
                "draws depend on the shards' live priority state"
            )
        if warm.kind == "fifo":
            raise ValueError("replay.tiers.hot does not apply to the fifo arm")
        from collections import deque

        self._warm = warm
        self.hot = hot
        # adopt the warm sampler's UNSPLIT chain (it never splits again —
        # every request hands keys down): update u draws the exact key
        # the tiers-off sampler would draw, hot hit or warm miss alike
        self._key = base_key if base_key is not None else warm._key
        self.updates_per_iter = warm.updates_per_iter
        self.batch_size = warm.batch_size
        self.prioritized = False
        self.kind = warm.kind
        # per pending iteration: ("hot", [(device batch, key), ...]) or
        # ("warm", None) — FIFO with request/get, like the job queue
        self._route: "deque[tuple[str, list | None]]" = deque()
        self.hot_hits = 0
        self.hot_misses = 0
        self.sample_wait_ms = 0.0

    def append(self, rows) -> None:
        """Feed the hot ring (flat [n, ...] arrays — the collector's
        device-resident transition batch, before any host hop)."""
        self.hot.append(rows)

    def request_iteration(self, watermarks: Sequence[int],
                          beta: float = 0.0) -> None:
        import jax

        keys = []
        for _ in range(self.updates_per_iter):
            self._key, sub = jax.random.split(self._key)
            keys.append(sub)
        if self.hot.ready():
            # dispatch the draws NOW: async device work overlaps the
            # learner exactly like the warm prefetch thread would
            staged = [(self.hot.sample(k), k) for k in keys]
            self._route.append(("hot", staged))
            self.hot_hits += self.updates_per_iter
        else:
            self.hot_misses += self.updates_per_iter
            self._warm.request_iteration(watermarks, beta, keys=keys)
            self._route.append(("warm", None))

    def get_iteration(self):
        t0 = time.perf_counter()
        if not self._route:
            return None
        src, staged = self._route.popleft()
        if src == "hot":
            out = [
                (wire.unflatten_fields(batch), key, {"tier": "hot"})
                for batch, key in staged
            ]
        else:
            out = self._warm.get_iteration()
        wait_ms = (time.perf_counter() - t0) * 1e3
        self.sample_wait_ms = 0.2 * wait_ms + 0.8 * self.sample_wait_ms
        return out

    def update_priorities(self, infos, prios) -> None:
        self._warm.update_priorities(infos, prios)

    def gauges(self) -> dict[str, float]:
        g = self._warm.gauges()
        g["sample_wait_ms"] = float(self.sample_wait_ms)
        g["hot_hits"] = float(self.hot_hits)
        g["hot_misses"] = float(self.hot_misses)
        return g

    def close(self) -> None:
        self._warm.close()
