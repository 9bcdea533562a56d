"""TPU-native commit-digest plane (§4 multicast on the ICI).

The paper's commit-set multicast is a host-network broadcast.  When AFT
nodes are TPU hosts, the metadata plane can instead ride the interconnect:
each node packs its recently-committed transaction IDs into a fixed-size
``(k, 4)`` int32 digest — ``[ts_hi, ts_lo, hash_hi, hash_lo]`` rows — and a
single ``shard_map``-ped ``all_gather`` over the ``nodes`` mesh axis
exchanges all digests in one collective, off the transaction critical path.

A digest row is a *pointer*, not the record: the receiver resolves the full
commit record from shared storage via the timestamp-prefixed commit-log key
(IDs serialize with a zero-padded timestamp, so a prefix listing is exact),
verifies the uuid hash, and merges via the same ``merge_remote_commits``
path the host-network multicast uses.  The write-ordering protocol (§3.3)
guarantees the record is durable before its ID can appear in any digest.

Supersedence pruning (§4.1, Algorithm 2) applies before packing, exactly as
in the host-network plane.

The plane also carries a *horizon channel*: one extra ``(1, 4)`` row per
node per round publishes the node's commit horizon
(``AftNode.commit_horizon_ns``), and every receiver folds the gathered
horizons into its read watermark (``set_watermark_provider``) — the same
bounded-staleness frontier the host-network ``MulticastAgent`` gossips.
A node withholds its horizon for a round whenever the round's digest could
not carry its full fresh set (k-truncation or §4.1 pruning): a horizon must
never claim coverage of a commit whose pointer was not exchanged, so the
channel degrades to a stalled (fail-safe) watermark instead.
"""

from __future__ import annotations

import hashlib
import json
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ids import TxnId
from .node import AftNode
from .records import COMMIT_PREFIX, TransactionRecord, commit_key
from .supersede import is_superseded

DIGEST_WIDTH = 4

# storage namespace for published node-metrics snapshots (repro/obs):
# m/<node_id> holds the node's latest registry snapshot as JSON
METRICS_PREFIX = "m/"


def _hash64(s: str) -> int:
    return int.from_bytes(hashlib.blake2b(s.encode(), digest_size=8).digest(),
                          "big", signed=False)


def _split64(v: int) -> Tuple[int, int]:
    v &= (1 << 64) - 1
    return (v >> 32) & 0xFFFFFFFF, v & 0xFFFFFFFF


def _join64(hi: int, lo: int) -> int:
    return ((hi & 0xFFFFFFFF) << 32) | (lo & 0xFFFFFFFF)


def pack_digest(tids: Sequence[TxnId], k: int) -> np.ndarray:
    """(k, 4) int32 digest; zero rows pad.  Keeps the newest k txns."""
    rows = np.zeros((k, DIGEST_WIDTH), dtype=np.uint32)
    newest = sorted(tids)[-k:]
    for i, tid in enumerate(newest):
        ts_hi, ts_lo = _split64(tid.timestamp)
        h_hi, h_lo = _split64(_hash64(tid.encode()))
        rows[i] = (ts_hi, ts_lo, h_hi, h_lo)
    return rows.view(np.int32)


def unpack_digest(rows: np.ndarray) -> List[Tuple[int, int]]:
    """→ [(timestamp, uuid_hash64)] for non-empty rows."""
    rows = np.asarray(rows).view(np.uint32).reshape(-1, DIGEST_WIDTH)
    out = []
    for ts_hi, ts_lo, h_hi, h_lo in rows.tolist():
        if not (ts_hi | ts_lo | h_hi | h_lo):
            continue
        out.append((_join64(ts_hi, ts_lo), _join64(h_hi, h_lo)))
    return out


def digest_mesh(n: int) -> Mesh:
    """A 1-D ``nodes`` mesh over the largest number of local devices that
    divides ``n`` (one digest row block per device)."""
    ndev = len(jax.devices())
    use = 1
    for d in range(min(n, ndev), 0, -1):
        if n % d == 0:
            use = d
            break
    return jax.make_mesh((use,), ("nodes",), devices=jax.devices()[:use])


def place_digests(digests: np.ndarray, mesh: Mesh) -> jax.Array:
    """Shard the stacked digests over the ``nodes`` axis: each device holds
    its own nodes' rows, which is the all_gather's operand."""
    return jax.device_put(digests, NamedSharding(mesh, P("nodes")))


def digest_gather(mesh: Mesh):
    """The jitted collective: every device contributes its ``nodes`` shard
    and receives the gathered whole."""
    def body(shard):
        return jax.lax.all_gather(shard, "nodes", axis=0, tiled=True)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("nodes"),
                                 out_specs=P(), check_vma=False))


def exchange_digests(digests: np.ndarray,
                     mesh: Optional[Mesh] = None) -> np.ndarray:
    """All-gather node digests over the ``nodes`` mesh axis.

    ``digests``: (n_nodes, k, 4) int32, row i owned by node i.  Returns the
    same array made globally visible — on an n-device mesh each device
    contributes its shard and receives the gathered whole in one collective.
    """
    if mesh is None:
        mesh = digest_mesh(digests.shape[0])
    return np.asarray(digest_gather(mesh)(place_digests(digests, mesh)))


class DigestPlane:
    """Drives gossip rounds for an in-process set of AFT nodes."""

    def __init__(self, nodes: Sequence[AftNode], storage, *,
                 k: int = 128, mesh: Optional[Mesh] = None):
        self.nodes = list(nodes)
        self.storage = storage
        self.k = k
        self.mesh = mesh
        self._pending: Dict[str, List[TransactionRecord]] = {
            n.node_id: [] for n in self.nodes}
        # receiver node_id → {src node_id → newest gathered horizon}
        self.peer_horizons: Dict[str, Dict[str, int]] = {
            n.node_id: {} for n in self.nodes}
        self.stats = {"rounds": 0, "rows_sent": 0, "records_fetched": 0,
                      "pruned": 0, "horizons_withheld": 0,
                      "resolve_memo_hits": 0}
        for node in self.nodes:
            node.set_watermark_provider(self._floor_fn(node))

    # -- elastic membership --------------------------------------------------
    def add_node(self, node: AftNode) -> None:
        """Admit a (JOINING) member to the gossip plane: digest slot,
        horizon book-keeping, and watermark provider in one step — the
        node starts gating its own watermark on the full peer set
        immediately (fail-safe: unheard peers floor at -1)."""
        if any(n.node_id == node.node_id for n in self.nodes):
            return
        self.nodes.append(node)
        self._pending.setdefault(node.node_id, [])
        self.peer_horizons.setdefault(node.node_id, {})
        node.set_watermark_provider(self._floor_fn(node))

    def remove_node(self, node_or_id) -> None:
        """Retire a member: peers' watermark floors stop waiting on its
        horizon the moment it leaves ``self.nodes`` (the floor closure
        re-reads the list every round), and its gathered-horizon residue is
        dropped so a later rejoin starts clean."""
        node_id = getattr(node_or_id, "node_id", node_or_id)
        self.nodes = [n for n in self.nodes if n.node_id != node_id]
        self._pending.pop(node_id, None)
        self.peer_horizons.pop(node_id, None)
        for known in self.peer_horizons.values():
            known.pop(node_id, None)

    def membership_listener(self):
        """Adapter for ``AftCluster.add_membership_listener``: keeps the
        plane's peer set in step with lifecycle transitions."""
        def on_event(event: str, node: AftNode) -> None:
            if event in ("join", "live"):
                self.add_node(node)
            elif event == "retired":
                self.remove_node(node)
        return on_event

    def _floor_fn(self, node: AftNode):
        """Watermark floor for one node: min over the *currently live* other
        plane members' gathered horizons (-1 until heard from — fail-safe),
        or None when the node stands alone."""
        def floor() -> Optional[int]:
            others = [p for p in self.nodes
                      if p.node_id != node.node_id and p.alive]
            if not others:
                return None
            known = self.peer_horizons.get(node.node_id, {})
            return min(known.get(p.node_id, -1) for p in others)
        return floor

    def _resolve(self, ts: int, uuid_hash: int) -> Optional[TransactionRecord]:
        """Commit-log lookup by timestamp prefix + hash verification."""
        prefix = f"{COMMIT_PREFIX}{ts:020d}."
        for key in self.storage.list_keys(prefix):
            raw = self.storage.get(key)
            if raw is None:
                continue
            rec = TransactionRecord.decode(raw)
            if _hash64(rec.tid.encode()) == uuid_hash:
                return rec
        return None

    def step(self) -> int:
        """One gossip round.  Returns the number of records merged."""
        per_node: List[np.ndarray] = []
        # horizon BEFORE draining (mirrors MulticastAgent.step): commits
        # visible after this point either ride this round's digest or carry
        # timestamps above the horizon (in-flight commits cap it)
        horizons: Dict[str, Optional[int]] = {
            n.node_id: (n.commit_horizon_ns() if n.alive else None)
            for n in self.nodes}
        for node in self.nodes:
            fresh = self._pending[node.node_id]
            fresh.extend(node.drain_fresh_commits())
            kept = []
            for rec in fresh:
                if is_superseded(rec, node.cache):
                    self.stats["pruned"] += 1
                    continue
                kept.append(rec)
            self._pending[node.node_id] = []
            tids = [r.tid for r in kept]
            if len(kept) != len(fresh) or len(tids) > self.k:
                # the digest cannot carry every fresh commit this round
                # (§4.1 pruning or k-truncation): withhold the horizon so it
                # never claims a commit whose pointer was not exchanged
                horizons[node.node_id] = None
                self.stats["horizons_withheld"] += 1
            self.stats["rows_sent"] += len(tids)
            per_node.append(pack_digest(tids, self.k))
        if not per_node:
            return 0
        gathered = exchange_digests(np.stack(per_node), self.mesh)
        h_gathered = self._exchange_horizons(horizons)
        merged = 0
        # decode-once fan-in: every receiver resolves the same gathered
        # digest rows, so one storage lookup + record decode per (ts, hash)
        # serves all n receivers (the decoded record also seeds the
        # encode-once cache, so downstream re-fan-out reuses its bytes)
        resolved: Dict[Tuple[int, int], Optional[TransactionRecord]] = {}
        for i, node in enumerate(self.nodes):
            if not node.alive:
                continue
            for j, src in enumerate(self.nodes):
                if j == i:
                    continue
                for ts, h in unpack_digest(gathered[j]):
                    if (ts, h) in resolved:
                        rec = resolved[(ts, h)]
                        self.stats["resolve_memo_hits"] += 1
                    else:
                        rec = self._resolve(ts, h)
                        resolved[(ts, h)] = rec
                        if rec is not None:
                            self.stats["records_fetched"] += 1
                    if rec is None:
                        continue
                    merged += node.merge_remote_commits([rec])
                src_h = h_gathered.get(src.node_id)
                if src_h is not None:
                    mine = self.peer_horizons[node.node_id]
                    if src_h > mine.get(src.node_id, -1):
                        mine[src.node_id] = src_h
        self.stats["rounds"] += 1
        return merged

    def _exchange_horizons(
        self, horizons: Dict[str, Optional[int]]
    ) -> Dict[str, Optional[int]]:
        """All-gather the per-node commit horizons as one extra (1, 4) row
        per node — ``[h_hi, h_lo, 1, 0]`` (the marker keeps a legitimate
        horizon distinguishable from an all-zero withheld row)."""
        rows = np.zeros((len(self.nodes), 1, DIGEST_WIDTH), dtype=np.uint32)
        for i, node in enumerate(self.nodes):
            h = horizons.get(node.node_id)
            if h is None or h < 0:
                continue  # withheld: peers keep their last value
            h_hi, h_lo = _split64(h)
            rows[i, 0] = (h_hi, h_lo, 1, 0)
        gathered = exchange_digests(rows.view(np.int32), self.mesh)
        out: Dict[str, Optional[int]] = {}
        for j, node in enumerate(self.nodes):
            row = np.asarray(gathered[j]).view(np.uint32).reshape(-1)
            if int(row[2]) != 1:
                out[node.node_id] = None
                continue
            out[node.node_id] = _join64(int(row[0]), int(row[1]))
        return out


class MetricsPlane:
    """Gossip-fed cluster metrics aggregation (repro/obs) on the ICI.

    Rides the exact machinery of :class:`DigestPlane`: each round, every
    node publishes its registry snapshot as JSON under ``m/<node_id>`` and
    contributes one ``[seq_hi, seq_lo, hash_hi, hash_lo]`` int32 row; a
    single ``all_gather`` (``exchange_digests`` with k=1) makes every row
    globally visible.  A row is a *pointer*, not the payload — the snapshot
    blob itself travels through shared storage, and the gossiped hash
    verifies the fetched bytes (a mismatch means the publish raced the
    fetch; the row is skipped and the next round retries).  Stale rows
    (seq not newer than the last ingested) are skipped too, so a wedged
    node's frozen snapshot is ingested once, not every round.

    The merged view lands in the fault manager (``ingest_metrics``), which
    is where a cluster-wide observer already lives; ``views`` keeps the
    plane's own copy for driving code that has no fault manager.
    """

    def __init__(self, nodes: Sequence[AftNode], storage, *,
                 fault_manager=None, mesh: Optional[Mesh] = None):
        self.nodes = list(nodes)
        self.storage = storage
        self.fault_manager = fault_manager
        self.mesh = mesh
        self._seq = 0
        self._ingested_seq: Dict[str, int] = {}
        self.views: Dict[str, dict] = {}  # node_id → latest snapshot
        self.stats = {"rounds": 0, "published": 0, "ingested": 0,
                      "hash_mismatches": 0}

    # -- elastic membership --------------------------------------------------
    def add_node(self, node: AftNode) -> None:
        if any(n.node_id == node.node_id for n in self.nodes):
            return
        self.nodes.append(node)

    def remove_node(self, node_or_id) -> None:
        node_id = getattr(node_or_id, "node_id", node_or_id)
        self.nodes = [n for n in self.nodes if n.node_id != node_id]
        self._ingested_seq.pop(node_id, None)
        self.views.pop(node_id, None)

    def membership_listener(self):
        """Adapter for ``AftCluster.add_membership_listener``: a retired
        node's last snapshot leaves the merged view at once, so autoscaler
        signals never average in a gone member."""
        def on_event(event: str, node: AftNode) -> None:
            if event in ("join", "live"):
                self.add_node(node)
            elif event == "retired":
                self.remove_node(node)
        return on_event

    def _publish(self, node: AftNode) -> Tuple[int, int]:
        """Write the node's snapshot blob; returns (seq, hash64)."""
        snap = node.registry.snapshot()
        blob = json.dumps(snap, sort_keys=True, default=str).encode()
        self.storage.put(f"{METRICS_PREFIX}{node.node_id}", blob)
        self.stats["published"] += 1
        return self._seq, _hash64(blob.decode())

    def step(self) -> int:
        """One gossip round.  Returns the number of snapshots ingested."""
        self._seq += 1
        rows = np.zeros((len(self.nodes), 1, DIGEST_WIDTH), dtype=np.uint32)
        for i, node in enumerate(self.nodes):
            if not node.alive:
                continue  # zero row: peers skip it, like an empty digest
            seq, h = self._publish(node)
            s_hi, s_lo = _split64(seq)
            h_hi, h_lo = _split64(h)
            rows[i, 0] = (s_hi, s_lo, h_hi, h_lo)
        gathered = exchange_digests(rows.view(np.int32), self.mesh)
        ingested = 0
        fresh: Dict[str, dict] = {}
        for j, node in enumerate(self.nodes):
            for seq, h in unpack_digest(gathered[j]):
                if seq <= self._ingested_seq.get(node.node_id, 0):
                    continue
                raw = self.storage.get(f"{METRICS_PREFIX}{node.node_id}")
                if raw is None or _hash64(raw.decode()) != h:
                    self.stats["hash_mismatches"] += raw is not None
                    continue
                snap = json.loads(raw)
                self._ingested_seq[node.node_id] = seq
                self.views[node.node_id] = snap
                fresh[node.node_id] = snap
                ingested += 1
        if fresh and self.fault_manager is not None:
            self.fault_manager.ingest_metrics(fresh)
        self.stats["rounds"] += 1
        self.stats["ingested"] += ingested
        return ingested
