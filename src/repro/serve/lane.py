"""Inference as a first-class AFT workload: the serving lane.

Each request is a workflow — ``tokenize → generate`` — driven through a
``WorkflowPool`` on the read-only fast lane (``TxnScope.STEP`` +
``read_only=True``): no memo writes, no commit, just read-atomic reads.
Session affinity comes from placement: both steps declare the session key
in ``Step.reads``, so the session's ``PlacementHint`` pins every request
of a session to one node, where ``StepContext.placed_node`` resolves the
node-local model replica (a ``ContinuousEngine``).  A consistent-hash or
cache-aware router therefore keeps a session's KV/weight locality without
any serving-specific routing code.  When a node dies mid-request the step
raises, the pool re-drives the workflow, and the fresh session routes to a
live replica — read-only re-execution is always safe.

Weights flow through AFT end to end:

* ``params_to_shards`` / ``shards_to_params`` pack a jax parameter tree
  into N byte shards (each embeds the publishing step, so torn assemblies
  are detectable even if isolation were broken);
* ``publish_params`` runs ``serve/refresh.py``'s fan-out/fan-in publish
  DAG — one ``TxnScope.WORKFLOW`` transaction, all-or-nothing under
  crashes, exactly-once on re-drive (UUID = ``publish.{run_id}.{step}``);
* ``read_params`` assembles the latest set in ONE read transaction
  (read-atomic ⇒ never torn) and raises ``TornWeightSet`` if the embedded
  shard steps disagree anyway — the benchmark's torn-read audit;
* ``InferenceLane.poll_weights`` probes the manifest with a
  bounded-staleness ``snapshot_read`` first (no transaction, answered from
  the gossip-fed watermark cache) and only pays the full read transaction
  when the snapshot shows — or cannot rule out — a newer step, then swaps
  every replica via ``install_weights`` (which spans the swap with the
  publish UUID for the offline checker).
"""

from __future__ import annotations

import json
import struct
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Mapping, Optional, Tuple

import jax

from ..checkpoint.serializer import leaf_from_bytes, leaf_to_bytes, tree_paths
from ..core import SnapshotUnavailable
from ..obs.registry import Registry
from ..obs.trace import region
from ..workflow import WorkflowSpec
from .refresh import (
    build_publish_workflow,
    manifest_key,
    publish_uuid,
    read_weight_set,
)


class TornWeightSet(RuntimeError):
    """Assembled weight shards disagree on their publishing step — a torn
    read.  Read-atomic isolation makes this unreachable through AFT; the
    class exists so audits can count it reaching zero."""


# ---------------------------------------------------------------------------
# parameter tree ↔ byte shards
# ---------------------------------------------------------------------------

def _pack_shard(step: int, items: List[Tuple[str, Any]]) -> bytes:
    parts = [struct.pack("<II", step, len(items))]
    for path, leaf in items:
        blob = leaf_to_bytes(leaf)
        enc = path.encode("utf-8")
        parts.append(struct.pack("<I", len(enc)))
        parts.append(enc)
        parts.append(struct.pack("<I", len(blob)))
        parts.append(blob)
    return b"".join(parts)


def _unpack_shard(raw: bytes) -> Tuple[int, Dict[str, Any]]:
    step, count = struct.unpack_from("<II", raw, 0)
    off = 8
    leaves: Dict[str, Any] = {}
    for _ in range(count):
        (plen,) = struct.unpack_from("<I", raw, off)
        off += 4
        path = raw[off:off + plen].decode("utf-8")
        off += plen
        (blen,) = struct.unpack_from("<I", raw, off)
        off += 4
        leaves[path] = leaf_from_bytes(raw[off:off + blen])
        off += blen
    return step, leaves


def params_to_shards(params: Any, *, step: int,
                     shards: int = 4) -> Dict[str, bytes]:
    """Round-robin the tree's leaves into ``shards`` named byte blobs.
    Every blob embeds ``step`` so a torn assembly is self-evident."""
    pairs = tree_paths(params)
    buckets: List[List[Tuple[str, Any]]] = [[] for _ in range(shards)]
    for i, pair in enumerate(pairs):
        buckets[i % shards].append(pair)
    return {f"part{i}": _pack_shard(step, bucket)
            for i, bucket in enumerate(buckets)}


def shards_to_params(blobs: Mapping[str, bytes], like: Any) -> Tuple[Any, int]:
    """Reassemble a parameter tree shaped like ``like``.  Raises
    ``TornWeightSet`` when shard headers disagree on the publishing step."""
    steps = set()
    leaves: Dict[str, Any] = {}
    for name in sorted(blobs):
        step, part = _unpack_shard(blobs[name])
        steps.add(step)
        leaves.update(part)
    if len(steps) != 1:
        raise TornWeightSet(f"shard steps disagree: {sorted(steps)}")
    paths = tree_paths(like)
    missing = [p for p, _ in paths if p not in leaves]
    if missing:
        raise TornWeightSet(f"weight set missing leaves: {missing[:4]}")
    treedef = jax.tree_util.tree_structure(like)
    flat = [leaves[p] for p, _ in paths]
    return jax.tree_util.tree_unflatten(treedef, flat), steps.pop()


# ---------------------------------------------------------------------------
# publish / read through AFT
# ---------------------------------------------------------------------------

def publish_params(driver, params: Any, *, run_id: str, step: int,
                   shards: int = 4, prefix: str = "weights"):
    """Publish a parameter tree through the atomic publish DAG.  ``driver``
    is a ``WorkflowExecutor`` (``run``) or ``WorkflowPool`` (``submit`` —
    returns the ticket; the publish commits when it resolves)."""
    blobs = params_to_shards(params, step=step, shards=shards)
    spec = build_publish_workflow(
        sorted(blobs), lambda name, _step: blobs[name],
        run_id=run_id, step=step, prefix=prefix)
    uuid = publish_uuid(run_id, step)
    if hasattr(driver, "run"):
        return driver.run(spec, uuid=uuid)
    return driver.submit(spec, uuid=uuid)


def read_params(client, like: Any, *, run_id: str,
                prefix: str = "weights") -> Optional[Tuple[int, Any]]:
    """Read-atomically assemble the latest published parameter tree.
    Returns ``(step, params)`` or None when nothing is published; raises
    ``TornWeightSet`` if the embedded shard steps disagree with each other
    or with the manifest (impossible through AFT — the audit hook)."""
    got = read_weight_set(client, run_id=run_id, prefix=prefix)
    if got is None:
        return None
    manifest_step, blobs = got
    params, embedded_step = shards_to_params(blobs, like)
    if embedded_step != manifest_step:
        raise TornWeightSet(
            f"manifest step {manifest_step} != shard step {embedded_step}")
    return manifest_step, params


# ---------------------------------------------------------------------------
# the lane
# ---------------------------------------------------------------------------

@dataclass
class LaneConfig:
    run_id: str = "serve"
    prefix: str = "weights"
    max_new_default: int = 16
    request_timeout_s: float = 120.0
    poll_every_s: float = 0.25        # replica weight-refresh cadence
    snapshot_probe: bool = True       # probe manifest via snapshot_read
    snapshot_staleness_s: float = 30.0


class InferenceLane:
    """Routes inference requests as read-only workflows over per-node
    model replicas, and keeps every replica's weights fresh through AFT.

    ``replicas`` maps node id → engine (anything with ``submit`` /
    ``install_weights`` / ``weights_step`` — in practice a
    ``ContinuousEngine``).  The caller owns engine lifecycles but
    ``lane.stop()`` stops them for convenience; ``detach`` drops a
    replica whose node was killed (in-flight requests re-route via the
    pool's retry, because a missing replica makes the step raise)."""

    def __init__(self, pool, cluster, replicas: Mapping[str, Any], *,
                 config: Optional[LaneConfig] = None, like: Any = None,
                 registry: Optional[Registry] = None):
        self.pool = pool
        self.cluster = cluster
        self.replicas: Dict[str, Any] = dict(replicas)
        self.config = config or LaneConfig()
        if like is None:
            engine = next(iter(self.replicas.values()))
            like = engine.model.abstract_params()
        self.like = like
        self.registry = registry or Registry(name="serve-lane")
        self.stats = {"requests": 0, "completed": 0, "rerouted": 0,
                      "torn_reads": 0, "refresh_polls": 0,
                      "refresh_installs": 0, "snapshot_skips": 0,
                      "refresh_errors": 0}
        self.refresh_error: Optional[BaseException] = None
        self.registry.attach_counters(self.stats, "lane.")
        self._h_request = self.registry.histogram("lane.request.wall")
        self._stop = threading.Event()
        self._poller: Optional[threading.Thread] = None

    # ------------------------------------------------------------- requests
    @staticmethod
    def session_key(session_id) -> str:
        return f"serve/session/{session_id}"

    def spec_for(self, session_id, prompt, max_new: int) -> WorkflowSpec:
        """Build the request workflow.  Both steps read the session key
        first, so ``declared_reads()`` leads with it and the placement hint
        pins the whole request (and every request of the session) to the
        session's node."""
        cfg = self.config
        skey = self.session_key(session_id)
        mkey = manifest_key(cfg.prefix, cfg.run_id)

        def tokenize(ctx):
            ctx.maybe_fail()
            p = ctx.args["prompt"]
            with region("aft.lane.tokenize"):
                if isinstance(p, str):
                    return [1 + (b % 250) for b in p.encode("utf-8")]
                return [int(t) for t in p]

        def generate(ctx):
            node = ctx.placed_node
            engine = self.replicas.get(node)
            if engine is None:
                # node died (or carries no replica): raising sends the
                # workflow back through the pool, which re-routes it
                self.stats["rerouted"] += 1
                raise RuntimeError(f"no model replica on node {node!r}")
            with region("aft.lane.read"):
                raw = ctx.get(mkey)  # read-atomic freshness marker
            manifest_step = json.loads(raw)["step"] if raw is not None else None
            with region("aft.lane.submit"):
                ticket = engine.submit(ctx.inputs["tokenize"],
                                       ctx.args["max_new"])
            tokens = ticket.result(timeout=cfg.request_timeout_s)
            return {"tokens": tokens, "node": node,
                    "weights_step": engine.weights_step,
                    "manifest_step": manifest_step,
                    # the engine's perf_counter stamps of this request
                    "stamps": {"submitted_at": ticket.submitted_at,
                               "admitted_at": ticket.admitted_at,
                               "first_token_at": ticket.first_token_at,
                               "finished_at": ticket.finished_at}}

        spec = WorkflowSpec(f"infer-{session_id}")
        spec.step("tokenize", tokenize, reads=(skey,), read_only=True)
        spec.step("generate", generate, deps=("tokenize",),
                  reads=(skey, mkey), read_only=True)
        return spec

    def submit(self, session_id, prompt, *, max_new: Optional[int] = None,
               uuid: Optional[str] = None):
        """Submit one request; returns the pool ticket.  ``ticket.result()``
        is the usual ``WorkflowResult`` — the generate step's payload dict
        lives at ``result.results["generate"]`` (see :func:`payload`)."""
        cfg = self.config
        self.stats["requests"] += 1
        t0 = time.perf_counter()
        spec = self.spec_for(session_id, prompt,
                             max_new or cfg.max_new_default)
        ticket = self.pool.submit(
            spec, uuid=uuid,
            args={"prompt": prompt, "max_new": max_new or cfg.max_new_default})

        def _done(_):
            self._h_request.observe_s(time.perf_counter() - t0)
            self.stats["completed"] += 1

        ticket.add_done_callback(_done)
        return ticket

    @staticmethod
    def payload(result) -> Dict[str, Any]:
        """The generate step's payload from a resolved request ticket:
        ``tokens``, ``node``, ``weights_step``, ``manifest_step`` and the
        engine ticket's ``stamps`` (``submitted_at``, ``admitted_at``,
        ``first_token_at``, ``finished_at``; ``time.perf_counter``)."""
        return result.results["generate"]

    # -------------------------------------------------------------- weights
    def publish(self, params: Any, step: int, *, driver=None, shards: int = 4):
        """Publish a new weight set (atomic, exactly-once).  Uses ``driver``
        when given (a WORKFLOW-scoped executor or pool — the request pool's
        STEP scope would tear the publish into per-shard transactions)."""
        if driver is None:
            driver = self._publisher()
        return publish_params(driver, params, run_id=self.config.run_id,
                              step=step, shards=shards,
                              prefix=self.config.prefix)

    def _publisher(self):
        from ..workflow import TxnScope, WorkflowConfig, WorkflowExecutor
        return WorkflowExecutor(
            self.pool.platform, cluster=self.cluster,
            config=WorkflowConfig(scope=TxnScope.WORKFLOW, max_attempts=8))

    def poll_weights(self) -> bool:
        """One refresh round over every replica: snapshot-probe the
        manifest, and when a newer step is (or may be) out there, read the
        set atomically and swap.  Returns True if any replica swapped."""
        cfg = self.config
        self.stats["refresh_polls"] += 1
        client = self.cluster.client()
        mkey = manifest_key(cfg.prefix, cfg.run_id)
        installed = False
        for node_id, engine in list(self.replicas.items()):
            if cfg.snapshot_probe:
                try:
                    snap = client.snapshot_read(mkey, cfg.snapshot_staleness_s)
                    if (snap.value is not None
                            and json.loads(snap.value)["step"]
                            <= engine.weights_step):
                        # the watermark already covers a step we have —
                        # skip the read transaction entirely
                        self.stats["snapshot_skips"] += 1
                        continue
                except SnapshotUnavailable:
                    pass  # gossip lag: fall through to the full read
            try:
                got = read_params(client, self.like, run_id=cfg.run_id,
                                  prefix=cfg.prefix)
            except TornWeightSet:
                self.stats["torn_reads"] += 1
                continue
            if got is None:
                continue
            step, params = got
            if engine.install_weights(
                    params, step,
                    publish_uuid=publish_uuid(cfg.run_id, step)):
                self.stats["refresh_installs"] += 1
                installed = True
        return installed

    def start_refresher(self) -> None:
        def loop():
            while not self._stop.wait(self.config.poll_every_s):
                try:
                    self.poll_weights()
                except Exception as exc:  # retried next round, but counted
                    self.refresh_error = exc
                    self.stats["refresh_errors"] += 1

        self._poller = threading.Thread(target=loop, daemon=True,
                                        name="lane-refresher")
        self._poller.start()

    # ------------------------------------------------------------ lifecycle
    def detach(self, node_id: str):
        """Drop (and stop) the replica on a dead node; in-flight requests
        routed there fail fast and re-route through the pool."""
        engine = self.replicas.pop(node_id, None)
        if engine is not None:
            engine.stop()
        return engine

    def stop(self) -> None:
        self._stop.set()
        if self._poller is not None:
            self._poller.join(timeout=5)
            self._poller = None
        for engine in self.replicas.values():
            engine.stop()
