"""fig_workflow: DAG-composed requests — AFT-scoped vs. unscoped execution.

A fan-out-8/fan-in workflow (every branch read-modify-writes its own key,
the fan-in summarizes all branches) runs as a closed-loop stream under an
injected mid-branch crash rate ≥ 5%, in two modes:

* **aft** — the whole DAG is one AFT transaction (``TxnScope.WORKFLOW``)
  with memoized per-step resume; crashes retry the workflow under the same
  UUID and commit exactly once.
* **unscoped** — the baseline without the shim: branches write in place,
  immediately visible, with §6.1.2 metadata embedded; a crash leaves a
  fractured prefix and a retry re-applies effects.

A concurrent **auditor** plays the Table-2 role for DAGs: each audit reads
the summary plus every branch key as one observation and scores it with the
Definition-1 checker.  Exactly-once is scored at the end: every branch
counter must equal the number of completed workflows (each workflow
increments each branch exactly once, no matter how many attempts it took).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List

from repro.core import AftNode, AftNodeConfig, TransactionObserver
from repro.core.errors import ReadAbortError
from repro.core.records import extract_metadata
from repro.faas.platform import FaasConfig, LambdaPlatform
from repro.obs import trace as obs_trace
from repro.obs.checker import check_events
from repro.workflow import (
    TxnScope,
    WorkflowConfig,
    WorkflowError,
    WorkflowExecutor,
    WorkflowSpec,
)

from .common import QUICK_TIME_SCALE, engine, make_cluster, save

BRANCHES = 8
FAILURE_RATE = 0.08          # ≥ 5% per failure point, two points per branch


def branch_keys() -> List[str]:
    return [f"wf/branch{i}" for i in range(BRANCHES)]


def build_spec(epoch: int) -> WorkflowSpec:
    spec = WorkflowSpec(f"fanout{BRANCHES}")

    def branch_fn(ctx) -> int:
        key = f"wf/branch{ctx.branch}"
        raw = ctx.get(key)
        count = json.loads(raw)["count"] if raw else 0
        ctx.maybe_fail()  # the mid-branch fractional-execution hazard
        ctx.put(key, json.dumps({"count": count + 1, "epoch": epoch}).encode())
        return count + 1

    names = spec.fan_out("branch", branch_fn, BRANCHES)

    def summarize(ctx) -> int:
        counts = [ctx.inputs[n] for n in names]
        ctx.maybe_fail()
        ctx.put(
            "wf/summary",
            json.dumps({"epoch": epoch, "counts": counts}).encode(),
        )
        return sum(counts)

    spec.fan_in("summary", summarize, names, allow_skipped_deps=False)
    return spec


class Auditor:
    """Reads summary + all branch keys as ONE observation, repeatedly,
    concurrent with the workflow stream; scores with Definition 1."""

    def __init__(self, mode: str, *, cluster=None, storage=None):
        self.mode = mode
        self.cluster = cluster
        self.storage = storage
        self.audits = 0
        self.fr_anomalies = 0
        self.read_aborts = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _audit_aft(self) -> None:
        node = self.cluster.pick_node()
        obs = TransactionObserver()
        tx = node.start_transaction()
        try:
            for key in ["wf/summary"] + branch_keys():
                value, tid = node.get_versioned(tx, key)
                cowritten = ()
                if tid is not None:
                    record = node.cache.get(tid)
                    if record is not None:
                        cowritten = record.write_set
                obs.observe_read(key, value, tid, cowritten)
        finally:
            node.abort_transaction(tx)
            node.release_transaction(tx)
        self.fr_anomalies += obs.fr_anomalies

    def _audit_plain(self) -> None:
        obs = TransactionObserver()
        for key in ["wf/summary"] + branch_keys():
            raw = self.storage.get(key)
            if raw is None:
                obs.observe_read(key, None, None)
                continue
            value, tid, cowritten = extract_metadata(raw)
            obs.observe_read(key, value, tid, cowritten)
        self.fr_anomalies += obs.fr_anomalies

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                if self.mode == "aft":
                    self._audit_aft()
                else:
                    self._audit_plain()
                self.audits += 1
            except ReadAbortError:
                self.read_aborts += 1  # §3.6 staleness abort, not an anomaly
            except Exception:
                pass  # cluster mid-teardown
            time.sleep(0.001)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)


def _final_counts(storage) -> Dict[str, int]:
    """Read committed branch counters from the durable source of truth: a
    fresh node bootstrapped from the Commit Set (so no multicast races)."""
    node = AftNode(storage, AftNodeConfig(node_id="final-audit"))
    counts: Dict[str, int] = {}
    tx = node.start_transaction()
    for key in branch_keys():
        raw = node.get(tx, key)
        counts[key] = json.loads(raw)["count"] if raw else 0
    node.abort_transaction(tx)
    return counts


def _final_counts_plain(storage) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for key in branch_keys():
        raw = storage.get(key)
        if raw is None:
            counts[key] = 0
        else:
            value, _, _ = extract_metadata(raw)
            counts[key] = json.loads(value)["count"]
    return counts


def _run_mode(mode: str, workflows: int, ts: float, seed: int) -> Dict:
    store = engine("dynamodb", ts, seed=seed)
    platform = LambdaPlatform(
        FaasConfig(time_scale=ts, failure_rate=FAILURE_RATE,
                   max_workers=32, seed=seed)
    )
    cluster = None
    if mode == "aft":
        # one node: the workflow stream is a chain of read-modify-writes, and
        # AFT guarantees read atomicity, not serializability — cross-node
        # commit visibility is only eventual (multicast, §4), so the counter
        # chain pins to a single node exactly as §3.1 pins a transaction
        cluster = make_cluster(store, nodes=1, time_scale=ts)
        executor = WorkflowExecutor(
            platform, cluster=cluster,
            config=WorkflowConfig(scope=TxnScope.WORKFLOW, max_attempts=25),
        )
    else:
        executor = WorkflowExecutor(
            platform, storage=store,
            config=WorkflowConfig(
                scope=TxnScope.NONE, max_attempts=25,
                declared_writes=tuple(branch_keys()) + ("wf/summary",),
            ),
        )
    auditor = Auditor(mode, cluster=cluster, storage=store)
    auditor.start()

    completed = 0
    attempts = 0
    failed = 0
    t0 = time.perf_counter()
    for epoch in range(workflows):
        try:
            result = executor.run(build_spec(epoch))
            completed += 1
            attempts += result.attempts
        except WorkflowError:
            failed += 1
    wall = time.perf_counter() - t0
    auditor.stop()

    counts = _final_counts(store) if mode == "aft" else _final_counts_plain(store)
    # exactly-once: each completed workflow increments each branch once
    violations = sum(abs(c - completed) for c in counts.values())

    out = {
        "mode": mode,
        "workflows_completed": completed,
        "workflows_failed": failed,
        "attempts": attempts,
        "workflow_retries": executor.stats["workflow_retries"],
        "steps_memoized": executor.stats["steps_memoized"],
        "failures_injected": platform.failures_injected,
        "wall_s": round(wall, 2),
        "workflows_per_s": round(completed / wall, 2) if wall > 0 else 0.0,
        "audits": auditor.audits,
        "audit_read_aborts": auditor.read_aborts,
        "fr_anomalies": auditor.fr_anomalies,
        "exactly_once_violations": violations,
        "branch_counts": counts,
    }
    if cluster is not None:
        # cluster-merged metrics view: gossip the per-node registry
        # snapshots through the ICI plane when jax is installed, else take
        # the fault manager's direct in-process path — same merged view.
        # A failed device round raises: it is not replaced in silence.
        fm = cluster.fault_manager
        try:
            from repro.core.gossip import MetricsPlane
        except ImportError:
            fm.collect_metrics()
        else:
            MetricsPlane(cluster.live_nodes(), store, fault_manager=fm).step()
        out["obs"] = fm.cluster_metrics()
    platform.shutdown()
    if cluster is not None:
        cluster.stop()
    return out


def run(quick: bool = True) -> Dict:
    ts = QUICK_TIME_SCALE
    workflows = 30 if quick else 120
    # tracing on for the aft stream: REPRO_TRACE_FILE adds the file sink
    # (the CI obs-check hook replays it); otherwise the ring buffer alone
    # feeds the offline checker below
    prev_tracer = obs_trace.get_tracer()
    tracer = obs_trace.enable(
        path=os.environ.get(obs_trace.TRACE_FILE_ENV), capacity=500_000
    )
    try:
        aft = _run_mode("aft", workflows, ts, seed=11)
    finally:
        obs_trace.set_tracer(prev_tracer)
        tracer.close()
    unscoped = _run_mode("unscoped", workflows, ts, seed=11)

    checked = check_events(tracer.events())
    aft["trace_events"] = len(tracer.events())
    aft["trace_violations"] = len(checked.violations)
    save("obs_metrics", {
        **aft.pop("obs", {"nodes": {}, "cluster": {}}),
        "trace": {"events": aft["trace_events"],
                  "violations": aft["trace_violations"],
                  "summary": checked.summary()},
    })
    out = {
        "branches": BRANCHES,
        "failure_rate": FAILURE_RATE,
        "workflows": workflows,
        "aft": aft,
        "unscoped": unscoped,
        "headline": {
            "aft_anomalies": aft["fr_anomalies"] + aft["exactly_once_violations"],
            "unscoped_anomalies": unscoped["fr_anomalies"]
            + unscoped["exactly_once_violations"],
            "aft_exactly_once": aft["exactly_once_violations"] == 0,
            "trace_violations": aft["trace_violations"],
        },
    }
    save("fig_workflow", out)
    return out


if __name__ == "__main__":
    print(json.dumps(run(), indent=1))
