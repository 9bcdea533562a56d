"""The inference serving lane: requests as read-only AFT workflows.

Covers session placement stickiness, the shard codec round-trip (including
torn-set detection), atomic publish → snapshot-probed poll → monotonic
install, and re-routing after a replica's node dies."""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import AftCluster, ClusterConfig  # noqa: E402
from repro.faas.platform import FaasConfig, LambdaPlatform  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.models.config import get_config  # noqa: E402
from repro.serve.engine import ContinuousEngine, ServeConfig  # noqa: E402
from repro.serve.lane import (  # noqa: E402
    InferenceLane,
    LaneConfig,
    TornWeightSet,
    params_to_shards,
    shards_to_params,
)
from repro.storage.memory import MemoryStorage  # noqa: E402
from repro.workflow import PoolConfig, TxnScope, WorkflowPool  # noqa: E402


# --------------------------------------------------------------- shard codec

def small_tree():
    return {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": {"w": np.ones((4,), np.float32),
                  "s": np.asarray(2.5, np.float32)}}


def test_shard_roundtrip():
    tree = small_tree()
    blobs = params_to_shards(tree, step=9, shards=2)
    assert sorted(blobs) == ["part0", "part1"]
    out, step = shards_to_params(blobs, tree)
    assert step == 9
    for want, got in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_shard_torn_step_detected():
    tree = small_tree()
    a = params_to_shards(tree, step=1, shards=2)
    b = params_to_shards(tree, step=2, shards=2)
    torn = {"part0": a["part0"], "part1": b["part1"]}
    with pytest.raises(TornWeightSet):
        shards_to_params(torn, tree)


def test_shard_missing_leaves_detected():
    tree = small_tree()
    blobs = params_to_shards(tree, step=1, shards=2)
    with pytest.raises(TornWeightSet):
        shards_to_params({"part0": blobs["part0"]}, tree)


# ------------------------------------------------------------------ the lane

@pytest.fixture(scope="module")
def model_and_params():
    cfg = dataclasses.replace(
        get_config("tinyllama-1.1b").reduced(pattern_repeats=2),
        kv_cache_dtype="float32")
    model = Model(cfg)
    return model, model.init_params(jax.random.key(0))


@pytest.fixture()
def lane_setup(model_and_params):
    model, params = model_and_params
    cluster = AftCluster(
        MemoryStorage(),
        ClusterConfig(num_nodes=2, start_background_threads=False,
                      routing="consistent_hash"))
    platform = LambdaPlatform(FaasConfig(time_scale=0.0))
    pool = WorkflowPool(platform, cluster=cluster,
                        config=PoolConfig(scope=TxnScope.STEP,
                                          max_attempts=8))
    scfg = ServeConfig(max_len=48, slots=4, prefill_chunk=4)
    replicas = {n.node_id: ContinuousEngine(model, None, scfg,
                                            name=f"rep-{n.node_id}")
                for n in cluster.live_nodes()}
    lane = InferenceLane(pool, cluster, replicas,
                         config=LaneConfig(run_id="t"))
    yield model, params, cluster, pool, platform, replicas, lane
    lane.stop()
    pool.close()
    platform.shutdown()


def install_all(lane, cluster, replicas, params, step):
    lane.publish(params, step)
    cluster.step_all()  # propagate commit metadata without gossip threads
    lane.poll_weights()
    assert all(e.weights_step == step for e in replicas.values())


def test_publish_poll_install_and_serve(lane_setup):
    model, params, cluster, pool, platform, replicas, lane = lane_setup
    install_all(lane, cluster, replicas, params, 1)
    for eng in replicas.values():
        eng.start()

    tickets = [lane.submit(f"s{i % 2}", [1 + i, 2, 3], max_new=3)
               for i in range(6)]
    results = [InferenceLane.payload(t.result(timeout=60)) for t in tickets]
    assert all(len(r["tokens"]) == 3 for r in results)
    assert all(r["weights_step"] == 1 for r in results)
    # session stickiness: every request of a session served by ONE node
    by_session = {}
    for i, r in enumerate(results):
        by_session.setdefault(i % 2, set()).add(r["node"])
    assert all(len(nodes) == 1 for nodes in by_session.values())
    assert lane.stats["torn_reads"] == 0
    assert lane.stats["completed"] == 6


def test_refresh_under_traffic_and_snapshot_skip(lane_setup):
    model, params, cluster, pool, platform, replicas, lane = lane_setup
    install_all(lane, cluster, replicas, params, 1)
    for eng in replicas.values():
        eng.start()

    params2 = jax.tree.map(lambda x: x * 1.01, params)
    install_all(lane, cluster, replicas, params2, 2)
    # replicas already current → the snapshot probe skips the read txn
    before = lane.stats["snapshot_skips"]
    assert not lane.poll_weights()
    assert lane.stats["snapshot_skips"] > before

    r = InferenceLane.payload(
        lane.submit("s0", [9, 9, 9], max_new=2).result(timeout=60))
    assert r["weights_step"] == 2
    assert r["manifest_step"] == 2
    assert lane.stats["torn_reads"] == 0


def test_answers_carry_engine_stamps(lane_setup):
    """Each answer carries its engine ticket's stamps, in order, all taken
    after the lane accepted the request."""
    import time

    model, params, cluster, pool, platform, replicas, lane = lane_setup
    install_all(lane, cluster, replicas, params, 1)
    for eng in replicas.values():
        eng.start()
    sent = time.perf_counter()
    tickets = [lane.submit(f"s{i}", [1 + i] * (2 + 3 * i), max_new=3)
               for i in range(4)]
    for t in tickets:
        st = InferenceLane.payload(t.result(timeout=60))["stamps"]
        assert sent <= st["submitted_at"] <= st["admitted_at"] \
            <= st["first_token_at"] <= st["finished_at"]


def test_kill_reroutes_to_live_replica(lane_setup):
    model, params, cluster, pool, platform, replicas, lane = lane_setup
    install_all(lane, cluster, replicas, params, 1)
    for eng in replicas.values():
        eng.start()

    victim = cluster.live_nodes()[0]
    cluster.kill_node(0)
    lane.detach(victim.node_id)
    survivor = cluster.live_nodes()[0].node_id

    results = [InferenceLane.payload(
        lane.submit(f"s{i}", [3 + i, 4, 5], max_new=2).result(timeout=60))
        for i in range(4)]
    assert all(r["node"] == survivor for r in results)
    assert all(len(r["tokens"]) == 2 for r in results)


def test_tokenize_step_string_prompts(lane_setup):
    model, params, cluster, pool, platform, replicas, lane = lane_setup
    install_all(lane, cluster, replicas, params, 1)
    for eng in replicas.values():
        eng.start()
    r = InferenceLane.payload(
        lane.submit("s0", "hi there", max_new=2).result(timeout=60))
    assert len(r["tokens"]) == 2  # tokenizer step mapped str → token ids


def test_lane_refresher_counts_errors(lane_setup, monkeypatch):
    """A poll that raises is retried next round, and counted."""
    import time

    model, params, cluster, pool, platform, replicas, lane = lane_setup

    def broken_poll():
        raise OSError("storage unreachable")

    monkeypatch.setattr(lane, "poll_weights", broken_poll)
    lane.config.poll_every_s = 0.01
    lane.start_refresher()
    deadline = time.monotonic() + 30
    while lane.stats["refresh_errors"] < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert lane.stats["refresh_errors"] >= 2
    assert isinstance(lane.refresh_error, OSError)
