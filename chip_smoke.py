#!/usr/bin/env python3
"""Chip smoke: the AFT serving lane at full tinyllama-1.1b width on one TPU.

    python chip_smoke.py              # one chip: lane, digest plane, kernels
    python chip_smoke.py --chips 4    # four chips: digest + metrics planes only

The one-chip run, all in this one process:

1. refuses to run unless ``jax.devices()[0]`` is a TPU;
2. builds a 2-node ``AftCluster`` over ``MemoryStorage``, a ``WorkflowPool``
   and one ``ContinuousEngine`` replica per node, wired into an
   ``InferenceLane`` (as ``benchmarks/fig_serve.run_lane`` wires them);
3. initialises random tinyllama-1.1b weights on the chip from ``--seed``,
   publishes them through the lane (one WORKFLOW-scope AFT transaction) and
   installs them on every replica;
4. serves requests over several sessions and publishes a second weight step
   mid-stream;
5. teacher-forces every finished request through ``Model.forward`` on the
   weights that served it;
6. runs one round of the commit-digest plane (``core/gossip.py``);
7. runs ``flash_attention`` and ``ssd_scan`` compiled for the chip
   (``interpret=False``) against their references.

The ``--chips 4`` run puts one AFT node on each of four chips and runs one
``DigestPlane`` and one ``MetricsPlane`` round over the 4-device ``nodes``
mesh, and nothing else.

Times printed here are smoke timings of one run, not benchmark results.
Any failed check, exception or timeout exits non-zero; only a run whose
every check passed prints the final JSON line.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import resource
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core import AftCluster, ClusterConfig, gossip  # noqa: E402
from repro.faas.platform import FaasConfig, LambdaPlatform  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.ref import attention_ref, ssd_scan_ref  # noqa: E402
from repro.kernels.ssd_scan import ssd_scan  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.models.config import get_config  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.obs.checker import check_events  # noqa: E402
from repro.serve.engine import ContinuousEngine, ServeConfig  # noqa: E402
from repro.serve.lane import InferenceLane, LaneConfig  # noqa: E402
from repro.storage.memory import MemoryStorage  # noqa: E402
from repro.workflow import PoolConfig, TxnScope, WorkflowPool  # noqa: E402

TIMEOUT_S = 1100          # the whole run, compilation included
ARCH = "tinyllama-1.1b"
PROMPT_LENS = (16, 200)   # inclusive range of prompt tokens
MAX_NEWS = (16, 64)       # inclusive range of tokens to generate


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    print(f"  ok: {what}", flush=True)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# reference check
# ---------------------------------------------------------------------------

def bf16_margin(max_logit):
    """Three bfloat16 ulps at the magnitude of the reference's top logit:
    the engine's cached decode and the reference's full-sequence forward
    round in different orders, and the logits leave the last matmul as
    bfloat16."""
    mag = np.maximum(np.abs(max_logit), 1.0)
    return 3.0 * np.exp2(np.floor(np.log2(mag)) - 7)


def reference_scores(model, params, seqs, pad_to: int):
    """Teacher-force each (prompt, generated) pair through
    ``Model.forward``.  Returns per request, for each generated position,
    (reference argmax, reference max logit, reference logit of the token
    the engine emitted).  Padding to one length keeps it one compile."""
    toks = np.zeros((len(seqs), pad_to), np.int32)
    tgt = np.zeros((len(seqs), pad_to), np.int32)
    for r, (prompt, gen) in enumerate(seqs):
        full = list(prompt) + list(gen)
        toks[r, :len(full) - 1] = full[:-1]
        tgt[r, :len(full) - 1] = full[1:]

    @jax.jit
    def score(params, toks, tgt):
        logits, _ = model.forward(params, toks)
        return (jnp.argmax(logits, -1), jnp.max(logits, -1),
                jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0])

    am, mx, lt = (np.asarray(a) for a in score(params, toks, tgt))
    out = []
    for r, (prompt, gen) in enumerate(seqs):
        sl = slice(len(prompt) - 1, len(prompt) - 1 + len(gen))
        out.append((am[r, sl], mx[r, sl], lt[r, sl]))
    return out


def positions_ok(gen, scores):
    am, mx, lt = scores
    gen = np.asarray(gen)
    exact = am == gen
    return exact | (lt >= mx - bf16_margin(mx)), exact


def request_ok(gen, s1, s2, served_by):
    """``served_by`` is 1 or 2 when every token of the request came from
    that weight step, or None when the request was in flight across the
    swap: then some prefix must match step 1 and the rest step 2 (weights
    change only between engine iterations)."""
    ok1, ex1 = positions_ok(gen, s1)
    ok2, ex2 = positions_ok(gen, s2)
    if served_by == 1:
        return bool(ok1.all()), int(ex1.sum())
    if served_by == 2:
        return bool(ok2.all()), int(ex2.sum())
    n = len(gen)
    for k in range(n + 1):
        if ok1[:k].all() and ok2[k:].all():
            return True, int(ex1[:k].sum() + ex2[k:].sum())
    return False, int(np.maximum(ex1, ex2).sum())


# ---------------------------------------------------------------------------
# phase: the serving lane
# ---------------------------------------------------------------------------

def make_requests(n: int, sessions: int, vocab: int, seed: int,
                  prompt_lens=PROMPT_LENS, max_news=MAX_NEWS):

    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        prompt = [int(t) for t in rng.integers(1, vocab, size=plen)]
        max_new = int(rng.integers(max_news[0], max_news[1] + 1))
        out.append((f"s{i % sessions}", prompt, max_new))
    return out


def lane_phase(cfg, scfg, *, seed: int, requests: int = 12,
               sessions: int = 4, prompt_lens=PROMPT_LENS,
               max_news=MAX_NEWS) -> dict:


    dev = jax.devices()[0]
    model = Model(cfg)
    say(f"[lane] model {cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_heads} heads / {cfg.num_kv_heads} kv, "
        f"vocab {cfg.vocab_size}, {cfg.dtype}")

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(model.init_params)(jax.random.key(seed)))
    leaves = jax.tree.leaves(params)
    n_params = sum(int(x.size) for x in leaves)
    n_bytes = sum(int(x.nbytes) for x in leaves)
    say(f"[lane] params on {dev.device_kind}: {n_params} parameters, "
        f"{n_bytes} bytes ({time.perf_counter() - t0:.3f} s to init)")
    # step 2 is step 1 scaled, kept on the host so the source sets do not
    # stay on the device beside the replicas' installed copies
    params2 = jax.device_get(jax.jit(lambda p: jax.tree.map(
        lambda x: (x * 1.01).astype(x.dtype), p))(params))

    cluster = AftCluster(MemoryStorage(), ClusterConfig(
        num_nodes=2, routing="consistent_hash"))
    platform = LambdaPlatform(
        FaasConfig(time_scale=0.0, max_workers=32, seed=seed))
    pool = WorkflowPool(platform, cluster=cluster, config=PoolConfig(
        scope=TxnScope.STEP, max_attempts=10))
    replicas = {n.node_id: ContinuousEngine(model, None, scfg,
                                            name=f"rep-{n.node_id}")
                for n in cluster.live_nodes()}
    lane = InferenceLane(pool, cluster, replicas, config=LaneConfig(
        run_id="smoke", poll_every_s=0.25, request_timeout_s=600.0))
    prev_tracer = obs_trace.get_tracer()
    tracer = obs_trace.enable(capacity=500_000)
    try:
        # -- publish and install step 1 ------------------------------------
        t0 = time.perf_counter()
        lane.publish(params, 1)
        say(f"[lane] published step 1 in {time.perf_counter() - t0:.3f} s")
        del params, leaves
        cluster.step_all()
        t0 = time.perf_counter()
        lane.poll_weights()
        say(f"[lane] installed step 1 on {len(replicas)} replicas in "
            f"{time.perf_counter() - t0:.3f} s")
        check(all(e.weights_step == 1 for e in replicas.values()),
              "every replica installed step 1")
        for eng in replicas.values():
            installed, _ = eng.current_params()
            il = jax.tree.leaves(installed)
            say(f"[lane] {eng.name}: {sum(int(x.nbytes) for x in il)} bytes "
                f"installed in {len(il)} leaves")
            check(all(isinstance(x, jax.Array) and x.devices() == {dev}
                      for x in il),
                  f"{eng.name}: every installed leaf is a jax.Array on "
                  f"{dev.platform}:{dev.id}")
        first = next(iter(replicas.values()))
        ref1, _ = first.current_params()

        # -- compile: first calls of each jitted step, per replica ---------
        warm = [1] * (scfg.prefill_chunk + 1)
        for eng in replicas.values():
            ticket = eng.submit(warm, 2)
            t0 = time.perf_counter()
            eng.step()  # one non-final prefill chunk: compiles prefill
            t_pre = time.perf_counter() - t0
            t0 = time.perf_counter()
            eng.step()  # final chunk + first decode: compiles decode
            t_dec = time.perf_counter() - t0
            while not ticket.done():
                eng.step()
            ticket.result(timeout=0)
            say(f"[lane] {eng.name}: first prefill-chunk call {t_pre:.3f} s, "
                f"first decode iteration {t_dec:.3f} s (set-up: trace, "
                f"compile, run)")
        say(f"[lane] steady decode step: {decode_step_seconds(first, ref1)}")

        # -- serve, with a step-2 publish mid-stream ------------------------
        for eng in replicas.values():
            eng.start()
        lane.start_refresher()
        reqs = make_requests(requests, sessions, cfg.vocab_size, seed,
                             prompt_lens, max_news)
        half = len(reqs) // 2
        tickets, at_submit = [], []

        def submit(batch):
            for session, prompt, max_new in batch:
                at_submit.append(min(e.weights_step
                                     for e in replicas.values()))
                tickets.append(lane.submit(session, prompt, max_new=max_new))

        def wait_for(cond, what, limit_s=600.0):
            deadline = time.perf_counter() + limit_s
            while not cond():
                if time.perf_counter() > deadline:
                    raise AssertionError(f"timed out waiting for {what}")
                time.sleep(0.01)

        t_serve = time.perf_counter()
        submit(reqs[:half])
        wait_for(lambda: sum(t.done() for t in tickets) >= 2,
                 "two first-wave requests")
        t0 = time.perf_counter()
        lane.publish(params2, 2)
        say(f"[lane] published step 2 mid-stream in "
            f"{time.perf_counter() - t0:.3f} s")
        del params2
        wait_for(lambda: all(e.weights_step == 2 for e in replicas.values()),
                 "every replica to install step 2")
        submit(reqs[half:])
        payloads = [InferenceLane.payload(t.result(timeout=600))
                    for t in tickets]
        serve_s = time.perf_counter() - t_serve
        ref2, _ = first.current_params()
    finally:
        lane.stop()
        obs_trace.set_tracer(prev_tracer)
        tracer.close()
        pool.close()
        platform.shutdown()
        cluster.stop()

    tokens_out = sum(len(p["tokens"]) for p in payloads)
    steps = sorted({p["weights_step"] for p in payloads})
    say(f"[lane] served {len(payloads)} requests over "
        f"{len({r[0] for r in reqs})} sessions, {tokens_out} tokens, in "
        f"{serve_s:.3f} s wall (smoke timing)")
    check(len(payloads) == len(reqs)
          and all(len(p["tokens"]) == r[2] for p, r in zip(payloads, reqs)),
          f"all {len(reqs)} requests completed with their max_new tokens")
    check(steps == [1, 2], f"both weight steps served (saw {steps})")
    check(lane.stats["torn_reads"] == 0, "0 torn weight reads")
    errors = lane.stats["refresh_errors"] + sum(
        e.stats["refresh_errors"] for e in replicas.values())
    check(errors == 0, f"0 refresh errors (lane error: {lane.refresh_error})")
    checked = check_events(tracer.events())
    check(checked.ok, f"0 checker violations over {checked.events} trace "
          f"events ({checked.refreshes_checked} weight refreshes checked)")

    # -- teacher-forced reference on the weights that served each request --
    seqs = [(r[1], p["tokens"]) for r, p in zip(reqs, payloads)]
    s1 = reference_scores(model, ref1, seqs, scfg.max_len)
    s2 = reference_scores(model, ref2, seqs, scfg.max_len)
    exact = total = 0
    served = {1: 0, 2: 0, None: 0}
    for i, (p, (_, gen)) in enumerate(zip(payloads, seqs)):
        by = 1 if p["weights_step"] == 1 else 2 if at_submit[i] == 2 else None
        served[by] += 1
        ok, n_exact = request_ok(gen, s1[i], s2[i], by)
        check(ok, f"request {i} ({len(gen)} tokens, weights step "
              f"{by or '1->2'}) matches the teacher-forced reference")
        exact += n_exact
        total += len(gen)
    say(f"[lane] reference: {exact}/{total} generated tokens are the exact "
        f"reference argmax; requests by weight step: {served[1]} on 1, "
        f"{served[2]} on 2, {served[None]} across the swap")
    return {"requests": len(payloads), "tokens": tokens_out,
            "serve_s": serve_s}


def decode_step_seconds(engine, params, iters: int = 20) -> str:
    """Median of ``iters`` decode steps with every slot live, each ended
    by ``block_until_ready``, on a scratch decode state."""
    S, L = engine.config.slots, engine.config.max_len
    state = engine.model.init_decode_state(S, L)
    tokens = jnp.ones((S,), jnp.int32)
    positions = jnp.full((S,), L // 2, jnp.int32)
    key = jax.random.key(0)
    times = []
    for _ in range(iters + 1):
        t0 = time.perf_counter()
        nxt, state = engine._decode(params, state, tokens, positions, key)
        jax.block_until_ready((nxt, state))
        times.append(time.perf_counter() - t0)
    med = float(np.median(times[1:]))
    return f"{med * 1e3:.3f} ms median of {iters} ({S} slots x {L} rows)"


# ---------------------------------------------------------------------------
# phase: the commit-digest plane
# ---------------------------------------------------------------------------

def digest_phase(n_nodes: int, *, txns_per_node: int = 3,
                 metrics: bool = False) -> None:
    """One ``DigestPlane`` round (and, with ``metrics``, one
    ``MetricsPlane`` round) over ``n_nodes`` AFT nodes on a ``nodes`` mesh
    of as many local devices as divide ``n_nodes``."""
    mesh = gossip.digest_mesh(n_nodes)
    devices = list(mesh.devices.flat)
    say(f"[digest] {n_nodes} AFT nodes over a {len(devices)}-device nodes "
        f"mesh: {[f'{d.platform}:{d.id}' for d in devices]}")
    # no background agents and no eager push: only the plane moves commits
    cluster = AftCluster(MemoryStorage(), ClusterConfig(
        num_nodes=n_nodes, start_background_threads=False,
        multicast_eager_push=False))
    exchanged = []
    real_exchange = gossip.exchange_digests

    def recording_exchange(digests, mesh=None):
        placed = gossip.place_digests(digests, mesh)
        shards = {s.device for s in placed.addressable_shards}
        out = real_exchange(digests, mesh)
        exchanged.append((np.asarray(digests), out, shards))
        return out

    gossip.exchange_digests = recording_exchange
    try:
        nodes = cluster.live_nodes()
        committed = {}
        for node in nodes:
            tids = []
            for j in range(txns_per_node):
                tx = node.start_transaction()
                node.put(tx, f"smoke/{node.node_id}/{j}",
                         f"{node.node_id}:{j}".encode())
                tids.append(node.commit_transaction(tx))
            committed[node.node_id] = tids

        plane = gossip.DigestPlane(nodes, cluster.storage, mesh=mesh)
        t0 = time.perf_counter()
        merged = plane.step()
        say(f"[digest] one DigestPlane round: {merged} records merged in "
            f"{time.perf_counter() - t0:.3f} s (smoke timing, first call "
            f"compiles)")
        digests, gathered, shards = exchanged[0]
        check(np.array_equal(gathered, np.concatenate(
            [digests[i:i + 1] for i in range(n_nodes)])),
            "gathered digests equal the NumPy concatenation of the "
            "per-node digests")
        for i, node in enumerate(nodes):
            want = {(t.timestamp, gossip._hash64(t.encode()))
                    for t in committed[node.node_id]}
            check(set(gossip.unpack_digest(digests[i])) == want,
                  f"{node.node_id}'s digest row carries its "
                  f"{len(want)} commits")
        check(len(shards) == len(devices) and all(
            d.platform == devices[0].platform for d in shards),
            f"the gather's operand is sharded over {len(devices)} distinct "
            f"{devices[0].platform} devices")
        check(merged == n_nodes * (n_nodes - 1) * txns_per_node,
              f"each node merged the other nodes' commits ({merged})")
        for node in nodes:
            tx = node.start_transaction()
            seen = all(node.get(tx, f"smoke/{src.node_id}/{j}")
                       == f"{src.node_id}:{j}".encode()
                       for src in nodes for j in range(txns_per_node))
            node.abort_transaction(tx)
            check(seen, f"{node.node_id} reads every node's commits")

        if metrics:
            exchanged.clear()
            mplane = gossip.MetricsPlane(nodes, cluster.storage, mesh=mesh)
            ingested = mplane.step()
            digests, gathered, shards = exchanged[0]
            check(np.array_equal(gathered, np.concatenate(
                [digests[i:i + 1] for i in range(n_nodes)])),
                "gathered metrics rows equal their NumPy concatenation")
            check(len(shards) == len(devices),
                  f"the metrics gather's operand is sharded over "
                  f"{len(devices)} devices")
            check(ingested == n_nodes
                  and set(mplane.views) == {n.node_id for n in nodes},
                  f"one MetricsPlane round ingested all {n_nodes} node "
                  f"snapshots")
    finally:
        gossip.exchange_digests = real_exchange
        cluster.stop()


# ---------------------------------------------------------------------------
# phase: the Pallas kernels
# ---------------------------------------------------------------------------

def rel_err(out, ref) -> float:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


def kernel_phase(*, interpret: bool = False, seed: int = 0,
                 flash=(1, 32, 4, 2048, 64),
                 ssd=(1, 2048, 112, 64, 64, 256)) -> None:
    """``flash_attention`` at tinyllama prefill width (b, h, kvh, s, d) and
    ``ssd_scan`` at zamba2-7b head widths (b, s, h, p, n, chunk), each
    against its pure-jnp reference."""
    b, h, kvh, s, d = flash
    ks = jax.random.split(jax.random.key(seed), 7)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, kvh, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, kvh, s, d), jnp.bfloat16)
    t0 = time.perf_counter()
    out = jax.block_until_ready(flash_attention(q, k, v, interpret=interpret))
    say(f"[kernels] flash_attention {flash} bf16, interpret={interpret}: "
        f"first call {time.perf_counter() - t0:.3f} s")
    ref = attention_ref(q, k, v)
    err = rel_err(out, ref)
    check(np.isfinite(np.asarray(out, np.float32)).all() and err < 2e-2,
          f"flash_attention matches attention_ref (max err {err:.2e} of "
          f"max |ref|)")

    b, s, h, p, n, chunk = ssd
    x = jax.random.normal(ks[3], (b, s, h, p))
    da = -jax.nn.softplus(jax.random.normal(ks[4], (b, s, h)))
    bm = jax.random.normal(ks[5], (b, s, n))
    cm = jax.random.normal(ks[6], (b, s, n))
    t0 = time.perf_counter()
    y, st = jax.block_until_ready(
        ssd_scan(x, da, bm, cm, chunk=chunk, interpret=interpret))
    say(f"[kernels] ssd_scan {ssd} f32, interpret={interpret}: first call "
        f"{time.perf_counter() - t0:.3f} s")
    with jax.default_matmul_precision("highest"):
        yr, sr = ssd_scan_ref(x, da, bm, cm)
    ey, es = rel_err(y, yr), rel_err(st, sr)
    check(np.isfinite(np.asarray(y)).all() and ey < 2e-2 and es < 2e-2,
          f"ssd_scan matches ssd_scan_ref (max err {ey:.2e} on y, {es:.2e} "
          f"on the final state, of max |ref|)")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the digest and metrics planes over "
                         "four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache = use_compile_cache()

    devices = jax.devices()
    dev = devices[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; compile cache: {cache}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (found {dev.platform}); refusing to run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    t_all = time.perf_counter()
    if args.chips == 4:
        digest_phase(4, metrics=True)
    else:
        lane_phase(get_config(ARCH),
                   ServeConfig(slots=8, max_len=512, prefill_chunk=16),
                   seed=args.seed)
        digest_phase(2)
        kernel_phase(interpret=False, seed=args.seed)
        stats = dev.memory_stats() or {}
        say(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    say(f"host peak RSS: "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024} bytes")
    say(f"all phases passed in {time.perf_counter() - t_all:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    # a hung phase dumps every thread's stack and exits non-zero
    faulthandler.dump_traceback_later(TIMEOUT_S, exit=True)
    sys.exit(main())
