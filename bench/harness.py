"""The system under test, wired as a deployment runs it, and one measured
window of traffic through it.

``System`` builds an AFT cluster over in-memory storage, a ``WorkflowPool``
and one ``ContinuousEngine`` replica per node, joined by an
``InferenceLane``.  Each request is the lane's read-only two-step workflow:
it reads the session key and the weight manifest read-atomically and
submits the prompt to the session's replica.

The benchmark times the layers from outside, by wrapping the calls into
each: a proxy round each replica's ``submit`` (engine time of a request),
and wrappers round each engine's jitted ``prefill`` and ``decode`` calls
and its ``step`` (the work of every call, and host spans that a traced
run puts on the profiler's clock).  Nothing in the program is changed.
"""

from __future__ import annotations

import gc
import queue
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import numpy as np

import spec
import traffic as traffic_mod

GRACE_S = 60.0          # wait past the window for answers still due
TRACE_S = 4.0           # longest traced stretch of a window
# wait past the window for the profiler to write its trace: 4 s of a
# 5.5 ms decode hold most of a million device events, and stop_trace took
# 78 s with the Python tracer on and 92 s with it off on one TPU v5e
STOP_TRACE_S = 180.0


class TraceTimeout(TimeoutError):
    """The profiler did not write its trace within ``STOP_TRACE_S``."""


# ---------------------------------------------------------------------------
# the program's configuration, from the configuration file
# ---------------------------------------------------------------------------

def arch_config(cj: dict):
    """The registered configuration with the file's sizes, mapped by the
    configuration's family (``arch``).  A size that differs from the
    registered one must be listed in ``reduced``."""
    import dataclasses

    from repro.models.config import get_config

    base = get_config(cj["registered"])
    over = spec.family(cj).arch(cj, base)
    for key, (name, value) in over.items():
        if key not in cj.get("reduced", ()) and getattr(base, name) != value:
            raise ValueError(f"{key}={value} differs from {base.name}'s "
                             f"{getattr(base, name)} and is not in reduced")
    return dataclasses.replace(base, name=f"{base.name}@bench",
                               **dict(over.values()))


def check_layout(model, params) -> None:
    """The benchmark's weight tree has the model's structure, shapes and
    dtypes."""
    want = model.abstract_params()
    if jax.tree.structure(want) != jax.tree.structure(params):
        raise ValueError("weight tree does not match the model's layout")
    for w, p in zip(jax.tree.leaves(want), jax.tree.leaves(params)):
        if w.shape != p.shape or w.dtype != p.dtype:
            raise ValueError(f"leaf {p.shape} {p.dtype} != model's "
                             f"{w.shape} {w.dtype}")


# ---------------------------------------------------------------------------
# wrappers round the calls into each layer
# ---------------------------------------------------------------------------

def _key(prompt) -> tuple:
    return (len(prompt),) + tuple(int(t) for t in prompt[:16])


class Recorder:
    """What the wrappers saw: one tuple per prefill/decode call, and each
    engine ticket with the weight step its engine held at submit."""

    def __init__(self):
        self.calls: List[tuple] = []
        self.tickets: Dict[tuple, tuple] = {}


class Replica:
    """A replica as the lane sees it: the engine, with ``submit`` and
    ``install_weights`` timed from outside."""

    def __init__(self, engine, rec: Recorder):
        self._engine = engine
        self._rec = rec

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def submit(self, prompt, max_new):
        with jax.profiler.TraceAnnotation("bench.engine_submit"):
            step = self._engine.weights_step
            ticket = self._engine.submit(prompt, max_new)
        self._rec.tickets[_key(prompt)] = (ticket, step)
        return ticket

    def install_weights(self, *args, **kwargs):
        with jax.profiler.TraceAnnotation("bench.install"):
            return self._engine.install_weights(*args, **kwargs)


def wrap_engine(eng, rec: Recorder) -> None:
    """Record each jitted call's work and span the engine's iteration.
    The engine advances one prompt chunk per iteration, for the first slot
    whose prompt is not yet prefilled, so that slot is the call's."""
    L, C = eng.config.max_len, eng.config.prefill_chunk
    prefill, decode, step = eng._prefill, eng._decode, eng.step

    def prefill_call(*args):
        req = next(r for r in eng._slots
                   if r is not None and r.offset < len(r.prompt))
        off, plen = req.offset, len(req.prompt)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.prefill_call"):
            out = prefill(*args)
        rec.calls.append((t, "prefill", min(C, plen - off), off,
                          off + C >= plen))
        return out

    def decode_call(*args):
        pos = eng._positions
        live = pos < L
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.decode_call"):
            out = decode(*args)
        rec.calls.append((t, "decode", int(live.sum()),
                          int((pos[live] + 1).sum())))
        return out

    def engine_step():
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            return step()

    eng._prefill, eng._decode, eng.step = prefill_call, decode_call, \
        engine_step
    eng.bench_jitted = (prefill, decode)


def compile_counts(engines) -> List[int]:
    """Compiled variants behind each replica's two programs (-1 where a
    program is not a jitted callable)."""
    return [getattr(f, "_cache_size", lambda: -1)()
            for e in engines for f in e.bench_jitted]


# ---------------------------------------------------------------------------
# the system
# ---------------------------------------------------------------------------

class System:
    def __init__(self, cell, model, params, seed: int):
        from repro.core import AftCluster, ClusterConfig
        from repro.faas.platform import FaasConfig, LambdaPlatform
        from repro.serve.engine import ContinuousEngine, ServeConfig
        from repro.serve.lane import InferenceLane, LaneConfig
        from repro.storage.memory import MemoryStorage
        from repro.workflow import PoolConfig, TxnScope, WorkflowPool

        s = cell.settings
        sv = s["serve"]
        self.scfg = ServeConfig(slots=int(sv["slots"]),
                                max_len=int(sv["max_len"]),
                                prefill_chunk=int(sv["prefill_chunk"]))
        nodes = int(s["nodes"])
        # a worker per request the replicas can hold, and as many queued
        workers = 2 * nodes * self.scfg.slots + 16
        self.rec = Recorder()
        self.cluster = AftCluster(MemoryStorage(), ClusterConfig(
            num_nodes=nodes, routing="consistent_hash"))
        self.platform = LambdaPlatform(FaasConfig(
            time_scale=0.0, max_workers=workers, seed=seed & 0x7FFFFFFF))
        self.pool = WorkflowPool(self.platform, cluster=self.cluster,
                                 config=PoolConfig(
                                     scope=TxnScope.STEP, max_attempts=10,
                                     max_inflight_steps=workers))
        self.engines = {n.node_id: ContinuousEngine(
            model, None, self.scfg, name=f"rep-{n.node_id}")
            for n in self.cluster.live_nodes()}
        for eng in self.engines.values():
            eng.install_weights(params, 1)
            wrap_engine(eng, self.rec)
        replicas = {k: Replica(e, self.rec)
                    for k, e in self.engines.items()}
        pub = cell.traffic.get("publish", {})
        self.publishes = bool(pub)
        self.lane = InferenceLane(self.pool, self.cluster, replicas,
                                  config=LaneConfig(
                                      run_id="bench",
                                      poll_every_s=float(
                                          pub.get("poll_every_s", 0.25)),
                                      request_timeout_s=300.0))
        self.stopped = False

    def warm(self) -> None:
        """Compile and run each replica's prefill and decode once at the
        cell's shapes, then send a few requests through the lane."""
        C = self.scfg.prefill_chunk
        for eng in self.engines.values():
            t = eng.submit([1] * (C + 1), 2)
            while not t.done():
                eng.step()
            t.result(timeout=0)
        for eng in self.engines.values():
            eng.start()
        if self.publishes:
            self.lane.start_refresher()
        tickets = [self.lane.submit(f"warm{i}", [1 + i] * 8, max_new=2)
                   for i in range(4 * len(self.engines))]
        for t in tickets:
            t.result(timeout=300)
        self.rec.calls.clear()
        self.rec.tickets.clear()

    def stop(self) -> None:
        if self.stopped:
            return
        self.stopped = True
        self.lane.stop()
        self.pool.close()
        self.platform.shutdown()
        self.cluster.stop()


def free(system: System) -> None:
    """Drop the program's state (caches, engines, lane) from the device."""
    system.stop()
    for eng in system.engines.values():
        eng._state = eng._params = None
    system.engines.clear()
    system.lane = system.pool = system.cluster = None
    gc.collect()


# ---------------------------------------------------------------------------
# one window
# ---------------------------------------------------------------------------

@dataclass
class Sent:
    session: str
    prompt: List[int]
    max_new: int
    due: float
    sent: float
    ticket: Any = None
    done: Optional[float] = None
    ok: bool = False
    tokens: List[int] = field(default_factory=list)
    step: Optional[int] = None
    served_by: Optional[int] = None
    engine_s: Optional[float] = None
    error: str = ""


@dataclass
class RunData:
    """What a metric reader gets."""
    cell: Any
    seconds: float
    t0: float = 0.0
    t1: float = 0.0
    setup_s: float = 0.0
    requests: List[Sent] = field(default_factory=list)
    calls: List[tuple] = field(default_factory=list)
    publish: Optional[dict] = None
    trace: Optional[dict] = None
    trace_t: Optional[tuple] = None
    peak: Optional[dict] = None
    compiles_in_window: int = 0


def _send(system: System, r: traffic_mod.Request, due: float,
          on_done=None) -> Sent:
    s = Sent(r.session, r.prompt, r.max_new, due, time.perf_counter())
    with jax.profiler.TraceAnnotation("bench.submit"):
        s.ticket = system.lane.submit(r.session, r.prompt, max_new=r.max_new)

    def done(_):
        s.done = time.perf_counter()
        if on_done is not None:
            on_done(s.done)

    s.ticket.add_done_callback(done)
    return s


def _open_loop(system, reqs, dues, t0, t1) -> List[Sent]:
    out = []
    for r, d in zip(reqs, dues):
        due = t0 + d
        if due >= t1:
            break
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        out.append(_send(system, r, due))
    return out


def _closed_loop(system, reqs, outstanding, t0, t1) -> List[Sent]:
    done_q: "queue.Queue[float]" = queue.Queue()
    out, it = [], iter(reqs)
    for _ in range(outstanding):
        out.append(_send(system, next(it), t0, done_q.put))
    while True:
        left = t1 - time.perf_counter()
        if left <= 0:
            break
        try:
            due = done_q.get(timeout=left)
        except queue.Empty:
            break
        if due >= t1:
            break
        out.append(_send(system, next(it), due, done_q.put))
    return out


class _Tracer(threading.Thread):
    """Profiles ``TRACE_S`` seconds from the middle of the window.  The
    traced span is recorded when its sleep ends, before ``stop_trace``
    writes the trace.  Host events are the host tracer's alone (the
    ``bench.`` and ``aft.`` annotations); the Python tracer is off."""

    def __init__(self, log_dir: str, start: float, length: float):
        super().__init__(daemon=True, name="bench-trace")
        self.log_dir, self.start_at, self.length = log_dir, start, length
        self.span: Optional[tuple] = None
        self.error: Optional[Exception] = None

    def run(self):
        try:
            time.sleep(max(0.0, self.start_at - time.perf_counter()))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation("bench.trace_window"):
                    a = time.perf_counter()
                    time.sleep(self.length)
                    self.span = (a, time.perf_counter())
            finally:
                jax.profiler.stop_trace()
        except Exception as exc:  # re-raised by run_window
            self.error = exc


class _Publisher(threading.Thread):
    """Publishes weight step ``step`` through the lane at ``at``, and
    watches for the moment every replica serves it."""

    def __init__(self, system: System, params, step: int, at: float):
        super().__init__(daemon=True, name="bench-publish")
        self.system, self.params, self.step, self.at = (system, params,
                                                        step, at)
        self.times: Dict[str, float] = {}
        self.error: Optional[Exception] = None

    def run(self):
        try:
            time.sleep(max(0.0, self.at - time.perf_counter()))
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.publish"):
                self.system.lane.publish(self.params, self.step)
            b = time.perf_counter()
            self.params = None
            engines = list(self.system.engines.values())
            deadline = b + GRACE_S
            while any(e.weights_step < self.step for e in engines):
                if time.perf_counter() > deadline:
                    raise TimeoutError("replicas did not install the "
                                       "published step")
                time.sleep(0.005)
            c = time.perf_counter()
            self.times = {"publish_s": b - a, "install_s": c - b,
                          "refresh_s": c - a}
        except Exception as exc:  # re-raised by run_window
            self.error = exc


def run_window(system: System, cell, seed: int, seconds: float, *,
               vocab: int, params2=None, trace_dir: Optional[str] = None,
               rate: Optional[float] = None) -> RunData:
    """Offer the cell's traffic for ``seconds``, then wait for every
    request sent to resolve (``GRACE_S`` at most past the window), and for
    the profiler to write its trace (``STOP_TRACE_S``)."""
    s, tr = cell.settings, cell.traffic
    run = RunData(cell=cell, seconds=seconds)
    if tr["loop"] == "open":
        rate = float(rate or s["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        reqs = traffic_mod.make_requests(tr, n, vocab, seed)
        dues = traffic_mod.due_times(
            traffic_mod.arrival_gaps(tr, n, rate))
    else:
        outstanding = int(s["outstanding"])
        reqs = traffic_mod.make_requests(
            tr, outstanding * 64, vocab, seed)

    publisher = tracer = None
    before = compile_counts(system.engines.values())
    first_call = len(system.rec.calls)
    t0 = time.perf_counter()
    t1 = t0 + seconds
    if "publish" in tr and params2 is not None:
        publisher = _Publisher(system, params2,
                               int(tr["publish"]["step"]),
                               t0 + float(tr["publish"]["at_s"]))
        publisher.start()
    if trace_dir is not None:
        length = min(TRACE_S, 0.5 * seconds)
        tracer = _Tracer(trace_dir, t0 + 0.5 * (seconds - length), length)
        tracer.start()
    if tr["loop"] == "open":
        sent = _open_loop(system, reqs, dues, t0, t1)
    else:
        sent = _closed_loop(system, reqs, outstanding, t0, t1)
    time.sleep(max(0.0, t1 - time.perf_counter()))
    last_call = len(system.rec.calls)
    run.compiles_in_window = sum(
        a != b for a, b in zip(before,
                               compile_counts(system.engines.values())))

    deadline = t1 + GRACE_S
    while any(r.done is None for r in sent) \
            and time.perf_counter() < deadline:
        time.sleep(0.01)
    if publisher is not None:
        publisher.join(max(1.0, deadline - time.perf_counter()))
    if tracer is not None:
        tracer.join(max(1.0, t1 + STOP_TRACE_S - time.perf_counter()))
        if tracer.is_alive():
            raise TraceTimeout(f"the profiler had not written its trace "
                               f"{STOP_TRACE_S:.0f} s after the window")
    for th in (publisher, tracer):
        if th is not None and th.error is not None:
            raise th.error
    _collect(system, sent)
    run.t0, run.t1 = t0, t1
    run.requests = sent
    run.calls = system.rec.calls[first_call:last_call]
    if publisher is not None:
        run.publish = publisher.times or None
    if tracer is not None:
        run.trace_t = tracer.span
    return run


def _collect(system: System, sent: List[Sent]) -> None:
    from repro.serve.lane import InferenceLane

    for r in sent:
        if r.done is None:
            r.error = "no answer within the grace period"
            continue
        try:
            payload = InferenceLane.payload(r.ticket.result(timeout=0))
        except Exception as exc:  # a failed request counts as missing
            r.error = repr(exc)
            continue
        r.ok = True
        r.tokens = [int(t) for t in payload["tokens"]]
        r.step = int(payload["weights_step"])
        got = system.rec.tickets.get(_key(r.prompt))
        if got is not None:
            ticket, step_in = got
            if ticket.finished_at is not None:
                r.engine_s = ticket.finished_at - ticket.submitted_at
            r.served_by = r.step if step_in == r.step else None
        else:
            r.served_by = r.step


def device_peak() -> dict:
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    if stats:
        print(f"device memory: peak {stats.get('peak_bytes_in_use')} of "
              f"{stats.get('bytes_limit')} bytes", file=sys.stderr)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}


def host_numpy(tree):
    return jax.tree.map(np.asarray, jax.device_get(tree))
