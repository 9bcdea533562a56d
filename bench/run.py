#!/usr/bin/env python3
"""One run of one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the first timed request): the
cell's weights are made on the device from ``--seed`` in one jitted call
and installed on every replica; each replica runs its prefill and decode
programs once at the cell's shapes (served from JAX's persistent
compilation cache after a checkout's first run), and a few requests pass
through the lane.  Then the cell's traffic runs for ``--seconds``; every
request sent is waited for.  With ``--trace 1`` the middle of the window is
profiled and the per-layer metrics are read instead of the end-to-end
ones.  After the window the program's state is freed and a sample of the
answers is compared with the float32 reference (``compare.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``compared``: each number compared with its limit.  The same
numbers are the last lines of standard error.

Two more modes serve the cell's calibration, not its runs:
``--rates a,b,..`` offers each rate in turn after one set-up and prints the
latency and throughput of each (the sweep for the knee), and
``--calibrate a,b,..`` runs the cell once per seed and prints, per seed,
the widest gap of the program's tokens and of the float8 control's.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import spec  # noqa: E402


class NoChip(SystemExit):
    pass


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rates", default="",
                    help="comma-separated offered rates: the knee sweep")
    ap.add_argument("--calibrate", default="",
                    help="comma-separated seeds: program and control gaps")
    return ap.parse_args(argv)


def use_cache(root: Path) -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else a fixed directory inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def check_device(devices, chips: int, peaks: dict) -> dict:
    """The run's device: an accelerator, as many chips as the cell asks
    for, and a kind the peaks table knows.  Anything else ends the run
    before any timing."""
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", file=sys.stderr, flush=True)
    if dev.platform == "cpu":
        raise NoChip(f"no accelerator (JAX found {dev.platform})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, found "
                     f"{len(devices)}")
    if dev.device_kind not in peaks:
        raise NoChip(f"device kind {dev.device_kind!r} is not in the "
                     f"peaks table")
    return peaks[dev.device_kind]


class Setup:
    """Everything a window needs, built from the seed."""

    def __init__(self, cell, seed: int, model):
        import harness
        import weights

        self.cell, self.seed = cell, seed
        cj = cell.config
        self.params = weights.make(cj, seed, 1)
        harness.check_layout(model, self.params)
        self.params2 = None
        pub = cell.traffic.get("publish")
        if pub is not None:
            p2 = weights.make(cj, seed, int(pub["step"]))
            self.params2 = harness.host_numpy(p2)
            del p2
        self.system = harness.System(cell, model, self.params, seed)
        self.tracer = None
        if pub is not None:
            from repro.obs import trace as obs_trace

            self.tracer = obs_trace.enable(capacity=2_000_000)
        self.system.warm()

    def finish(self) -> tuple:
        """Stop and free the program; return (lane stats, checker
        violations or None)."""
        import harness

        stats = dict(self.system.lane.stats)
        for eng in self.system.engines.values():
            stats["refresh_errors"] += eng.stats["refresh_errors"]
        harness.free(self.system)
        violations = None
        if self.tracer is not None:
            from repro.obs import trace as obs_trace
            from repro.obs.checker import check_events

            obs_trace.disable()
            violations = len(check_events(self.tracer.events()).violations)
            self.tracer.close()
        return stats, violations


def compare_run(setup: Setup, run, stats, violations, control=False):
    import compare
    import jax

    cell = setup.cell
    rows = int(cell.settings["reference_rows"])
    done = [r for r in run.requests if r.ok]
    sample = compare.pick(done, setup.seed, rows)
    by_step = {1: setup.params}
    if setup.params2 is not None and any(r.step == 2 for p in sample
                                         for r in p):
        by_step[2] = jax.device_put(setup.params2)
    readings = None
    if sample:
        readings = compare.gaps(by_step, cell.config, sample, rows,
                                int(cell.settings["serve"]["max_len"]),
                                control=control)
    pub = "publish" in cell.traffic
    exact = compare.counts(run, stats if pub else None,
                           violations if pub else None)
    if pub and run.publish is None:
        exact["publish_unfinished"] = 1
    correct, compared = compare.judge(
        readings and readings["max_gap"], float(cell.settings["gap_limit"]),
        exact)
    return correct, compared, readings


def one_run(args, cell, model, peak, root) -> int:
    import compare
    import harness
    import xtrace

    setup = Setup(cell, args.seed, model)
    setup_s = time.perf_counter() - T_START
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    try:
        run = harness.run_window(setup.system, cell, args.seed, args.seconds,
                                 vocab=int(cell.config["vocab_size"]),
                                 params2=setup.params2, trace_dir=trace_dir)
        run.setup_s = setup_s
        run.peak = peak
        device = harness.device_peak()
        stats, violations = setup.finish()
        gc.collect()
        if trace_dir is not None:
            # a rehearsal on the CPU reads the CPU client's thread instead
            lines = {} if peak is not None else {
                "device_prefix": "/host:CPU",
                "modules_line": "tf_XLAPjRtCpuClient",
                "ops_line": "tf_XLAPjRtCpuClient"}
            run.trace = xtrace.reduce(xtrace.load(trace_dir), **lines)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if run.compiles_in_window:
        print(f"warning: {run.compiles_in_window} compilations inside the "
              f"window", file=sys.stderr)
    correct, compared, readings = compare_run(setup, run, stats, violations)
    metrics = spec.read_metrics(cell.per_layer if args.trace
                                else cell.end_to_end, run, root)
    result = {"correct": correct, "attempted": len(run.requests),
              "failed": sum(not r.ok for r in run.requests),
              "metrics": metrics, "device": device}
    if args.trace:
        if run.trace is None:
            raise RuntimeError("the trace holds no device events")
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["compared"] = compared
    if readings is not None:
        print(f"reference sample: {readings['requests']} requests, "
              f"{readings['served_tokens']} served tokens in "
              f"{readings['passes']} passes, "
              f"{readings['exact']} exactly the reference's argmax",
              file=sys.stderr)
    print("\n".join(compare.describe(compared)), file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


def sweep(args, cell, model) -> int:
    """One set-up, then each rate's window in turn."""
    import numpy as np

    import harness

    setup = Setup(cell, args.seed, model)
    for rate in [float(r) for r in args.rates.split(",")]:
        run = harness.run_window(setup.system, cell, args.seed,
                                 args.seconds,
                                 vocab=int(cell.config["vocab_size"]),
                                 rate=rate)
        lat = sorted((r.done - r.due) if r.ok else float("inf")
                     for r in run.requests)
        n = len(lat)
        done = [r for r in run.requests if r.ok and r.done <= run.t1]
        toks = sum(len(r.prompt) + len(r.tokens) for r in done)
        late = [r.sent - r.due for r in run.requests]
        print(json.dumps({
            "rate": rate, "attempted": n,
            "completed_in_window": len(done),
            "failed": sum(not r.ok for r in run.requests),
            "p50_ms": 1e3 * lat[n // 2] if n else None,
            "p95_ms": 1e3 * lat[min(n - 1, int(np.ceil(0.95 * n)) - 1)]
            if n else None,
            "tokens_per_s": toks / args.seconds,
            "late_p99_ms": 1e3 * float(np.percentile(late, 99)) if n
            else None}), flush=True)
    setup.finish()
    return 0


def calibrate(args, cell, model) -> int:
    """Per seed: a run at the cell's load, the program's widest gap, and
    the widest gap of the float8 control on the same sample."""
    import harness

    for seed in [int(s) for s in args.calibrate.split(",")]:
        setup = Setup(cell, seed, model)
        run = harness.run_window(setup.system, cell, seed, args.seconds,
                                 vocab=int(cell.config["vocab_size"]),
                                 params2=setup.params2)
        stats, violations = setup.finish()
        correct, compared, readings = compare_run(setup, run, stats,
                                                  violations, control=True)
        print(json.dumps({"seed": seed, "correct": correct,
                          "compared": compared, "readings": readings}),
              flush=True)
        del setup, run
        gc.collect()
    return 0


def main(argv=None, root: Path = spec.ROOT, require_chip: bool = True) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload, root)
    sys.path.insert(0, str(Path(root) / "src"))
    import jax

    use_cache(root)
    peaks = spec.load_peaks()
    peak = None
    if require_chip:
        try:
            peak = check_device(jax.devices(), cell.chips, peaks)
        except NoChip as exc:
            print(f"bench: {exc}; refusing to run", file=sys.stderr)
            return 2
    import harness
    from repro.models import Model

    model = Model(harness.arch_config(cell.config))
    if args.rates:
        return sweep(args, cell, model)
    if args.calibrate:
        return calibrate(args, cell, model)
    return one_run(args, cell, model, peak, root)


if __name__ == "__main__":
    sys.exit(main())
