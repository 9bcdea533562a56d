#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e chip, without the
chip, and print what each needs on the device.

    JAX_PLATFORMS=cpu python3 bench/memcheck.py --workload <cell>

The weight maker, the engine's prefill and decode programs at the cell's
``ServeConfig``, and the reference's forward pass at the cell's reference
rows are each lowered on shapes placed on one described chip and compiled
by the TPU compiler; ``memory_analysis`` gives their argument, output and
temporary bytes.  The weights stay resident through the window and the
reference, so the figure to hold against the chip's memory is the weights
plus each program's arguments other than the weights, outputs and
temporaries.
"""

import argparse
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import harness  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import weights  # noqa: E402


def placed(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def report(name, compiled, weight_bytes):
    m = compiled.memory_analysis()
    args, out, tmp = (m.argument_size_in_bytes, m.output_size_in_bytes,
                      m.temp_size_in_bytes)
    print(f"{name}: arguments {args}, outputs {out}, temporaries {tmp}; "
          f"beside resident weights {weight_bytes + max(args - weight_bytes, 0) + out + tmp} bytes",
          flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    cj = cell.config
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    one = SingleDeviceSharding(topo.devices[0])

    from repro.models import Model
    from repro.serve.engine import ContinuousEngine, ServeConfig

    model = Model(harness.arch_config(cj))
    key = jax.eval_shape(lambda: jax.random.key(0))
    params = placed(jax.eval_shape(weights.maker(cj), key), one)
    wbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    print(f"{args.workload}: weights {wbytes} bytes", flush=True)
    report("weights", weights.maker(cj).lower(
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one)).compile(),
        0)

    sv = cell.settings["serve"]
    scfg = ServeConfig(slots=sv["slots"], max_len=sv["max_len"],
                       prefill_chunk=sv["prefill_chunk"])
    model.init_decode_state = lambda S, L: jax.eval_shape(
        lambda: Model.init_decode_state(model, S, L))
    eng = ContinuousEngine(model, None, scfg)
    state = placed(eng._state, one)
    sbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    print(f"decode state (cache): {sbytes} bytes", flush=True)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa
    k = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one)
    S, C = scfg.slots, scfg.prefill_chunk
    report("decode", eng._decode.lower(params, state, i32(S), i32(S),
                                       k).compile(), wbytes)
    report("prefill", eng._prefill.lower(params, state, i32(), i32(C),
                                         i32(), i32(), k).compile(), wbytes)

    rows = int(cell.settings["reference_rows"])
    packed = (i32(rows), i32(rows), i32(rows), i32(reference.WANT_MAX))
    ckey = tuple(sorted((a, b) for a, b in cj.items()
                        if not isinstance(b, (dict, list))))
    report("reference", reference._forward.lower(
        params, None, *packed, cfg_key=ckey,
        window=reference.window_rows(sv["max_len"])).compile(), wbytes)


if __name__ == "__main__":
    main()
