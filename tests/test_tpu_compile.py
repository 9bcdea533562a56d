"""Compile the device path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, too much fast memory, a program larger than the device.
These cases compile the Pallas kernels at published widths, the full-width
tinyllama-1.1b serving steps, the serving steps of the benchmark's cells
and the 4-device digest all_gather for a ``v5e:2x2`` topology.  Nothing
runs, so they say nothing about results or times.

The topology is described inside a module fixture (never at import time):
only one process at a time may load the TPU library, so under several test
workers only the worker that runs this file loads it.
"""

import dataclasses
import json
import os
import re
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

DEVICE_BYTES = 16 * 2**30  # one v5e chip's HBM


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        compilation_cache.reset_cache()
        if prev_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


FLASH_CASES = {
    # name: (b, h, kvh, s, d, window, softcap)
    "tinyllama-1.1b": (1, 32, 4, 2048, 64, 0, 0.0),
    "gemma2-9b": (1, 16, 8, 4096, 256, 4096, 50.0),
}


@pytest.mark.parametrize("arch", sorted(FLASH_CASES))
def test_flash_attention_compiles(one_chip, arch):
    from repro.kernels.flash_attention import flash_attention

    b, h, kvh, s, d, window, softcap = FLASH_CASES[arch]
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, kvh, s, d), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, window=window,
                                        softcap=softcap)
    ).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_at_zamba2_width(one_chip):
    from repro.kernels.ssd_scan import ssd_scan

    b, s, h, p, n = 1, 2048, 112, 64, 64  # zamba2-7b: 7168 / 64 heads

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(
        lambda x, da, bm, cm: ssd_scan(x, da, bm, cm, chunk=256)
    ).lower(arg(b, s, h, p), arg(b, s, h), arg(b, s, n),
            arg(b, s, n)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def tinyllama_engine():
    """The full-width tinyllama-1.1b ContinuousEngine at the smoke's
    shape (8 slots x 512 rows); holds its decode state, not its weights."""
    from repro.models import Model
    from repro.models.config import get_config
    from repro.serve.engine import ContinuousEngine, ServeConfig

    model = Model(get_config("tinyllama-1.1b"))
    return ContinuousEngine(
        model, None, ServeConfig(slots=8, max_len=512, prefill_chunk=16))


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_tinyllama_serving_step_compiles(one_chip, tinyllama_engine, step):
    eng = tinyllama_engine
    params = _shapes(jax.eval_shape(eng.model.init_params,
                                    jax.random.key(0)), one_chip)
    state = _shapes(eng._state, one_chip)
    key = _shapes(jax.eval_shape(lambda: jax.random.key(0)), one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if step == "decode":
        lowered = eng._decode.lower(params, state, i32(8), i32(8), key)
    else:
        lowered = eng._prefill.lower(params, state, i32(), i32(16), i32(),
                                     i32(), key)
    mem = lowered.compile().memory_analysis()
    assert 2e9 < mem.argument_size_in_bytes < DEVICE_BYTES


BENCH = Path(__file__).resolve().parents[1] / "bench"


def _cell_engine(cell):
    """The ContinuousEngine of a benchmark cell: the registered
    configuration with the sizes of the cell's configuration file, at the
    cell's slots, rows and chunk.  Its decode state is abstract."""
    from repro.models import Model
    from repro.models.config import get_config
    from repro.serve.engine import ContinuousEngine, ServeConfig

    cj = json.loads((BENCH / "configs" / f"{cell.rsplit('.', 1)[0]}.json")
                    .read_text())
    sv = json.loads((BENCH / "cells" / f"{cell}.json").read_text())["serve"]
    cfg = dataclasses.replace(get_config(cj["registered"]),
                              pattern_repeats=cj["num_hidden_layers"],
                              vocab_size=cj["vocab_size"])
    model = Model(cfg)
    model.init_decode_state = lambda S, L: jax.eval_shape(
        lambda: Model.init_decode_state(model, S, L))
    return ContinuousEngine(model, None, ServeConfig(**sv))


@pytest.mark.parametrize("cell,step", [("qwen2-0.5b.chat", "decode"),
                                       ("qwen1.5-110b-4l.rag", "decode"),
                                       ("qwen2-0.5b.chat", "prefill")])
def test_cell_serving_step_copies_no_cache(one_chip, cell, step):
    """The serving steps write the KV cache in place: no copy in the
    program has a ``max_len`` dimension, the new state aliases the donated
    one, and the decode step's scratch memory is a small share of the
    state."""
    eng = _cell_engine(cell)
    params = _shapes(jax.eval_shape(eng.model.init_params,
                                    jax.random.key(0)), one_chip)
    state = _shapes(eng._state, one_chip)
    key = _shapes(jax.eval_shape(lambda: jax.random.key(0)), one_chip)
    S, L, C = eng._S, eng._L, eng._C

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if step == "decode":
        lowered = eng._decode.lower(params, state, i32(S), i32(S), key)
    else:
        lowered = eng._prefill.lower(params, state, i32(), i32(C), i32(),
                                     i32(), key)
    compiled = lowered.compile()
    copies = [m.group(1) for m in re.finditer(
        r"= \w+\[([\d,]*)\]\S* copy\(", compiled.as_text())]
    assert copies, "no copy matched: the HLO text format changed"
    assert not [c for c in copies if str(L) in c.split(",")], copies
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(state))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    if step == "decode":
        assert mem.temp_size_in_bytes < state_bytes / 4


def test_digest_all_gather_compiles_on_four_chips(topo):
    from repro.core.gossip import DIGEST_WIDTH, digest_gather

    mesh = Mesh(topo.devices, ("nodes",))
    digests = jax.ShapeDtypeStruct((4, 128, DIGEST_WIDTH), jnp.int32,
                                   sharding=NamedSharding(mesh, P("nodes")))
    text = digest_gather(mesh).lower(digests).compile().as_text()
    assert "all-gather" in text
