"""Model families as files (``families/<model_type>.py``).

The Qwen2 code moved out of ``weights.py``, ``reference.py``, ``work.py``
and ``harness.py`` into ``families/qwen2.py`` reads exactly what it read
before.  Every constant below was computed on commit 090a31b, the tree
before the move, with that tree's functions (``weights.make``,
``weights.layout``, ``reference.forward``, ``reference.quantize(params)``,
``work.call_work(work.dims(cj), call)``), on XLA's CPU backend of JAX
0.9.0.  The CPU logits differ in their last bits between a process held to
one core and one with more, so both were computed there.

A second family joins by a file alone, and an unknown ``model_type`` is
refused by the path looked for.
"""

import hashlib
import json
import os
import shutil

import jax
import numpy as np
import pytest

import harness
import reference
import spec
import weights
import work
from conftest import BENCH, TINY

LEAVES = {
    "['embed']":
        "834bc351835716cfcf21e7ef940cb3c9f8c03d73564fb2ef9858e74e7cdad40b",
    "['final_norm']":
        "0edd12cfffae8cdb181c55d371a2d62230e3e7ae9e6168949b97f1a29c54f894",
    "['pattern']['00_attn']['attn']['bk']":
        "109c4f804da242683291096979cc579bc0424a856d2aa270956fa79367d24afd",
    "['pattern']['00_attn']['attn']['bq']":
        "28b4645a3e510dbdb172112b5ad4cb4ca40d9006a1d355655793e7cd20cba486",
    "['pattern']['00_attn']['attn']['bv']":
        "a13e65d5814921decef145ad7e7966bc80c8cb445630d5a082cde4704dbfacdd",
    "['pattern']['00_attn']['attn']['wk']":
        "ac2df3650de2c39e73f60644159042e0c77b0e2ce432caf04410d5716b7d7d4d",
    "['pattern']['00_attn']['attn']['wo']":
        "114766da85fb49fc01a25b80ffb3b41b0063020d0e288f28af06371b06b77ca7",
    "['pattern']['00_attn']['attn']['wq']":
        "9aa73beeabfce0a4b080ba2d0232b925e16a3e3b420633d4a53e8a02a1ed20af",
    "['pattern']['00_attn']['attn']['wv']":
        "acf265e0fb165d6d6f3e7352a9c08f14711cb55439be9f9ae5c4c024e4639f09",
    "['pattern']['00_attn']['ln1']":
        "8f6910ffaabc86d6f21a887ec67de385d36f0524bf3f6793617f6358b99f88f2",
    "['pattern']['00_attn']['ln2']":
        "4c50ac007a530fef90b2fd848512d34387cc21bfca982bb68bdcea76854ba90a",
    "['pattern']['00_attn']['mlp']['w1']":
        "a7270e0cdb3a4a2d91e96ec16e81aa69ad9b4df0e75aa5d3906cc2b03f03b540",
    "['pattern']['00_attn']['mlp']['w2']":
        "b663e07d229abfb8db9f2da6e9ec76ca8112d6c8e07b3b68cc93febba268f1b3",
    "['pattern']['00_attn']['mlp']['w3']":
        "941067ce7d6c52660e4345d87ca94f01e59a8a23c75e4842c744eb916e7dab74",
}

# the reference's logits on SEQS, and the float8 control's
LOGITS = {
    "one core":
        "69ffc58428acbedd67feaa1d0d1e3f18d10fc347e9361640c9591142b8b22d6f",
    "more cores":
        "0d515adfbebbe417f29d9fdb42ec32512b78697c82f45bd233235885c8e2f168",
}
CONTROL = {
    "one core":
        "65e52f774205001dedc1cd85267efa8352b8c8371a25ea52461d5f14daace07e",
    "more cores":
        "6c3495b795235a4516dce6e01ab894319adca6c83a1548e24515b6d24abe6c77",
}

# sha256 of ``json.dumps(layout(cj), sort_keys=True)``
LAYOUT = {
    "qwen2-0.5b":
        "330d2f67b2e1813870af9c6ae7e2506dff410ec5c9d676d10f816b11d3797316",
    "qwen1.5-110b-4l":
        "97f2b8cd63cff34ae47950da20dc1e8f81661d019e7e4e1c20e757de2c64a012",
}

CALLS = [(0.0, "prefill", 256, 0, False), (0.0, "prefill", 100, 512, True),
         (0.0, "prefill", 1, 0, True), (0.0, "decode", 1, 30),
         (0.0, "decode", 64, 9000), (0.0, "decode", 16, 40000)]
# [flops, bytes] of each of CALLS
WORK = {
    "qwen2-0.5b": [
        [186036781056.0, 719257600.0], [76675981312.0, 995621888.0],
        [988008448.0, 987936512.0], [990502912.0, 988292864.0],
        [64001179648.0, 1098629120.0], [19247398912.0, 1479471104.0]],
    "qwen1.5-110b-4l": [
        [2787450552320.0, 10880024576.0], [1094847823872.0, 11194728448.0],
        [11183194112.0, 11183095808.0], [11186995200.0, 11183570944.0],
        [716895682560.0, 11331567616.0], [184171888640.0, 11838685184.0]],
}

# two packed sequences, the second across the first query block's end
SEQS = [(list(range(1, 201)), list(range(300, 320))),
        (list(range(7, 187)), list(range(400, 440))),
        ([5, 9, 11], [13, 17])]


def sha(a) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()


def config(path) -> dict:
    return json.loads(path.read_text())


def test_weights_are_those_before_the_move():
    params = weights.make(config(TINY / "bench/configs/tiny.json"), 3)
    got = {jax.tree_util.keystr(p): sha(leaf) for p, leaf in
           jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == LEAVES


def test_reference_and_control_logits_are_those_before_the_move():
    cj = config(TINY / "bench/configs/tiny.json")
    params = weights.make(cj, 3)
    packed = reference.pack(SEQS, 512)
    cores = "one core" if len(os.sched_getaffinity(0)) == 1 \
        else "more cores"
    assert sha(reference.forward(params, cj, packed, 512)) == LOGITS[cores]
    q, scales = reference.quantize(params, cj)
    assert sha(reference.forward(q, cj, packed, 512, scales)) \
        == CONTROL[cores]


@pytest.mark.parametrize("name", sorted(LAYOUT))
def test_layout_and_work_are_those_before_the_move(name):
    cj = config(BENCH / "configs" / f"{name}.json")
    layout = spec.family(cj).layout(cj)
    assert hashlib.sha256(json.dumps(layout, sort_keys=True).encode()
                          ).hexdigest() == LAYOUT[name]
    got = [[work.call_work(cj, c)["flops"], work.call_work(cj, c)["bytes"]]
           for c in CALLS]
    assert got == WORK[name]


def make_root(root, model_type: str) -> None:
    """A benchmark root laid out like ``tests/data/tiny``, with one
    configuration of ``model_type`` and one cell."""
    for kind in ("configs", "cells", "families"):
        (root / "bench" / kind).mkdir(parents=True)
    cj = {"source": "a test-only family", "registered": "kimi-k2-1t-a32b",
          "model_type": model_type, "hidden_size": 64,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "intermediate_size": 96, "moe_intermediate_size": 32,
          "n_routed_experts": 4, "num_experts_per_tok": 2,
          "num_hidden_layers": 3, "first_k_dense_replace": 1,
          "vocab_size": 300, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
          "torch_dtype": "bfloat16",
          "reduced": ["hidden_size", "num_attention_heads",
                      "num_key_value_heads", "intermediate_size",
                      "moe_intermediate_size", "n_routed_experts",
                      "num_experts_per_tok", "num_hidden_layers",
                      "vocab_size"]}
    (root / "bench/configs/toy.json").write_text(json.dumps(cj))
    shutil.copy(TINY / "bench/cells/tiny.chat.json",
                root / "bench/cells/toy.chat.json")
    bench = json.loads((TINY / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "toy", "source": "test",
                         "file": "bench/configs/toy.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": "toy.chat", "config": "toy",
                           "traffic": "chat", "chips": 1, "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def tree_digest(top) -> dict:
    return {str(p): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(top.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts and ".jax_cache" not in p.parts}


def test_a_second_family_joins_by_its_file_alone(tmp_path):
    """One dense layer then two of routed experts, with a router and an
    expert axis Qwen2 lacks, and work that depends on routing."""
    before = tree_digest(BENCH), (spec.ROOT / "BENCHMARK.json").read_bytes()
    make_root(tmp_path, "toy_moe")
    shutil.copy(BENCH / "tests/data/toy_moe.py",
                tmp_path / "bench/families/toy_moe.py")

    cj = spec.load_cell("toy.chat", tmp_path).config
    assert spec.family(cj).__file__ == str(
        tmp_path / "bench/families/toy_moe.py")
    params = weights.make(cj, 2**33 + 1)
    assert params["pattern"]["00_dense"]["mlp"]["w1"].shape == (1, 64, 96)
    assert params["pattern"]["01_moe"]["moe"]["router"].shape == (2, 64, 4)
    assert params["pattern"]["01_moe"]["moe"]["w1"].shape == (2, 4, 64, 32)

    packed = reference.pack(SEQS, 512)
    logits = np.asarray(reference.forward(params, cj, packed, 512))
    assert logits.shape == (reference.WANT_MAX, 300)
    assert np.isfinite(logits).all()
    q, scales = reference.quantize(params, cj)
    assert scales["pattern"]["01_moe"]["moe"]["w1"].shape == (2, 4, 32)
    assert "router" not in scales["pattern"]["01_moe"]["moe"]
    control = np.asarray(reference.forward(q, cj, packed, 512, scales))
    n = packed[5]
    assert 0 < np.abs(control[:n] - logits[:n]).max() < 1.0

    # one token reads the weights of fewer experts than 64 tokens do
    one = work.call_work(cj, (0.0, "prefill", 1, 0, True))
    many = work.call_work(cj, (0.0, "prefill", 64, 0, True))
    assert one["bytes"] < many["bytes"] and one["flops"] < many["flops"]

    cfg = harness.arch_config(cj)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.moe_d_ff,
            cfg.pattern_repeats) == (4, 2, 32, 2)
    unlisted = dict(cj, reduced=[k for k in cj["reduced"]
                                 if k != "n_routed_experts"])
    with pytest.raises(ValueError, match="n_routed_experts"):
        harness.arch_config(unlisted)

    assert (tree_digest(BENCH),
            (spec.ROOT / "BENCHMARK.json").read_bytes()) == before


def test_an_unknown_model_type_names_the_file_looked_for(tmp_path):
    make_root(tmp_path, "no_such_family")
    with pytest.raises(FileNotFoundError,
                       match=r"families/no_such_family\.py"):
        spec.load_cell("toy.chat", tmp_path)
