"""Operation and byte counts of the benchmark's kernels and steps, from
the shapes ``jax.eval_shape`` gives the seeded weights, against hand
counts of both configurations."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import spec  # noqa: E402
from chipbench.costs import dense_decoder, fused_head_gate  # noqa: E402
from chipbench.weights import dense_decoder as W  # noqa: E402


def _hand_trunk(d, f, layers, h, kv, hd, batch, seq):
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    matmuls = 2 * per_layer * layers * batch * seq
    pairs = seq * (seq + 1) // 2
    attn = 2 * 2 * h * hd * pairs * batch * layers
    return matmuls + attn


@pytest.mark.parametrize("cfg,hand,approx", [
    ("yi6b-lm-cls", _hand_trunk(4096, 11008, 32, 32, 4, 128, 32, 512),
     1.84e14),
    ("danube-lm-cls", _hand_trunk(2560, 6912, 24, 32, 8, 80, 32, 256),
     2.76e13),
])
def test_trunk_flops(cfg, hand, approx):
    config = spec.load_json(spec.HERE / "configs" / f"{cfg}.json")
    sizes = config["model"]
    shapes = W.program_shapes(sizes)
    got = dense_decoder.trunk_flops(shapes, sizes, 32, config["seq_len"])
    assert got == hand
    assert got == pytest.approx(approx, rel=0.01)


def test_matrix_params_excludes_embedding_and_head():
    sizes = spec.load_json(spec.HERE / "configs" / "yi6b-lm-cls.json")[
        "model"]
    shapes = W.program_shapes(sizes)
    n = dense_decoder.matrix_params(shapes)
    assert n == 32 * (2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008)
    total = sum(x.size for x in __import__("jax").tree.leaves(shapes))
    assert total == pytest.approx(6.06e9, rel=0.01)


def test_window_caps_visible_pairs():
    assert dense_decoder.visible_pairs(4) == 10
    assert dense_decoder.visible_pairs(4, window=2) == 7
    assert dense_decoder.visible_pairs(256, window=4096) == 256 * 257 // 2


@pytest.mark.parametrize("b,d,v,mbytes,gflop", [
    (32, 4096, 64000, 524.6, 16.78),
    (32, 2560, 32000, 164.0, 5.24),
])
def test_head_gate_cost(b, d, v, mbytes, gflop):
    flops, nbytes = fused_head_gate.cost(b, d, v)
    assert flops == 2 * b * d * v
    assert flops / 1e9 == pytest.approx(gflop, rel=1e-3)
    assert nbytes / 1e6 == pytest.approx(mbytes, rel=1e-3)
    # bound by bytes on a v5e: flop/byte far under 197e12 / 819e9
    assert flops / nbytes < 197e12 / 819e9
