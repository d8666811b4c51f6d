"""The DeepSeek-V2-Lite EP4 configuration (``dsv2lite-ep4-lm-cls``) against
its plain reference, and the benchmark files that come with it, at a small
size on the CPU.

The program's trunk (``tiers/moe_lm_classifier.py`` through
``models.transformer.forward``) and the reference
(``references/moe_mla_decoder.py``) read the same seeded weights. In
float32 they agree to ``F32_TOL``: the same sums in another order and
another layout (rotate-half against the published interleaved rope).
Each planted fault moves the logits past that tolerance: YaRN left out,
the top-k renormalised, YaRN's mscale^2 left out of the softmax scale, one
held expert skipped, and the projections rounded to fp8.
"""

import dataclasses
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import counters, harness, spec  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.costs import moe_decoder as C  # noqa: E402
from chipbench.references import moe_mla_decoder as R  # noqa: E402
from chipbench.tiers import moe_lm_classifier as M  # noqa: E402
from chipbench.weights import moe_decoder as W  # noqa: E402

CELL = "dsv2lite-imdb-steady"
SMALL = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
             num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=16,
             v_head_dim=16, kv_lora_rank=32, vocab_size=512,
             n_routed_experts=8, experts_held=2, num_experts_per_tok=3)
SEED = 2 ** 31 + 4242
# float32 on both sides: only the order of the sums differs (measured
# 2.6e-6 at logits of magnitude 4)
F32_TOL = 1e-4


def config(**over):
    cfg = spec.load_json(spec.ROOT / "chipbench" / "configs" /
                         "dsv2lite-ep4-lm-cls.json")
    cfg["model"].update(SMALL, **{"torch_dtype": "float32", **over})
    return cfg


def tokens(n=4, s=64, seed=0):
    return np.random.default_rng(seed).integers(
        0, SMALL["vocab_size"], (n, s)).astype(np.int32)


def program(cfg, toks):
    return np.asarray(M.build(cfg, SEED).local_apply(jnp.asarray(toks)))


def gap(cfg, toks, **ref):
    return float(np.abs(program(cfg, toks) - R.logits(
        cfg["model"], SEED, toks, **ref)).max())


def test_program_matches_the_reference_in_f32():
    assert gap(config(), tokens()) < F32_TOL


def test_program_matches_the_reference_at_another_share():
    assert gap(config(first_expert=6), tokens()) < F32_TOL


def test_program_matches_the_reference_in_bf16():
    """Served type: bf16 weights and activations against the float32
    reference on the same bf16 weights; bf16 rounds each layer's
    activations to 8 bits of mantissa (relative 2^-9), so the logits
    (magnitude 4) agree to a few hundredths, not to F32_TOL."""
    cfg = config(torch_dtype="bfloat16")
    toks = tokens()
    got, want = program(cfg, toks), R.logits(cfg["model"], SEED, toks)
    assert np.abs(got - want).max() < 0.1
    assert np.abs(got - want).max() < 0.05 * np.abs(want).max()


def _with_program(monkeypatch, **fields):
    orig = M.model_config
    monkeypatch.setattr(M, "model_config", lambda c: dataclasses.replace(
        orig(c), **fields))


def test_fault_yarn_left_out(monkeypatch):
    _with_program(monkeypatch, rope_scaling=None)
    assert gap(config(), tokens()) > 10 * F32_TOL


def test_fault_topk_renormalised(monkeypatch):
    _with_program(monkeypatch, norm_topk_prob=True)
    assert gap(config(), tokens()) > 10 * F32_TOL


def test_fault_mscale_left_out_of_the_softmax_scale(monkeypatch):
    from repro.models import mla
    monkeypatch.setattr(mla, "yarn_softmax_scale", lambda q, y: q ** -0.5)
    assert gap(config(), tokens()) > 10 * F32_TOL


def test_fault_one_held_expert_skipped(monkeypatch):
    orig = W.program_params

    def skip(seed, sizes, dtype):
        p = orig(seed, sizes, dtype)
        p["blocks"]["moe"]["w_down"] = p["blocks"]["moe"]["w_down"].at[
            :, 1].set(0)
        return p

    monkeypatch.setattr(W, "program_params", skip)
    assert gap(config(), tokens()) > 10 * F32_TOL


def test_fault_projections_rounded_to_fp8():
    cfg, toks = config(), tokens()
    ref = R.logits(cfg["model"], SEED, toks)
    assert np.abs(R.logits(cfg["model"], SEED, toks, quant="fp8")
                  - ref).max() > 10 * F32_TOL


def small_cell():
    cell = spec.cell(CELL)
    cell.config["model"].update(SMALL)
    cell.config["seq_len"] = 32
    cell.knee["knee_rps"] = 60.0
    return cell


def test_a_sound_run_is_correct_and_the_fp8_control_is_not():
    res = harness.run_cell(small_cell(), SEED, 2.0, False,
                           time.perf_counter(), jax.devices(),
                           control="fp8")
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 50
    assert res["control"]["fp8"]["correct"] is False


def test_the_cell_resolves():
    cell = spec.cell(CELL)
    assert cell.config["tier"] == "moe_lm_classifier"
    assert spec.module("tiers", cell.config["tier"]) is M
    assert spec.module("references", cell.config["reference"]) is R
    assert cell.knee["knee_rps"] > 0
    names = {m["name"] for m in cell.per_layer}
    assert {"expert_gmm_roofline", "moe_step_mfu", "local_step_ms",
            "device_idle", "head_gate_roofline"} <= names
    assert "local_step_mfu" not in names
    assert {m["name"] for m in cell.end_to_end} == {"p95_ms", "local_p95_ms",
                                                    "setup_s"}


def test_the_file_keeps_every_published_width():
    bench = spec.benchmark()
    entry = {c["name"]: c for c in bench["configs"]}["dsv2lite-ep4-lm-cls"]
    assert entry["reduced"] == ["experts_held"]
    cfg = spec.load_json(spec.ROOT / entry["file"])
    model = cfg["model"]
    # the published keys stand at the top level too, the same as in 'model'
    assert {k: cfg.get(k) for k in model if k != "torch_dtype"} == {
        k: v for k, v in model.items() if k != "torch_dtype"}
    assert model["n_routed_experts"] == 64 and model["experts_held"] == 16
    assert model["num_hidden_layers"] == 27
    assert model["hidden_size"] == 2048
    assert model["moe_intermediate_size"] == 1408


@pytest.mark.parametrize("key,value", [
    ("model_type", "llama"), ("q_lora_rank", 1536),
    ("scoring_func", "sigmoid"), ("topk_method", "group_limited_greedy"),
    ("n_group", 8), ("hidden_act", "gelu"),
    ("rope_scaling", {"type": "linear", "factor": 4})])
def test_the_tier_refuses_what_it_does_not_implement(key, value):
    cfg = config()
    cfg["model"][key] = value
    with pytest.raises(ValueError):
        M.model_config(cfg)


def test_hand_counts():
    """The EP4 share holds 4.91B parameters (as the program's own pytree
    does), and a dispatch of 32 x 512 tokens costs 41.5 TFLOP at the
    uniform router's 24,576 routed rows a layer."""
    sizes = spec.load_json(spec.ROOT / "chipbench" / "configs" /
                           "dsv2lite-ep4-lm-cls.json")["model"]
    held = sum(C.held_params(sizes).values())
    assert held == pytest.approx(4.91e9, rel=1e-3)
    shapes = W.program_shapes(sizes)
    assert held == sum(x.size for x in jax.tree.leaves(shapes))
    assert C.expected_rows(sizes, 32 * 512) == 24576
    f = C.trunk_flops(sizes, 32, 512)
    assert f["total"] == pytest.approx(41.5e12, rel=2e-3)
    assert f["mla_proj"] == pytest.approx(12.2e12, rel=5e-3)
    assert f["attention"] == pytest.approx(1.16e12, rel=5e-3)
    assert f["dense_mlp"] == pytest.approx(2.2e12, rel=5e-3)
    assert f["shared"] == pytest.approx(14.7e12, rel=5e-3)
    assert f["routed"] == pytest.approx(11.05e12, rel=5e-3)
    assert f["router"] == pytest.approx(0.11e12, rel=2e-2)
    # counted rows replace the expectation
    rows = [24576.0] * 26
    assert C.trunk_flops(sizes, 32, 512, rows)["total"] == f["total"]
    # the product's bytes: 16 held experts' matrices once, rows in and out
    assert C.expert_gmm_bytes(sizes, 0) == 2 * 16 * 3 * 2048 * 1408


PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def fake_run(counts, kernel_s=0.1, step_s=0.4, steps=2, window=(1.0, 11.0)):
    ops = [T.Event("%expert_gmm.3 = bf16[98304,1408] custom-call(...)",
                   2.0, 2.0 + kernel_s / 2),
           T.Event("expert_gmm.7", 5.0, 5.0 + kernel_s / 2),
           # reads the kernel's output: not kernel time
           T.Event("%fusion.331 = bf16[16384,2048] fusion(%expert_gmm.7)",
                   5.5, 5.9),
           T.Event("fusion.1", 3.0, 3.5)]
    modules = [T.Event("jit_step(1)", 2.0 + 3 * i, 2.0 + 3 * i + step_s)
               for i in range(steps)]
    spans = [{"counter": counters.HELD_ROWS, "window": i + 1, "t": 2.0 + i,
              "value": c, "stages": []} for i, c in enumerate(counts)]
    spans.append({"uid": 0, "window": 1, "stages": [["gate", 2.5]]})
    cfg = spec.load_json(spec.ROOT / "chipbench" / "configs" /
                         "dsv2lite-ep4-lm-cls.json")
    return SimpleNamespace(
        trace=T.Trace(ops=[ops], modules=[modules],
                      host=[T.Event(T.WINDOW_SPAN, *window)], window=window),
        spans=spans, opened=window[0], seconds=window[1] - window[0],
        peak=PEAK, batch=32, cell=SimpleNamespace(config=cfg),
        costs={"head_flops": 2.0 * 32 * 2048 * 102400})


def test_expert_gmm_roofline_on_a_synthetic_trace():
    per_expert = [[1536] * 16] * 26                 # 24,576 rows a layer
    run = fake_run([per_expert, per_expert])
    sizes = run.cell.config["model"]
    least = 26 * max(C.routed_flops(sizes, 24576) / PEAK["bf16_flops_per_s"],
                     C.expert_gmm_bytes(sizes, 24576)
                     / PEAK["hbm_bytes_per_s"])
    got = spec.reader("expert_gmm_roofline")(run)
    assert got == pytest.approx(100 * least * 2 / 0.1)


def test_moe_step_mfu_on_a_synthetic_trace():
    run = fake_run([[[1536] * 16] * 26])
    flops = 41.44869933056e12 + 2.0 * 32 * 2048 * 102400
    got = spec.reader("moe_step_mfu")(run)
    assert got == pytest.approx(100 * flops / 0.4 / 197e12, rel=1e-6)


def test_counts_outside_the_window_are_left_out():
    run = fake_run([[[1536] * 16] * 26, [[0] * 16] * 26])
    run.spans[1]["t"] = 20.0                       # after the window
    assert counters.held_rows_per_layer(run) == [24576.0] * 26


@pytest.mark.parametrize("metric", ["expert_gmm_roofline", "moe_step_mfu"])
def test_readers_return_none_without_trace_or_counter(metric):
    read = spec.reader(metric)
    run = fake_run([[[1536] * 16] * 26])
    assert read(SimpleNamespace(**{**vars(run), "trace": None,
                                   "spans": None})) is None
    # a program that counts nothing (the parent's): no counter records
    assert read(SimpleNamespace(**{**vars(run), "spans": run.spans[-1:]})) \
        is None


def test_counter_records_serialise():
    rec = fake_run([[[1] * 16] * 26]).spans[0]
    assert json.loads(json.dumps(rec))["counter"] == "moe.held_rows"
