"""``correct`` comes out false when the timed path is broken underneath.

Each test drives the rest of a benchmark run (the harness's look for a
chip skipped) on the CPU, at a small size of the cell's configuration,
with one fault planted in the path the window serves, and reads the
verdict: an answer altered where it is produced (the fused head + gate's
prediction), half of the batch left out (rows past the middle replaced by
the mean of the rest), the most confident rows escalated in place of the
least, and a remote answer altered. The cells have no
training state and no exchange between chips, so those faults do not
apply. A last test puts the plain reference, computed in fp8, in the
program's place and sees the check's own comparison judge it not correct.
"""

import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import check, harness, spec  # noqa: E402
from chipbench.remote import ModelledRemote  # noqa: E402
from chipbench.tiers import lm_classifier  # noqa: E402

SMALL = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
SEED = 2 ** 31 + 4242


def small_cell(name="yi6b-imdb-steady"):
    cell = spec.cell(name)
    cell.config["model"].update(SMALL)
    cell.config["seq_len"] = 32
    cell.knee["knee_rps"] = 60.0
    return cell


def run(cell, seconds=2.0):
    import jax
    return harness.run_cell(cell, SEED, seconds, False, time.perf_counter(),
                            jax.devices())


def test_sound_run_matches_the_cascade():
    res = run(small_cell())
    assert res["attempted"] > 50 and res["failed"] == 0
    assert res["checks"]["cascade_mismatch"]["value"] == 0
    assert res["checks"]["escalation_inversion"]["value"] < 0.05
    assert res["checks"]["answer_gap"]["value"] < 0.1


def test_answer_altered_at_the_gate(monkeypatch):
    from repro.serving import engine
    orig = engine.fused_head_gate

    def wrong(*a, **k):
        out = orig(*a, **k)
        return {**out, "pred": (out["pred"] + 1) % SMALL["vocab_size"]}

    monkeypatch.setattr(engine, "fused_head_gate", wrong)
    res = run(small_cell())
    assert res["correct"] is False
    assert res["checks"]["answer_gap"]["value"] > \
        res["checks"]["answer_gap"]["limit"]


def test_half_the_batch_left_out(monkeypatch):
    from repro.kernels.fused_head_gate.ops import FusedLocalHead
    orig = lm_classifier.build

    def half(config, seed):
        tier = orig(config, seed)
        lh = tier.local_apply

        def trunk(tokens):
            h = lh.trunk(tokens)
            m = h.shape[0] // 2
            return h.at[m:].set(h[:m].mean(0).astype(h.dtype))

        tier.local_apply = FusedLocalHead(trunk, lh.w, lh.bias)
        return tier

    monkeypatch.setattr(lm_classifier, "build", half)
    res = run(small_cell())
    assert res["correct"] is False
    assert res["checks"]["conf_err"]["value"] > \
        res["checks"]["conf_err"]["limit"]


def test_most_confident_rows_escalated(monkeypatch):
    from repro.kernels.fused_head_gate import ops
    orig = ops.select_candidates
    monkeypatch.setattr(ops, "select_candidates",
                        lambda conf, *a, **k: orig(1.0 - conf, *a, **k))
    res = run(small_cell())
    assert res["correct"] is False
    assert res["checks"]["escalation_order"]["value"] > 0


def test_remote_answer_altered(monkeypatch):
    orig = ModelledRemote.__call__
    monkeypatch.setattr(ModelledRemote, "__call__",
                        lambda self, b: np.roll(orig(self, b), 1, axis=-1))
    res = run(small_cell())
    assert res["correct"] is False
    assert res["checks"]["cascade_mismatch"]["value"] > 0


def test_fp8_reference_in_the_programs_place_reads_higher():
    import jax
    cell = small_cell()
    res = harness.run_cell(cell, SEED, 2.0, False, time.perf_counter(),
                           jax.devices(), control="fp8")
    ctl = res["control"]["fp8"]
    assert res["correct"] is True
    assert ctl["correct"] is False
    assert set(ctl["checks"]) == {"answer_gap", "conf_err", "conf_err_rms",
                                  "escalation_inversion"}
    prog = res["numbers"]["conf_err_rms"]
    assert ctl["checks"]["conf_err_rms"]["value"] > 2 * prog


def test_as_served_escalates_the_lowest_rows():
    logits = np.array([[3.0, 0.0], [0.1, 0.0], [5.0, 0.0]])
    served = check.as_served([[{}, {}, {}]], logits, capacity=1)[0]
    assert [r["source"] for r in served] == ["local", "remote", "local"]
    assert all(r["prediction"] == 0 for r in served)


def test_windows_group_by_dispatch_stamp_despite_rounding():
    base = 12.3456785
    recs = [{"answered": True, "t_disp": base + d}
            for d in (-3e-11, 2e-11, 0.0, 0.25, 0.25 + 1e-11)]
    recs.append({"answered": False})
    assert [len(w) for w in check.windows_of(recs)] == [3, 2]


def test_control_verdict_judges_the_reference_numbers_only():
    limits = {"cascade_mismatch": 0, "pallas_gate_missing": 0,
              "answer_gap": 0.1, "conf_err": 0.2}
    ok, checks = check.control_verdict({"answer_gap": 0.05, "conf_err": 0.3},
                                       limits)
    assert ok is False and set(checks) == {"answer_gap", "conf_err"}
    ok, _ = check.control_verdict({"answer_gap": 0.05, "conf_err": 0.2},
                                  limits)
    assert ok is True
