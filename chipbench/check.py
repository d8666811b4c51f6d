"""The comparison that decides ``correct``.

Numbers compared, each against its limit from the configuration file
(``limits``); a run is correct when every number is at or under its limit
and every request due in the window was answered:

* ``cascade_mismatch`` (exact, limit 0): requests whose path through the
  cascade differs from what it must be. Per dispatch window, exactly
  ``min(capacity, rows)`` rows escalate (capacity mode, no threshold).
  A trusted row is ``LOCAL``. An escalated row's answer is recomputed from
  the modelled remote: trusted by the 2nd-level supervisor iff its
  confidence exceeds ``t_remote``, then served (``REMOTE`` or ``CACHED``)
  with the remote's answer token, else ``REJECTED`` to the fallback.
* ``escalation_order`` (exact, limit 0): dispatch windows in which an
  escalated row's gate confidence lies above a trusted row's. The engine
  escalates the first rows of the gate's ascending ranking and reports
  the confidences it ranked, so each window's escalated rows are its
  lowest-confidence ones; with ``conf_err`` this holds the escalation
  choice to the reference's.
* ``answer_gap``: over the sampled windows' trusted rows, the widest gap
  by which the served token's reference logit lies below the reference's
  best (logit units). Covers the trunk and the fused head's argmax.
* ``conf_err`` and ``conf_err_rms``: over every sampled row, the largest
  and the root-mean-square relative gap between the gate's confidence and
  the reference's max-softmax.
* ``escalation_inversion``: over the sampled windows, how far the
  reference ranks an escalated row above a trusted one, relative to the
  trusted row's confidence (0 when the escalation set is the reference's
  lowest-confidence set).
* ``pallas_gate_missing`` (limit 0, on a TPU): the compiled gated step
  holds no Pallas kernel.

The sampled windows are drawn from the seed among the windows dispatched
for requests due in the measured window; the reference runs over their
rows once the program's state is freed.
"""

from __future__ import annotations

import numpy as np

SERVED = ("REMOTE", "CACHED")


def softmax_max(logits: np.ndarray) -> np.ndarray:
    z = logits.astype(np.float64)
    z = z - z.max(-1, keepdims=True)
    return 1.0 / np.exp(z).sum(-1)


def windows_of(records: list[dict]) -> list[list[dict]]:
    """Requests grouped by dispatch window. Rows of one window share one
    dispatch stamp, recovered as due time + queue wait, which rounding
    can move by far less than the milliseconds between two dispatches."""
    out: list[list[dict]] = []
    last = None
    for r in sorted((r for r in records if r["answered"]),
                    key=lambda r: r["t_disp"]):
        if last is None or r["t_disp"] - last > 1e-4:
            out.append([])
        out[-1].append(r)
        last = r["t_disp"]
    return out


def cascade_mismatch(records: list[dict], windows: list[list[dict]],
                     capacity: int, remote, tokens: np.ndarray,
                     t_remote: float) -> int:
    bad = 0
    for w in windows:
        esc = sum(r["source"] != "local" for r in w)
        if esc != min(capacity, len(w)):
            bad += len(w)
    for r in records:
        if not r["answered"]:
            continue
        if r["source"] == "local":
            bad += r["disposition"] != "LOCAL"
            continue
        lg = remote.row_logits(tokens[r["content"]])
        conf = float(softmax_max(lg[None])[0])
        if conf > t_remote:
            ok = (r["source"] == "remote" and r["disposition"] in SERVED
                  and r["prediction"] == int(lg.argmax()))
        else:
            ok = (r["source"] == "fallback"
                  and r["disposition"] == "REJECTED")
        ok &= abs(r["remote_conf"] - conf) <= 1e-4 * conf
        bad += not ok
    return bad


def escalation_order(windows: list[list[dict]]) -> int:
    bad = 0
    for w in windows:
        esc = [r["local_conf"] for r in w if r["source"] != "local"]
        kept = [r["local_conf"] for r in w if r["source"] == "local"]
        bad += bool(esc and kept and max(esc) > min(kept))
    return bad


def sample_windows(windows: list[list[dict]], n: int, seed: int
                   ) -> list[list[dict]]:
    full = [w for w in windows if len(w) > 1]
    rng = np.random.default_rng([int(seed), 3])
    pick = rng.choice(len(full), size=min(n, len(full)), replace=False)
    return [full[i] for i in sorted(pick)]


def reference_numbers(sample: list[list[dict]], ref_logits: np.ndarray
                      ) -> dict:
    """answer_gap, conf_err(_rms), escalation_inversion of the sampled
    windows;
    ``ref_logits`` holds one row per sampled request, in sample order."""
    conf = softmax_max(ref_logits)
    best = ref_logits.max(-1)
    gaps, errs, inv = [], [], 0.0
    i = 0
    for w in sample:
        rows = range(i, i + len(w))
        i += len(w)
        esc, kept = [], []
        for j, r in zip(rows, w):
            errs.append(abs(r["local_conf"] - conf[j]) / conf[j])
            if r["source"] == "local":
                kept.append(conf[j])
                gaps.append(float(best[j] - ref_logits[j, r["prediction"]]))
            else:
                esc.append(conf[j])
        if esc and kept:
            inv = max(inv, (max(esc) - min(kept)) / min(kept))
    errs = np.asarray(errs)
    return {"answer_gap": max(gaps, default=0.0),
            "conf_err": float(errs.max(initial=0.0)),
            "conf_err_rms": float(np.sqrt(np.mean(errs ** 2)))
            if errs.size else 0.0,
            "escalation_inversion": float(inv)}


def as_served(sample: list[list[dict]], logits: np.ndarray,
              capacity: int) -> list[list[dict]]:
    """The sampled windows as a tier computing ``logits`` would serve
    them: its confidences and answers, and per window its
    ``min(capacity, rows)`` lowest-confidence rows escalated."""
    conf = softmax_max(logits)
    out, i = [], 0
    for w in sample:
        c = conf[i:i + len(w)]
        esc = set(np.argsort(c, kind="stable")[:min(capacity, len(w))])
        out.append([{"local_conf": float(c[j]),
                     "prediction": int(logits[i + j].argmax()),
                     "source": "remote" if j in esc else "local"}
                    for j in range(len(w))])
        i += len(w)
    return out


def verdict(numbers: dict, limits: dict, unanswered: int
            ) -> tuple[bool, dict]:
    """Every number the configuration gives a limit is compared."""
    checks = {k: {"value": numbers[k], "limit": v} for k, v in limits.items()}
    ok = unanswered == 0 and all(c["value"] <= c["limit"]
                                 for c in checks.values())
    return ok, checks


def control_verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """The verdict on the control, the reference in a lower precision put
    in the program's place: ``verdict`` over the numbers that
    ``reference_numbers`` reads of the rows it served, against the same
    limits. The exact numbers (``cascade_mismatch``,
    ``pallas_gate_missing``) judge the program's cascade, transport and
    compiled step, which the control does not replace."""
    ref = {k: v for k, v in limits.items() if k in numbers}
    if not ref:
        raise ValueError("no limit applies to the control's numbers")
    return verdict(numbers, ref, 0)
