"""Pallas kernel validation: every kernel is swept over shapes/dtypes and
asserted allclose against its ref.py pure-jnp oracle, with the kernel body
executed in interpret mode on the CPU. tests/test_tpu_compile.py compiles
the serve path's gate kernels for a described TPU v5e."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.supervisors import SOFTMAX_SUPERVISORS
from repro.kernels.confidence_gate.ops import confidence_gate
from repro.kernels.confidence_gate.ref import confidence_gate_ref
from repro.kernels.decode_attention.ops import decode_attn
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.maxconf.ops import maxconf
from repro.kernels.maxconf.ref import maxconf_ref
from repro.kernels.mdsa.ops import mdsa_distance
from repro.kernels.mdsa.ref import mdsa_ref
from repro.kernels.rwkv6_scan.ops import rwkv6_time_mix_scan
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref

KEY = jax.random.PRNGKey(0)


def rnd(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ----------------------------------------------------------------- maxconf

@pytest.mark.parametrize("b,v", [(4, 512), (8, 2048), (3, 1000), (16, 4096),
                                 (1, 5000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_maxconf_matches_ref(b, v, dtype):
    logits = rnd(KEY, (b, v), dtype, scale=4.0)
    got = maxconf(logits, force_pallas=True, interpret=True)
    want = maxconf_ref(logits)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_array_equal(np.asarray(got["prediction"]),
                                  np.asarray(want["prediction"]))
    for k in ("max_softmax", "pcs", "entropy"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=tol, atol=tol, err_msg=k)


def test_maxconf_extreme_logits_stable():
    logits = jnp.array([[1e4, -1e4, 0.0] + [0.0] * 125])
    got = maxconf(logits, force_pallas=True, interpret=True)
    assert bool(jnp.all(jnp.isfinite(got["max_softmax"])))
    np.testing.assert_allclose(float(got["max_softmax"][0]), 1.0, atol=1e-5)


# ---------------------------------------------------------- confidence gate

@pytest.mark.parametrize("supervisor", sorted(SOFTMAX_SUPERVISORS))
@pytest.mark.parametrize("b,v", [(8, 128), (4, 512), (3, 100), (16, 1000)])
def test_confidence_gate_matches_ref(supervisor, b, v):
    logits = rnd(jax.random.fold_in(KEY, b * v), (b, v), scale=4.0)
    got = confidence_gate(logits, supervisor=supervisor,
                          force_pallas=True, interpret=True)
    want = confidence_gate_ref(logits, supervisor=supervisor)
    np.testing.assert_allclose(np.asarray(got["conf"]),
                               np.asarray(want["conf"]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got["pred"]),
                                  np.asarray(want["pred"]))
    np.testing.assert_array_equal(np.asarray(got["idx"]),
                                  np.asarray(want["idx"]))


@pytest.mark.parametrize("supervisor", sorted(SOFTMAX_SUPERVISORS))
def test_confidence_gate_threshold_and_validity(supervisor):
    """t_local gates eligibility; rows >= n_valid (padding) never appear;
    unused slots are -1; idx ascends by confidence."""
    b, v = 12, 256
    logits = rnd(jax.random.fold_in(KEY, 99), (b, v), scale=4.0)
    conf = np.asarray(SOFTMAX_SUPERVISORS[supervisor](logits))
    n_valid, k = 9, 6
    # threshold between two rows' confidences, never ON one (a t equal to
    # a row's exact conf would flip on last-ulp kernel/ref differences)
    srt = np.sort(conf[:n_valid])
    t = float(0.5 * (srt[3] + srt[4]))
    got = confidence_gate(logits, t, n_valid, supervisor=supervisor, k=k,
                          force_pallas=True, interpret=True)
    want = confidence_gate_ref(logits, t, n_valid, supervisor=supervisor,
                               k=k)
    np.testing.assert_array_equal(np.asarray(got["idx"]),
                                  np.asarray(want["idx"]))
    idx = np.asarray(got["idx"])
    sel = idx[idx >= 0]
    assert (sel < n_valid).all()
    assert (conf[sel] < t).all()
    assert (np.diff(conf[sel]) >= 0).all()          # ascending confidence
    # every eligible valid row not selected has conf >= the selected max
    rest = np.setdiff1d(np.arange(n_valid), sel)
    if sel.size and sel.size < k:
        assert (conf[rest] >= t).all()              # gate exhausted


def test_confidence_gate_extreme_logits_stable():
    logits = jnp.array([[1e4, -1e4, 0.0] + [0.0] * 125] * 8)
    for sup in sorted(SOFTMAX_SUPERVISORS):
        got = confidence_gate(logits, supervisor=sup, force_pallas=True,
                              interpret=True)
        assert bool(jnp.all(jnp.isfinite(got["conf"]))), sup


def test_confidence_gate_callable_supervisor_falls_back(monkeypatch):
    """Callable supervisors (paper §4.2) take the jnp path everywhere:
    scoring and selection, even where the backend is a TPU."""
    from repro.kernels.confidence_gate import ops as gate_ops

    def no_kernel(*a, **kw):
        raise AssertionError("a callable supervisor reached a Pallas kernel")

    monkeypatch.setattr(gate_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(gate_ops, "gate_scores_pallas", no_kernel)
    monkeypatch.setattr(gate_ops, "select_pallas", no_kernel)

    def margin(logits):
        top2 = jax.lax.top_k(logits, 2)[0]
        return top2[..., 0] - top2[..., 1]

    logits = rnd(KEY, (8, 64), scale=2.0)
    got = confidence_gate(logits, supervisor=margin, k=4, force_pallas=True)
    want = confidence_gate_ref(logits, supervisor=margin, k=4)
    np.testing.assert_allclose(np.asarray(got["conf"]),
                               np.asarray(want["conf"]), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got["idx"]),
                                  np.asarray(want["idx"]))


def test_confidence_gate_early_emit_fires_inside_jit():
    """The early-emit host callback of the gated local step must fire
    exactly once per gate call from INSIDE a jitted computation, tagged
    with the dispatch seq and carrying the same conf/pred/idx the gate
    returns."""
    from repro.serving.engine import make_gated_local_step
    logits = rnd(jax.random.fold_in(KEY, 7), (8, 64), scale=4.0)
    fired = []

    def emit(tag, conf, pred, idx):
        fired.append((int(tag), np.asarray(pred).copy(),
                      np.asarray(idx).copy()))

    out = jax.jit(make_gated_local_step(lambda x: x, emit=emit))(
        logits, 0.5, 8, 11)
    jax.block_until_ready(out["pred"])
    assert len(fired) == 1
    tag, pred, idx = fired[0]
    assert tag == 11
    np.testing.assert_array_equal(pred, np.asarray(out["pred"]))
    np.testing.assert_array_equal(idx, np.asarray(out["idx"]))


# --------------------------------------------------------- fused head->gate

def _fused_mats(seed, b, d, v):
    k1 = jax.random.fold_in(KEY, seed)
    h = rnd(k1, (b, d), scale=1.0)
    w = rnd(jax.random.fold_in(k1, 1), (d, v), scale=1.0 / np.sqrt(d))
    bias = rnd(jax.random.fold_in(k1, 2), (v,), scale=0.1)
    return h, w, bias


@pytest.mark.parametrize("supervisor", sorted(SOFTMAX_SUPERVISORS))
@pytest.mark.parametrize("b,d,v", [(8, 128, 512), (3, 64, 100),
                                   (12, 96, 640)])
def test_fused_head_gate_matches_ref(supervisor, b, d, v):
    """Pallas body (interpret mode) vs the jnp oracle. pred/idx must be
    bitwise identical; conf tolerates summation-order noise from folding
    the vocab in 128-wide blocks (neg_entropy amplifies it through the
    cancellation in its epilogue, hence the 2e-4 rtol)."""
    from repro.kernels.fused_head_gate.ops import fused_head_gate
    from repro.kernels.fused_head_gate.ref import fused_head_gate_ref
    h, w, bias = _fused_mats(b * d * v, b, d, v)
    got = fused_head_gate(h, w, bias, supervisor=supervisor,
                          force_pallas=True, interpret=True)
    want = fused_head_gate_ref(h, w, bias, supervisor=supervisor)
    np.testing.assert_allclose(np.asarray(got["conf"]),
                               np.asarray(want["conf"]),
                               rtol=2e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got["pred"]),
                                  np.asarray(want["pred"]))
    np.testing.assert_array_equal(np.asarray(got["idx"]),
                                  np.asarray(want["idx"]))


def test_fused_head_gate_matches_composed_gate():
    """Fusing the projection must not change the gate's contract: the
    ref oracle equals confidence_gate_ref over the composed logits, and
    threshold/validity/k semantics carry over unchanged."""
    from repro.kernels.fused_head_gate.ops import fused_head_gate
    from repro.kernels.fused_head_gate.ref import fused_head_gate_ref
    b, d, v = 12, 64, 256
    h, w, bias = _fused_mats(5, b, d, v)
    logits = h @ w + bias
    for sup in sorted(SOFTMAX_SUPERVISORS):
        conf = np.asarray(SOFTMAX_SUPERVISORS[sup](logits))
        srt = np.sort(conf[:9])
        t = float(0.5 * (srt[3] + srt[4]))
        fused = fused_head_gate_ref(h, w, bias, t, 9, supervisor=sup, k=6)
        composed = confidence_gate_ref(logits, t, 9, supervisor=sup, k=6)
        np.testing.assert_array_equal(np.asarray(fused["idx"]),
                                      np.asarray(composed["idx"]), sup)
        np.testing.assert_array_equal(np.asarray(fused["pred"]),
                                      np.asarray(composed["pred"]), sup)
        # pallas body honours the same threshold/validity contract
        pal = fused_head_gate(h, w, bias, t, 9, supervisor=sup, k=6,
                              force_pallas=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(pal["idx"]),
                                      np.asarray(composed["idx"]), sup)


def test_fused_local_head_is_drop_in_local_apply():
    """FusedLocalHead composes trunk -> projection when called like a
    plain local_apply (the engine's non-fused paths and billing-parity
    A/B rely on this)."""
    from repro.kernels.fused_head_gate.ops import FusedLocalHead
    b, d, v = 4, 32, 64
    h, w, bias = _fused_mats(6, b, d, v)
    head = FusedLocalHead(trunk=lambda x: 2.0 * x, w=w, bias=bias)
    np.testing.assert_allclose(np.asarray(head(h)),
                               np.asarray((2.0 * h) @ w + bias),
                               rtol=1e-5, atol=1e-5)


def test_fused_head_gate_dim_mismatch_raises():
    from repro.kernels.fused_head_gate.ops import fused_head_gate
    h, w, _ = _fused_mats(8, 4, 32, 64)
    with pytest.raises(ValueError):
        fused_head_gate(h, w[:16], None)


# -------------------------------------------------------------------- mdsa

@pytest.mark.parametrize("b,d", [(8, 64), (128, 128), (100, 200), (1, 32)])
def test_mdsa_matches_ref(b, d):
    k1, k2 = jax.random.split(KEY)
    x = rnd(k1, (b, d))
    mean = rnd(k2, (d,))
    a = rnd(jax.random.fold_in(KEY, 7), (d, d), scale=0.3)
    prec = a @ a.T + jnp.eye(d)              # SPD
    got = mdsa_distance(x, mean, prec, force_pallas=True, interpret=True)
    want = mdsa_ref(x, mean, prec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------- flash attention

@pytest.mark.parametrize("t,h,kh,hd", [(256, 4, 4, 64), (512, 8, 2, 64),
                                       (256, 4, 1, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(t, h, kh, hd, causal):
    ks = jax.random.split(KEY, 3)
    q = rnd(ks[0], (2, t, h, hd))
    k = rnd(ks[1], (2, t, kh, hd))
    v = rnd(ks[2], (2, t, kh, hd))
    got = attention(q, k, v, causal=causal, force_pallas=True,
                    interpret=True)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_sliding_window():
    ks = jax.random.split(KEY, 3)
    q = rnd(ks[0], (1, 512, 4, 64))
    k = rnd(ks[1], (1, 512, 4, 64))
    v = rnd(ks[2], (1, 512, 4, 64))
    got = attention(q, k, v, causal=True, window=128, force_pallas=True,
                    interpret=True)
    want = attention_ref(q, k, v, causal=True, window=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    ks = jax.random.split(KEY, 3)
    q = rnd(ks[0], (1, 256, 4, 64), dtype)
    k = rnd(ks[1], (1, 256, 4, 64), dtype)
    v = rnd(ks[2], (1, 256, 4, 64), dtype)
    got = attention(q, k, v, causal=True, force_pallas=True, interpret=True)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


# -------------------------------------------------------- decode attention

@pytest.mark.parametrize("b,s,h,kh,hd", [(2, 1024, 8, 2, 64),
                                         (4, 2048, 4, 4, 64),
                                         (1, 512, 16, 2, 128)])
def test_decode_attention_matches_ref(b, s, h, kh, hd):
    ks = jax.random.split(KEY, 3)
    q = rnd(ks[0], (b, h, hd))
    kc = rnd(ks[1], (b, s, kh, hd))
    vc = rnd(ks[2], (b, s, kh, hd))
    kv_len = jnp.asarray(
        np.random.default_rng(0).integers(1, s + 1, (b,)), jnp.int32)
    got = decode_attn(q, kc, vc, kv_len, force_pallas=True, interpret=True)
    want = decode_attention_ref(q, kc, vc, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------- rwkv scan

@pytest.mark.parametrize("t,h,m", [(128, 2, 32), (256, 4, 64), (64, 1, 16)])
def test_rwkv6_scan_matches_ref(t, h, m):
    ks = jax.random.split(KEY, 5)
    b = 2
    r = rnd(ks[0], (b, t, h, m), scale=0.5)
    k = rnd(ks[1], (b, t, h, m), scale=0.5)
    v = rnd(ks[2], (b, t, h, m), scale=0.5)
    w = jax.nn.sigmoid(rnd(ks[3], (b, t, h, m)))   # decay in (0, 1)
    u = rnd(ks[4], (h, m), scale=0.5)
    s0 = jnp.zeros((b, h, m, m), jnp.float32)
    got_y, got_s = rwkv6_time_mix_scan(r, k, v, w, u, s0, force_pallas=True,
                                       interpret=True)
    want_y, want_s = rwkv6_scan_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=2e-3, atol=2e-3)


def test_rwkv6_scan_state_carry():
    """Scanning two halves with carried state == scanning the whole."""
    ks = jax.random.split(KEY, 5)
    b, t, h, m = 1, 64, 2, 16
    r = rnd(ks[0], (b, t, h, m), scale=0.5)
    k = rnd(ks[1], (b, t, h, m), scale=0.5)
    v = rnd(ks[2], (b, t, h, m), scale=0.5)
    w = jax.nn.sigmoid(rnd(ks[3], (b, t, h, m)))
    u = rnd(ks[4], (h, m), scale=0.5)
    s0 = jnp.zeros((b, h, m, m), jnp.float32)
    y_full, s_full = rwkv6_scan_ref(r, k, v, w, u, s0)
    half = t // 2
    y1, s1 = rwkv6_scan_ref(r[:, :half], k[:, :half], v[:, :half],
                            w[:, :half], u, s0)
    y2, s2 = rwkv6_scan_ref(r[:, half:], k[:, half:], v[:, half:],
                            w[:, half:], u, s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=1e-5, atol=1e-5)
