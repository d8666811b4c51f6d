"""Compile the serve path's kernels and gated step for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, installed with JAX, lowers
and compiles for a ``v5e:2x2`` topology that is described, not attached.
That catches what Pallas interpret mode cannot — a block layout Mosaic
refuses, a kernel the SPMD partitioner cannot split — at every later
change, for no chip time. The topology is described inside a fixture (the
TPU library may be loaded by one process at a time, and only a test that
runs may load it); the program's backend checks still see the CPU, so the
tests steer the gate onto its Pallas path themselves.
"""

from __future__ import annotations

import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

SERVE_BATCH = 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the gate ops take their Pallas path, as on the chip."""
    from repro.kernels.confidence_gate import ops as gate_ops
    from repro.kernels.fused_head_gate import ops as head_ops
    monkeypatch.setattr(gate_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(head_ops, "_on_tpu", lambda: True)


def _hlo(lowered) -> str:
    return lowered.compile().as_text()


@pytest.mark.parametrize("b,c", [(SERVE_BATCH, 128), (128, 64000)])
def test_confidence_gate_compiles(one_chip, on_tpu, b, c):
    from repro.kernels.confidence_gate.ops import confidence_gate
    spec = jax.ShapeDtypeStruct((b, c), jnp.float32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    hlo = _hlo(jax.jit(lambda x, t, n: confidence_gate(
        x, t, n, supervisor="max_softmax")).lower(spec, t, n))
    assert "tpu_custom_call" in hlo


def test_fused_head_gate_compiles(one_chip, on_tpu):
    """Yi-6B's head width: [32, 4096] x [4096, 64000] in bf16."""
    from repro.kernels.fused_head_gate.ops import fused_head_gate
    b, d, c = SERVE_BATCH, 4096, 64000

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = _hlo(jax.jit(lambda h, w, bias, t, n: fused_head_gate(
        h, w, bias, t, n, supervisor="max_softmax")).lower(
            sds((b, d), jnp.bfloat16), sds((d, c), jnp.bfloat16),
            sds((c,), jnp.float32), sds((), jnp.float32),
            sds((), jnp.int32)))
    assert "tpu_custom_call" in hlo


def _served_local_tier():
    """The serve driver's local surrogate and its input shape: [batch,
    seq // 2] int32 tokens."""
    from repro.models import surrogate as S
    scfg = S.SurrogateConfig("local", vocab_size=128, max_len=24,
                             d_model=32, num_heads=2, d_ff=32,
                             num_classes=8, dropout=0.0)
    params = S.init_params(scfg, jax.random.PRNGKey(0))
    return (lambda tk: S.apply(scfg, params, tk)), (SERVE_BATCH, 24)


def test_gated_local_step_holds_pallas_gate(one_chip, on_tpu):
    from repro.serving.engine import make_gated_local_step
    local_apply, shape = _served_local_tier()
    step = jax.jit(make_gated_local_step(local_apply))
    hlo = _hlo(step.lower(
        jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)))
    assert hlo.count("tpu_custom_call") >= 2        # scoring + selection


def test_gated_step_keeps_the_names_the_benchmark_matches(one_chip, on_tpu):
    """``chipbench/steps.py`` finds the gated step's program by
    ``STEP_MODULES`` and the fused head gate kernel by the substring
    ``HEAD_GATE_KERNEL`` of its op name: only the scoring kernel carries
    it, not the selection kernel beside it."""
    from chipbench.steps import HEAD_GATE_KERNEL, STEP_MODULES
    from repro.kernels.fused_head_gate.ops import FusedLocalHead
    from repro.serving.engine import make_gated_local_step
    d, c = 128, 512
    rng = np.random.default_rng(0)
    emb = jnp.asarray(rng.normal(size=(64, d)), jnp.bfloat16)
    head = FusedLocalHead(trunk=lambda tk: emb[tk].mean(1),
                          w=jnp.asarray(rng.normal(size=(d, c)),
                                        jnp.bfloat16))
    step = jax.jit(make_gated_local_step(head, emit=lambda *a: None))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    hlo = _hlo(step.lower(
        jax.ShapeDtypeStruct((SERVE_BATCH, 16), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
        scalar, scalar))
    assert re.match(r"HloModule (\w+),", hlo).group(1) in STEP_MODULES
    kernels = re.findall(r"^\s*%?(\S+) = .*custom_call_target=\"tpu_custom_call\"",
                         hlo, re.M)
    assert len(kernels) == 2, kernels                # scoring + selection
    assert [k for k in kernels if HEAD_GATE_KERNEL in k] == [
        k for k in kernels if k.startswith("head_gate_scores_pallas")]
    assert sum(HEAD_GATE_KERNEL in k for k in kernels) == 1


def test_gated_local_step_callable_supervisor_takes_jnp(one_chip, on_tpu):
    """A callable supervisor (paper §4.2, e.g. a bound MDSA) scores and
    selects in jnp on the chip: no Pallas kernel in the served step."""
    from repro.serving.engine import make_gated_local_step
    local_apply, shape = _served_local_tier()

    def margin(logits):
        top2 = jax.lax.top_k(logits, 2)[0]
        return top2[..., 0] - top2[..., 1]

    step = jax.jit(make_gated_local_step(local_apply, supervisor=margin))
    hlo = _hlo(step.lower(
        jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)))
    assert "tpu_custom_call" not in hlo


def test_data_parallel_gated_step_compiles_on_four_chips(topo, on_tpu):
    """ServeConfig.data_parallel on a 4-chip mesh: the gate runs under
    shard_map per row shard, selection over the gathered confidences."""
    from repro.serving.engine import make_gated_local_step
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    local_apply, shape = _served_local_tier()
    step = jax.jit(make_gated_local_step(local_apply, mesh=mesh))
    rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    compiled = step.lower(
        jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # the gathered confidences: XLA may lower a small all-gather as an
    # all-reduce of the zero-padded shards
    assert re.search(r"all-(gather|reduce)", hlo)
    out = compiled.output_shardings
    assert out["conf"].spec == P("data") and out["idx"].spec == P()


def test_data_parallel_gated_step_with_early_emit_compiles(topo, on_tpu):
    """Continuous batching arms early emit on the chip: the host callback
    runs after the shard_map, on the global triple."""
    from repro.serving.engine import make_gated_local_step
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    local_apply, shape = _served_local_tier()
    step = jax.jit(make_gated_local_step(local_apply, mesh=mesh,
                                         emit=lambda *a: None))
    rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    hlo = _hlo(step.lower(
        jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=rep), scalar, scalar))
    assert "tpu_custom_call" in hlo
    assert "callback" in hlo.lower()


def test_expert_gmm_compiles_at_the_served_widths(one_chip):
    """DeepSeek-V2-Lite's expert product: rows sorted by 16 held experts,
    d_model 2048 against the expert width 1408 (11 x 128) both ways, the
    gate and up projections in one kernel."""
    from repro.kernels.expert_gmm.kernel import expert_gmm_pallas
    m, d, f, e = 32 * 512 * 6, 2048, 1408, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for k, n, glu in ((d, f, True), (f, d, False)):
        up = sds((e, k, n), jnp.bfloat16) if glu else None
        hlo = _hlo(jax.jit(lambda x, w, g, u: expert_gmm_pallas(
            x, w, g, u)).lower(sds((m, k), jnp.bfloat16),
                               sds((e, k, n), jnp.bfloat16),
                               sds((e,), jnp.int32), up))
        assert re.search(r"^\s*(ROOT )?%?expert_gmm\S* = .*tpu_custom_call",
                         hlo, re.M)


def test_moe_mla_gated_step_compiles_at_full_width(one_chip, on_tpu,
                                                   monkeypatch):
    """The served DeepSeek-V2-Lite trunk at every published width (two of
    its 27 layers: the dense one and one expert layer), with the expert
    product and both gate kernels on their Pallas paths, and the held
    rows counted beside the gate's outputs."""
    from chipbench import spec
    from chipbench.tiers import moe_lm_classifier as M
    from chipbench.weights import moe_decoder as W
    from repro.kernels.expert_gmm import ops as gmm_ops
    from repro.kernels.fused_head_gate.ops import FusedLocalHead
    from repro.models import transformer as T
    from repro.serving.engine import make_gated_local_step
    monkeypatch.setattr(gmm_ops, "_on_tpu", lambda: True)
    cfg = spec.load_json(spec.ROOT / "chipbench" / "configs" /
                         "dsv2lite-ep4-lm-cls.json")
    cfg["model"]["num_hidden_layers"] = 2
    mcfg = M.model_config(cfg)
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        W.program_shapes(cfg["model"]))

    def fn(params, tokens, t, n, seq):
        def trunk(x):
            h, extras = T.forward(mcfg, params, {"tokens": x}, dropless=True)
            return h[:, -1], {M.COUNTER: extras["moe_held_rows"]}
        head = FusedLocalHead(trunk, params["head"]["w"], None, counted=True)
        return make_gated_local_step(head, emit=lambda *a: None)(
            tokens, t, n, seq)

    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(fn).lower(
        shapes, jax.ShapeDtypeStruct((SERVE_BATCH, 512), jnp.int32,
                                     sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
        scalar, scalar).compile()
    kernels = re.findall(
        r"^\s*%?(\S+) = .*custom_call_target=\"tpu_custom_call\"",
        compiled.as_text(), re.M)
    # gate and up in one product, down, the head gate's scoring and
    # selection
    assert sum(k.startswith("expert_gmm") for k in kernels) == 2, kernels
    assert sum(k.startswith("head_gate_scores_pallas") for k in kernels) == 1
    out = jax.eval_shape(fn, shapes, jax.ShapeDtypeStruct(
        (SERVE_BATCH, 512), jnp.int32), jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
    assert out["counters"][M.COUNTER].shape == (1, 16)


def test_data_parallel_counted_step_compiles_on_four_chips(topo, on_tpu):
    """A counted trunk under the data-parallel mesh: its counters are
    summed over the row shards and come back replicated."""
    from repro.kernels.fused_head_gate.ops import FusedLocalHead
    from repro.serving.engine import make_gated_local_step
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    w = jnp.zeros((24, 128), jnp.float32)
    head = FusedLocalHead(
        lambda x: (x.astype(jnp.float32),
                   {"rows": jnp.sum(x > 0, 0).astype(jnp.int32)}),
        w, None, counted=True)
    step = jax.jit(make_gated_local_step(head, mesh=mesh))
    rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    compiled = step.lower(
        jax.ShapeDtypeStruct((SERVE_BATCH, 24), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()
    assert compiled.output_shardings["counters"]["rows"].spec == P()
