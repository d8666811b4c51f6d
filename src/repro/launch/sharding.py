"""Sharding rules for the production mesh (DESIGN.md §7).

Megatron-style 2-way tensor parallelism over the ``model`` axis:

* column-parallel in-projections  -> P(..., "model")          (last dim)
* row-parallel out-projections    -> P(..., "model", None)    (contracting)
* vocab-parallel LM head; embedding sharded over d_model
* MoE expert weights sharded expert-major over ``model``      (EP)
* batch over ("pod", "data"); long_500k (batch=1) shards KV-cache slots
  over ``data`` instead

Every candidate dim is sharded only if divisible by the mesh axis size
(e.g. HuBERT's 504-class head stays replicated); this keeps one rule set
valid for all 10 assigned architectures.

All functions operate on ShapeDtypeStruct pytrees (via ``jax.eval_shape``)
so building a sharding plan never allocates device memory.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, SequenceKey

from repro.configs.base import ModelConfig
from repro.launch.mesh import batch_axes

# parents whose "w" (and "b") leaves are column-parallel (shard output dim)
_COL = {"wq", "wk", "wv", "wg", "wr", "w_gate", "w_up", "w_in", "w_zx",
        "w_bc", "w_dt", "w_dkv", "w_uk", "w_uv", "cm_wk", "cm_wr", "hidden"}
# parents whose "w" leaves are row-parallel (shard contracting dim)
_ROW = {"wo", "w_down", "w_out", "cm_wv", "out"}
# MoE stacked expert tensors (leaf IS the weight, expert dim leading)
_MOE_EXPERT = {"w_gate", "w_up", "w_down"}


def _names(path) -> list[str]:
    out = []
    for k in path:
        if isinstance(k, DictKey):
            out.append(str(k.key))
        elif isinstance(k, SequenceKey):
            out.append(str(k.idx))
    return out


def _axis(mesh, name: str) -> int:
    return mesh.shape[name]


def _put(spec: list, dim: int, axis: str, shape, axis_size: int) -> None:
    """Assign `axis` to `dim` if the dim size divides evenly."""
    if shape[dim] % axis_size == 0 and spec[dim] is None:
        spec[dim] = axis


_FSDP_MIN_ELEMS = 1 << 20       # only FSDP-shard leaves >= 1M elements


def param_spec(path, leaf, mesh, *, fsdp: bool = False) -> P:
    """PartitionSpec for one parameter leaf (works for layer-stacked
    leaves: rules index dims from the right).

    With ``fsdp=True``, large 2D+ weights are additionally sharded over the
    ``data`` axis on their non-``model`` matmul dim (ZeRO-3 style) — needed
    to fit e.g. qwen3-235B (470 GB of bf16 weights) on 256 x 16 GB chips,
    where 16-way tensor parallelism alone leaves 29 GB/chip.
    """
    names = _names(path)
    last = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    shape = leaf.shape
    nd = len(shape)
    spec: list = [None] * nd
    msize = _axis(mesh, "model")
    fsdp_dim = None                 # secondary (data-axis) shard candidate

    in_moe = "moe" in names
    if in_moe and last in _MOE_EXPERT and nd >= 3:
        # stacked experts [(L,) E, d, f] -> expert parallelism
        _put(spec, nd - 3, "model", shape, msize)
        fsdp_dim = nd - 2
    elif last == "embed":
        _put(spec, nd - 1, "model", shape, msize)       # d_model sharded
        fsdp_dim = nd - 2                               # vocab over data
    elif last == "w" and parent in _COL:
        _put(spec, nd - 1, "model", shape, msize)
        fsdp_dim = nd - 2
    elif last == "b" and parent in _COL:
        _put(spec, nd - 1, "model", shape, msize)
    elif last == "w" and parent == "head":
        _put(spec, nd - 1, "model", shape, msize)       # vocab-parallel
        fsdp_dim = nd - 2
    elif last == "b" and parent == "head":
        _put(spec, nd - 1, "model", shape, msize)
    elif last == "w" and parent in _ROW:
        _put(spec, nd - 2, "model", shape, msize)
        fsdp_dim = nd - 1
    # everything else (norms, router, loras, conv, decay, biases of
    # row-parallel projections) stays replicated
    if fsdp and fsdp_dim is not None and leaf.size >= _FSDP_MIN_ELEMS \
            and "data" in mesh.axis_names:
        _put(spec, fsdp_dim, "data", shape, _axis(mesh, "data"))
    return P(*spec)


def params_shardings(cfg: ModelConfig, mesh, *, fsdp: bool = False) -> Any:
    """NamedSharding pytree for init_params(cfg) — via eval_shape."""
    from repro.models import transformer as T
    shapes = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, param_spec(p, l, mesh, fsdp=fsdp)),
        shapes)


def opt_shardings(cfg: ModelConfig, mesh, pspec: Any) -> dict:
    """Optimizer state inherits the params' shardings (moments are
    params-shaped; step is a replicated scalar)."""
    return {"m": pspec, "v": pspec,
            "step": NamedSharding(mesh, P())}


# --------------------------------------------------------------------------
# activations / inputs
# --------------------------------------------------------------------------

def batch_spec(mesh, shape: tuple, *, batch_dim: int = 0) -> P:
    """Shard the batch dim over ("pod","data") when divisible."""
    ba = batch_axes(mesh)
    total = 1
    for a in ba:
        total *= _axis(mesh, a)
    spec: list = [None] * len(shape)
    if shape[batch_dim] % total == 0:
        spec[batch_dim] = ba if len(ba) > 1 else ba[0]
    return P(*spec)


def input_shardings(cfg: ModelConfig, mesh, batch_shapes: Any) -> Any:
    """NamedSharding pytree for a batch pytree of ShapeDtypeStructs."""
    return jax.tree.map(
        lambda l: NamedSharding(mesh, batch_spec(mesh, l.shape)),
        batch_shapes)


def cache_spec(path, leaf, mesh, *, seq_len: int) -> P:
    """KV/state cache leaf spec. Leaves are [L_or_G, B, ...]:

    * batch dim (1) over ("pod","data") when divisible;
    * attention KV caches additionally shard kv-heads over ``model`` when
      divisible, else the slot dim (long-context sequence sharding);
    * MLA latent caches shard the lora rank over ``model``;
    * recurrent states shard their head dim over ``model``.
    """
    names = _names(path)
    last = names[-1]
    shape = leaf.shape
    nd = len(shape)
    spec: list = [None] * nd
    msize = _axis(mesh, "model")
    ba = batch_axes(mesh)
    bsize = 1
    for a in ba:
        bsize *= _axis(mesh, a)
    if nd >= 2 and shape[1] % bsize == 0:
        spec[1] = ba if len(ba) > 1 else ba[0]

    if last in ("k", "v", "attn_k", "attn_v") and nd == 5:
        # [L, B, S, K, hd]
        if shape[3] % msize == 0:
            spec[3] = "model"
        elif shape[2] % msize == 0:
            spec[2] = "model"           # sequence-shard the cache
    elif last == "c_kv" and nd == 4:    # [L, B, S, r] MLA latent
        _put(spec, 3, "model", shape, msize)
    elif last == "wkv" and nd == 5:     # [L, B, H, M, M] rwkv state
        _put(spec, 2, "model", shape, msize)
    elif last == "ssm" and nd == 5:     # [L, B, h, p, n] mamba state
        _put(spec, 3, "model", shape, msize)   # P=128 divides; h may not
    return P(*spec)


def cache_shardings(cfg: ModelConfig, mesh, batch: int, max_len: int) -> Any:
    from repro.models import transformer as T
    shapes = jax.eval_shape(lambda: T.make_cache(cfg, batch, max_len))
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, cache_spec(p, l, mesh,
                                                    seq_len=max_len)),
        shapes)


def logits_sharding(cfg: ModelConfig, mesh, batch: int) -> NamedSharding:
    out_dim = cfg.num_classes or cfg.vocab_size
    spec = batch_spec(mesh, (batch, out_dim))
    s = list(spec) + [None] * (2 - len(spec))
    if out_dim % _axis(mesh, "model") == 0:
        s[1] = "model"
    return NamedSharding(mesh, P(*s))


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
