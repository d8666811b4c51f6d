"""Dry-run lowering helpers: ShapeDtypeStruct input specs + step builders.

This module is import-safe (it never touches jax device state); the
``dryrun.py`` entrypoint sets XLA_FLAGS for 512 host devices BEFORE
importing it. Everything here operates on abstract shapes, so lowering and
compiling never allocates model-sized buffers.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs.base import INPUT_SHAPES, ModelConfig, ShapeConfig
from repro.launch import sharding as sh
from repro.models import transformer as T
from repro.train.loop import make_train_step
from repro.train.optimizer import AdamWConfig, init_opt_state


# --------------------------------------------------------------------------
# input specs (assignment §Multi-pod dry-run item 2)
# --------------------------------------------------------------------------

def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this
    (arch, input-shape) pair — weak-type-correct, shardable, no device
    allocation.

    train/prefill: the full-sequence batch; decode: ONE new token plus a
    KV cache of seq_len slots (per assignment: decode shapes lower
    ``serve_step`` with a seq_len cache, not ``train_step``).
    """
    b, t = shape.global_batch, shape.seq_len
    dt = cfg.dtype
    if shape.kind in ("train", "prefill"):
        if cfg.family == "vlm":
            # half image patches (frontend stub), half text
            batch = {"embeds": _sds((b, t // 2, cfg.d_model), dt),
                     "tokens": _sds((b, t // 2), jnp.int32)}
        elif cfg.takes_embeddings:
            batch = {"embeds": _sds((b, t, cfg.d_model), dt)}
        else:
            batch = {"tokens": _sds((b, t), jnp.int32)}
        if cfg.is_encoder and shape.kind == "train":
            batch["labels"] = _sds((b, t), jnp.int32)
        return batch
    # decode: one token against a seq_len cache
    assert cfg.supports_decode, cfg.name
    cache = jax.eval_shape(lambda: T.make_cache(cfg, b, t))
    return {"token": _sds((b,), jnp.int32),
            "cache": cache,
            "pos": _sds((), jnp.int32)}


def params_specs(cfg: ModelConfig) -> Any:
    return jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))


# --------------------------------------------------------------------------
# step functions (what gets lowered)
# --------------------------------------------------------------------------

def make_steps(cfg: ModelConfig):
    """(train_step, prefill_step, decode_step) pure functions for cfg."""
    opt_cfg = AdamWConfig()
    train_step = make_train_step(cfg, opt_cfg, remat=True)

    def prefill_step(params, batch):
        if cfg.is_encoder:
            # encoder "prefill" == full forward + per-frame classification
            x, _ = T.forward(cfg, params, batch)
            from repro.models.layers import dense
            return dense(params["head"], x).astype(jnp.float32)
        return T.prefill(cfg, params, batch)

    def decode_step(params, token, cache, pos):
        return T.decode_step(cfg, params, token, cache, pos)

    return train_step, prefill_step, decode_step


# --------------------------------------------------------------------------
# lowering
# --------------------------------------------------------------------------

def _tp_param_bytes_per_chip(cfg: ModelConfig, mesh) -> float:
    """Per-chip weight bytes under pure tensor parallelism (no FSDP).
    Works with any mesh-like object exposing .shape/.axis_names (the
    PartitionSpec rules never touch device state)."""
    shapes = params_specs(cfg)
    total = 0.0

    def visit(path, leaf):
        nonlocal total
        spec = sh.param_spec(path, leaf, mesh, fsdp=False)
        frac = 1.0
        for ax in spec:
            if ax is not None:
                frac /= mesh.shape[ax]
        total += leaf.size * leaf.dtype.itemsize * frac

    jax.tree_util.tree_map_with_path(visit, shapes)
    return total


def lower_step(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
               fsdp: bool | None = None, remat: bool = True,
               donate: bool = True):
    """Build shardings and ``jit(...).lower(...)`` the right step for this
    (arch, shape) on ``mesh``. Returns the Lowered object.

    fsdp=None picks the policy: training always FSDPs (optimizer moments
    triple the weight footprint); serving (prefill/decode) uses pure TP
    whenever the TP-sharded weights fit comfortably per chip — FSDP at
    decode costs a full weight all-gather per TOKEN (§Perf iteration A1:
    60x collective reduction on deepseek-67b decode_32k)."""
    if fsdp is None:
        if shape.kind == "train":
            fsdp = True
        else:
            fsdp = _tp_param_bytes_per_chip(cfg, mesh) > 12e9
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        pshapes = params_specs(cfg)
        pshard = sh.params_shardings(cfg, mesh, fsdp=fsdp)
        ins = input_specs(cfg, shape)
        train_step, prefill_step, decode_step = make_steps(cfg)

        if shape.kind == "train":
            oshapes = jax.eval_shape(init_opt_state, pshapes)
            oshard = sh.opt_shardings(cfg, mesh, pshard)
            bshard = sh.input_shardings(cfg, mesh, ins)
            fn = jax.jit(
                train_step,
                in_shardings=(pshard, oshard, bshard),
                out_shardings=(pshard, oshard, sh.replicated(mesh)),
                donate_argnums=(0, 1) if donate else ())
            return fn.lower(pshapes, oshapes, ins)

        if shape.kind == "prefill":
            bshard = sh.input_shardings(cfg, mesh, ins)
            if cfg.is_encoder:
                out_sh = NamedSharding(
                    mesh, sh.batch_spec(mesh,
                                        (shape.global_batch, shape.seq_len,
                                         cfg.num_classes)))
                fn = jax.jit(prefill_step,
                             in_shardings=(pshard, bshard),
                             out_shardings=out_sh)
            else:
                cshard = sh.cache_shardings(cfg, mesh, shape.global_batch,
                                            shape.seq_len)
                lshard = sh.logits_sharding(cfg, mesh, shape.global_batch)
                fn = jax.jit(prefill_step,
                             in_shardings=(pshard, bshard),
                             out_shardings=(lshard, cshard))
            return fn.lower(pshapes, ins)

        # decode
        cshard = sh.cache_shardings(cfg, mesh, shape.global_batch,
                                    shape.seq_len)
        tshard = NamedSharding(mesh,
                               sh.batch_spec(mesh, (shape.global_batch,)))
        lshard = sh.logits_sharding(cfg, mesh, shape.global_batch)
        fn = jax.jit(decode_step,
                     in_shardings=(pshard, tshard, cshard,
                                   sh.replicated(mesh)),
                     out_shardings=(lshard, cshard),
                     donate_argnums=(2,) if donate else ())
        return fn.lower(pshapes, ins["token"], ins["cache"], ins["pos"])


def shape_by_name(name: str) -> ShapeConfig:
    return INPUT_SHAPES[name]
