"""Parameter definitions: shapes, logical sharding axes, and initializers.

A single source of truth (``model_def``) yields:
  * ``init_params(cfg, key)``      — concrete arrays (smoke tests, examples)
  * ``abstract_params(cfg)``       — ShapeDtypeStructs (dry-run, no allocation)
  * ``logical_axes(cfg)``          — pytree of logical-axis tuples, mapped to
                                     mesh axes by ``repro.launch.sharding``.

Logical axis names: "vocab", "embed", "heads", "kv_heads", "head_dim", "ff",
"expert", "lru", "ssd_inner", "ssd_bc", "ssd_heads".  ``None`` = replicated.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "fan_in"       # fan_in | zeros | ones | const:<v> | normal:<std>

    def stacked(self, n: int) -> "ParamDef":
        return ParamDef((n,) + self.shape, ("layer",) + self.axes, self.init)


def _attn_def(cfg: ModelConfig) -> Dict[str, ParamDef]:
    D, Hq, Hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": ParamDef((D, Hq, dh), ("embed", "heads", None)),
        "wk": ParamDef((D, Hkv, dh), ("embed", "kv_heads", None)),
        "wv": ParamDef((D, Hkv, dh), ("embed", "kv_heads", None)),
        "wo": ParamDef((Hq, dh, D), ("heads", None, "embed")),
    }


def _mlp_def(cfg: ModelConfig) -> Dict[str, ParamDef]:
    D, F = cfg.d_model, cfg.d_ff
    glu = cfg.activation in ("swiglu", "gelu_glu", "relu_glu")
    wi = ParamDef((D, 2, F), ("embed", None, "ff")) if glu else \
        ParamDef((D, F), ("embed", "ff"))
    return {"wi": wi, "wo": ParamDef((F, D), ("ff", "embed"))}


def _moe_def(cfg: ModelConfig) -> Dict[str, ParamDef]:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    glu = cfg.activation in ("swiglu", "gelu_glu", "relu_glu")
    wi = ParamDef((E, D, 2, F), ("expert", "embed", None, "ff")) if glu else \
        ParamDef((E, D, F), ("expert", "embed", "ff"))
    return {
        "router": ParamDef((D, E), ("embed", None)),
        "wi": wi,
        "wo": ParamDef((E, F, D), ("expert", "ff", "embed")),
    }


def _rglru_def(cfg: ModelConfig) -> Dict[str, ParamDef]:
    D, W, K = cfg.d_model, cfg.lru_width, cfg.conv_width
    return {
        "w_gate": ParamDef((D, W), ("embed", "lru")),
        "w_in": ParamDef((D, W), ("embed", "lru")),
        "conv_w": ParamDef((K, W), (None, "lru"), "normal:0.05"),
        "conv_b": ParamDef((W,), ("lru",), "zeros"),
        "w_a": ParamDef((W, W), (None, "lru"), "normal:0.01"),
        "w_x": ParamDef((W, W), (None, "lru"), "normal:0.01"),
        "lam": ParamDef((W,), ("lru",), "const:-5.0"),
        "w_out": ParamDef((W, D), ("lru", "embed")),
    }


def _ssd_def(cfg: ModelConfig) -> Dict[str, ParamDef]:
    D, di, K = cfg.d_model, cfg.d_inner, cfg.conv_width
    GN, H = cfg.ssm_groups * cfg.ssm_state, cfg.ssm_heads
    return {
        "wz": ParamDef((D, di), ("embed", "ssd_inner")),
        "wx": ParamDef((D, di), ("embed", "ssd_inner")),
        "wB": ParamDef((D, GN), ("embed", "ssd_bc")),
        "wC": ParamDef((D, GN), ("embed", "ssd_bc")),
        "wdt": ParamDef((D, H), ("embed", "ssd_heads")),
        "conv_x": ParamDef((K, di), (None, "ssd_inner"), "normal:0.05"),
        "bx": ParamDef((di,), ("ssd_inner",), "zeros"),
        "conv_B": ParamDef((K, GN), (None, "ssd_bc"), "normal:0.05"),
        "bB": ParamDef((GN,), ("ssd_bc",), "zeros"),
        "conv_C": ParamDef((K, GN), (None, "ssd_bc"), "normal:0.05"),
        "bC": ParamDef((GN,), ("ssd_bc",), "zeros"),
        "A_log": ParamDef((H,), ("ssd_heads",), "const:0.0"),
        "dt_bias": ParamDef((H,), ("ssd_heads",), "const:-2.0"),
        "D_skip": ParamDef((H,), ("ssd_heads",), "ones"),
        "norm": ParamDef((di,), ("ssd_inner",), "ones"),
        "out_proj": ParamDef((di, D), ("ssd_inner", "embed")),
    }


def layer_def(cfg: ModelConfig, layer_type: str) -> Dict:
    D = cfg.d_model
    ln = lambda: ParamDef((D,), (None,), "ones")
    if layer_type == "attn":
        ffn = {"moe": _moe_def(cfg)} if cfg.n_experts else {"mlp": _mlp_def(cfg)}
        return {"ln1": ln(), "attn": _attn_def(cfg), "ln2": ln(), **ffn}
    if layer_type == "rec":
        return {"ln1": ln(), "rec": _rglru_def(cfg), "ln2": ln(),
                "mlp": _mlp_def(cfg)}
    if layer_type == "ssd":
        return {"ln": ln(), "ssd": _ssd_def(cfg)}
    if layer_type == "enc":
        return {"ln1": ln(), "attn": _attn_def(cfg), "ln2": ln(),
                "mlp": _mlp_def(cfg)}
    if layer_type == "dec":
        return {"ln1": ln(), "attn": _attn_def(cfg),
                "ln2": ln(), "cross": _attn_def(cfg),
                "ln3": ln(), "mlp": _mlp_def(cfg)}
    raise ValueError(layer_type)


def _stack_def(d, n: int):
    return jax.tree.map(lambda p: p.stacked(n), d,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def hybrid_structure(cfg: ModelConfig):
    """(group pattern, n_groups, tail layer types) for pattern-based models."""
    types = cfg.layer_types()
    period = len(cfg.block_pattern)
    n_groups = cfg.n_layers // period
    tail = types[n_groups * period:]
    return cfg.block_pattern, n_groups, tail


def model_def(cfg: ModelConfig) -> Dict:
    D, V = cfg.d_model, cfg.vocab_padded
    out: Dict = {
        "embed": ParamDef((V, D), ("vocab", None), "normal:0.02"),
        "final_norm": ParamDef((D,), (None,), "ones"),
    }
    if not cfg.tie_embeddings:
        out["head"] = ParamDef((D, V), (None, "vocab"))

    if cfg.family == "encdec":
        out["enc_layers"] = _stack_def(layer_def(cfg, "enc"), cfg.enc_layers)
        out["dec_layers"] = _stack_def(layer_def(cfg, "dec"), cfg.dec_layers)
        out["enc_norm"] = ParamDef((D,), (None,), "ones")
        return out

    if cfg.block_pattern:
        pattern, n_groups, tail = hybrid_structure(cfg)
        group = {f"b{i}_{t}": layer_def(cfg, t) for i, t in enumerate(pattern)}
        out["groups"] = _stack_def(group, n_groups)
        out["tail"] = {f"t{i}_{t}": layer_def(cfg, t) for i, t in enumerate(tail)}
        return out

    lt = cfg.layer_types()[0]
    out["layers"] = _stack_def(layer_def(cfg, lt), cfg.n_layers)
    return out


# ---------------------------------------------------------------------------
# Materializers
# ---------------------------------------------------------------------------

def _is_def(x):
    return isinstance(x, ParamDef)


def _init_leaf(p: ParamDef, key, dtype):
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    if p.init.startswith("const:"):
        return jnp.full(p.shape, float(p.init.split(":")[1]), dtype)
    if p.init.startswith("normal:"):
        std = float(p.init.split(":")[1])
    else:  # fan_in
        fan_in = p.shape[0] if len(p.shape) == 1 else int(
            math.prod(p.shape[:-1]) if p.axes[-1] == "embed" else p.shape[0])
        # For projection tensors (D, ...out) fan-in is the first dim.
        fan_in = p.shape[0] if len(p.shape) >= 2 else p.shape[0]
        if len(p.shape) >= 3 and p.axes[0] == "expert":
            fan_in = p.shape[1]
        std = fan_in ** -0.5
    # drawn straight in the param dtype: a bf16 model never holds an f32
    # copy of a leaf (llama3.2-3b's stacked mlp.wi alone is 1.4e9 elements)
    return jax.random.normal(key, p.shape, dtype) * jnp.asarray(std, dtype)


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key) -> Dict:
    """Random params from ``key``, every leaf drawn inside this one jitted
    program, so peak device memory is the params plus one leaf's draw."""
    defs = model_def(cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    leaves, treedef = jax.tree.flatten(defs, is_leaf=_is_def)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(p, k, dtype) for p, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(cfg: ModelConfig) -> Dict:
    defs = model_def(cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    return jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, dtype),
                        defs, is_leaf=_is_def)


def logical_axes(cfg: ModelConfig) -> Dict:
    defs = model_def(cfg)
    return jax.tree.map(lambda p: p.axes, defs, is_leaf=_is_def)


def param_bytes(cfg: ModelConfig) -> int:
    defs = model_def(cfg)
    itemsize = jnp.dtype(cfg.param_dtype).itemsize
    return sum(math.prod(p.shape) * itemsize
               for p in jax.tree.leaves(defs, is_leaf=_is_def))


def n_params(cfg: ModelConfig) -> int:
    defs = model_def(cfg)
    return sum(math.prod(p.shape)
               for p in jax.tree.leaves(defs, is_leaf=_is_def))
