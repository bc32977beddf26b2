"""Plain reference of a decoder with grouped-query attention and a
mixture-of-experts SwiGLU feed-forward (Mixtral's and Grok-1's block), as
an architecture module of the benchmark: the weights' layout, the forward
and the FLOP and byte counts (``bench/spec.py``'s ``reference``).

It is what a new architecture brings as files only: dropped into
``bench/references/`` beside ``dense_gqa.py``, it takes attention, norms,
RoPE and the fp8 rounding from that module unchanged, and adds the expert
layer:

    per layer:  h = x + attention, as in dense_gqa
                r = n2(h) . Wr                      (router logits, E)
                g = softmax over the top_k of r      (renormalised gates)
                x = h + sum_k g_k W2[e_k] . (silu(W1g[e_k] n2(h))
                                             * W1u[e_k] n2(h))

Every token reaches its top_k experts (no capacity drop: the serving
path's dropless routing).  Matmuls take bf16 operands and accumulate in
f32; the gates and their sum are f32.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import costs, spec

dense = spec.reference("dense_gqa", Path(__file__).resolve().parents[1])


def _moe(x, p, top_k, quant):
    """x: (S, D) bf16 -> (S, D) f32; p: router (D, E), wi (E, D, 2, F),
    wo (E, F, D).  Every expert runs on every token; the gates keep the
    top_k."""
    logits = dense._mm("sd,de->se", x, p["router"], quant)
    top, idx = jax.lax.top_k(logits, top_k)
    gates = jnp.einsum("sk,ske->se", jax.nn.softmax(top, -1),
                       jax.nn.one_hot(idx, logits.shape[-1]))
    out = 0.0
    for e in range(logits.shape[-1]):
        h = dense._mm("sd,dtf->stf", x, p["wi"][e], quant)
        a = (jax.nn.silu(h[:, 0]) * h[:, 1]).astype(jnp.bfloat16)
        out = out + gates[:, e:e + 1] * dense._mm("sf,fd->sd", a,
                                                  p["wo"][e], quant)
    return out


@functools.lru_cache(maxsize=None)
def _fns(m_json: str, quant: Optional[str]):
    m = json.loads(m_json)
    eps, theta = m["norm_eps"], m["rope_theta"]

    @jax.jit
    def embed(table, tokens):
        rows = jnp.take(table, tokens, axis=0)
        if quant == "fp8":
            rows = dense._fp8(rows,
                              jnp.max(jnp.abs(table)).astype(jnp.float32))
        return rows.astype(jnp.bfloat16)

    @jax.jit
    def layer(x, layers, i):
        lp = jax.tree.map(lambda a: a[i], layers)
        pos = jnp.arange(x.shape[0])
        xin = dense._rms(x, lp["ln1"], eps)
        a = lp["attn"]
        q = dense._mm("sd,dhk->shk", xin, a["wq"], quant).astype(jnp.bfloat16)
        k = dense._mm("sd,dhk->shk", xin, a["wk"], quant).astype(jnp.bfloat16)
        v = dense._mm("sd,dhk->shk", xin, a["wv"], quant).astype(jnp.bfloat16)
        o = dense._attention(dense._rope(q, pos, theta),
                             dense._rope(k, pos, theta), v, quant)
        h = x + dense._mm("shk,hkd->sd", o, a["wo"], quant).astype(
            jnp.bfloat16)
        f = _moe(dense._rms(h, lp["ln2"], eps), lp["moe"], m["top_k"], quant)
        return h + f.astype(jnp.bfloat16)

    @jax.jit
    def head(params, x, rows, score):
        xr = dense._rms(x[rows], params["final_norm"], eps)
        lg = dense._mm("rd,dv->rv", xr, params["head"], quant)[:, :m["vocab"]]
        return (lg.max(-1), lg.std(-1), jnp.argmax(lg, -1).astype(jnp.int32),
                jnp.take_along_axis(lg, score, axis=1))

    return embed, layer, head


def scores(params, m: Dict, tokens: np.ndarray, rows: np.ndarray,
           score: np.ndarray, quant: Optional[str] = None,
           pad_to: int = 0) -> Dict:
    """As ``dense_gqa.scores``, through the expert layers."""
    embed, layer, head = _fns(json.dumps(m, sort_keys=True), quant)
    S = len(tokens)
    Sp = -(-max(S, pad_to) // dense.Q_BLOCK) * dense.Q_BLOCK
    t = np.zeros(Sp, np.int32)
    t[:S] = tokens
    x = embed(params["embed"], jnp.asarray(t))
    for i in range(m["n_layers"]):
        x = layer(x, params["layers"], jnp.int32(i))
    out = {"max": [], "std": [], "argmax": [], "score": []}
    n, B = len(rows), dense.ROW_BLOCK
    for b in range(0, n, B):
        r = np.zeros(B, np.int32)
        sc = np.zeros((B, score.shape[1]), np.int32)
        k = min(B, n - b)
        r[:k], sc[:k] = rows[b:b + k], score[b:b + k]
        res = head(params, x, jnp.asarray(r), jnp.asarray(sc))
        for name, v in zip(("max", "std", "argmax", "score"), res):
            out[name].append(np.asarray(v)[:k])
    return {k: np.concatenate(v) for k, v in out.items()}


def layout(m: Dict) -> Dict:
    """The engine's tree for a MoE model: dense_gqa's, with ``moe``
    (router (L, D, E), wi (L, E, D, 2, F), wo (L, E, F, D)) in place of
    ``mlp``, at the same standard deviations."""
    D, F, L, E = m["d_model"], m["d_ff"], m["n_layers"], m["n_experts"]
    Hq, Hkv, dh = m["n_heads"], m["n_kv_heads"], dense.head_dim(m)
    V = costs.vocab_padded(m)
    res = (2 * L) ** -0.5
    return {
        "embed": ((V, D), 1.0),
        "final_norm": ((D,), "ones"),
        "head": ((D, V), D ** -0.5),
        "layers": {
            "ln1": ((L, D), "ones"),
            "ln2": ((L, D), "ones"),
            "attn": {"wq": ((L, D, Hq, dh), D ** -0.5),
                     "wk": ((L, D, Hkv, dh), D ** -0.5),
                     "wv": ((L, D, Hkv, dh), D ** -0.5),
                     "wo": ((L, Hq, dh, D), (Hq * dh) ** -0.5 * res)},
            "moe": {"router": ((L, D, E), D ** -0.5),
                    "wi": ((L, E, D, 2, F), D ** -0.5),
                    "wo": ((L, E, F, D), F ** -0.5 * res)},
        },
    }


def _attn_params(m: Dict) -> int:
    D, Hq, Hkv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], \
        dense.head_dim(m)
    return D * (Hq + 2 * Hkv) * dh + Hq * dh * D


def _expert_params(m: Dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def token_flops(m: Dict, context: float, *, head: bool) -> float:
    """Model FLOPs: attention's projections, the router and top_k experts
    a layer (not the experts a dense dispatch also runs), attention, and
    the head only when ``head``."""
    active = _attn_params(m) + m["d_model"] * m["n_experts"] \
        + m["top_k"] * _expert_params(m)
    f = 2.0 * m["n_layers"] * active + dense.attn_flops(m, context)
    if head:
        f += 2.0 * dense.head_params(m)
    return f


def prefill_flops(m: Dict, tokens: float, prompts: float,
                  mean_keys: float) -> float:
    return tokens * token_flops(m, mean_keys, head=False) \
        + prompts * 2.0 * dense.head_params(m)


kv_token_bytes = dense.kv_token_bytes


def weight_bytes(m: Dict) -> int:
    """Every expert's weights: a decode batch reaches all of them."""
    L, D, E = m["n_layers"], m["d_model"], m["n_experts"]
    layer = _attn_params(m) + D * E + E * _expert_params(m)
    return (L * layer + dense.head_params(m) + (2 * L + 1) * D) * costs.BF16


def decode_bytes(m: Dict, steps: float, rows: float,
                 mean_keys: float) -> float:
    return steps * weight_bytes(m) + rows * (mean_keys + 1) \
        * kv_token_bytes(m)
