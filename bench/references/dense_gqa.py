"""Plain reference forward of a dense decoder with grouped-query attention.

Straightforward ``jax.numpy`` over one whole sequence, with no cache,
paging, chunking, batching or kernel, following the configuration's
``model`` block:

    x = embed[tokens]
    per layer:  h = x + Wo . attn(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x))
                x = h + W2 . act(W1 n2(h))
    logits = head . n(x)

``n`` is RMSNorm (scale, no bias, epsilon ``norm_eps``), RoPE rotates the two
halves of each head (base ``rope_theta``), attention is causal softmax
attention with ``n_heads / n_kv_heads`` query heads per KV head, ``act`` is
tanh-GELU (``gelu``) or squared ReLU (``squared_relu``).  Matmuls take bf16
operands and accumulate in f32 (the served type); norms, RoPE, softmax and
the head are f32, with f32 matmuls at ``highest`` precision.  Padded head
columns past ``vocab`` are left out.

``quant="fp8"`` computes every matmul (the layers' projections, attention's
scores and values, the head) and the embedding with both operands rounded
to fp8 (e4m3, one scale per tensor): the control the benchmark's check must
refuse.

The module also holds what the harness needs to know of this architecture
(see ``bench/spec.py``'s ``reference``): ``layout(m)``, the benchmark's
weights in the engine's parameter tree, and the FLOP and byte counts the
per-layer readers take (``token_flops``, ``prefill_flops``,
``weight_bytes``, ``kv_token_bytes``, ``decode_bytes``).

It imports nothing of the program under test.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import costs

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256            # query rows per attention block
ROW_BLOCK = 256          # head rows per logits block
FP8_MAX = 448.0


def _fp8(x, amax=None):
    """Round to fp8 e4m3 with one scale for the tensor (its largest
    magnitude, or ``amax``), back in bf16."""
    if amax is None:
        amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = (x.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def _mm(spec, a, w, quant):
    if quant == "fp8":
        a, w = _fp8(a), _fp8(w)
    return jnp.einsum(spec, a, w, preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(jnp.bfloat16)


def _rope(x, pos, theta):
    """x: (S, H, dh); rotate the two halves by angle pos * theta^(-i/half)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           -1).astype(jnp.bfloat16)


def _attention(q, k, v, quant):
    """Causal GQA attention. q: (S, Hq, dh), k/v: (S, Hkv, dh)."""
    S, Hq, dh = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    if quant == "fp8":
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, Hkv, G, dh)
    kpos = jnp.arange(S)

    def block(args):
        qb, i = args
        s = jnp.einsum("qhgd,khd->hgqk", qb, k,
                       preferred_element_type=jnp.float32) * dh ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant == "fp8":
            o = jnp.einsum("hgqk,khd->qhgd", _fp8(p), v,
                           preferred_element_type=jnp.float32)
        else:
            o = jnp.einsum("hgqk,khd->qhgd", p, v.astype(jnp.float32),
                           precision=HI)
        return o.astype(jnp.bfloat16)

    out = jax.lax.map(block, (qg, jnp.arange(S // Q_BLOCK)))
    return out.reshape(S, Hq, dh)


@functools.lru_cache(maxsize=None)
def _fns(m_json: str, quant: Optional[str]):
    m = json.loads(m_json)
    eps, theta = m["norm_eps"], m["rope_theta"]
    act = {"gelu": lambda h: jax.nn.gelu(h, approximate=True),
           "squared_relu": lambda h: jnp.square(jax.nn.relu(h))}[
        m["activation"]]

    @jax.jit
    def embed(table, tokens):
        rows = jnp.take(table, tokens, axis=0)
        if quant == "fp8":
            rows = _fp8(rows, jnp.max(jnp.abs(table)).astype(jnp.float32))
        return rows.astype(jnp.bfloat16)

    @jax.jit
    def layer(x, layers, i):
        lp = jax.tree.map(lambda a: a[i], layers)
        pos = jnp.arange(x.shape[0])
        xin = _rms(x, lp["ln1"], eps)
        a = lp["attn"]
        q = _mm("sd,dhk->shk", xin, a["wq"], quant).astype(jnp.bfloat16)
        k = _mm("sd,dhk->shk", xin, a["wk"], quant).astype(jnp.bfloat16)
        v = _mm("sd,dhk->shk", xin, a["wv"], quant).astype(jnp.bfloat16)
        o = _attention(_rope(q, pos, theta), _rope(k, pos, theta), v, quant)
        h = x + _mm("shk,hkd->sd", o, a["wo"], quant).astype(jnp.bfloat16)
        hin = _rms(h, lp["ln2"], eps)
        f = act(_mm("sd,df->sf", hin, lp["mlp"]["wi"], quant))
        f = _mm("sf,fd->sd", f.astype(jnp.bfloat16), lp["mlp"]["wo"], quant)
        return h + f.astype(jnp.bfloat16)

    @jax.jit
    def head(params, x, rows, score):
        """Stats of the head's logits at hidden rows ``rows``: row max, row
        standard deviation, argmax, and the logits of ``score`` (R, k)."""
        xr = _rms(x[rows], params["final_norm"], eps)
        lg = _mm("rd,dv->rv", xr, params["head"], quant)[:, :m["vocab"]]
        return (lg.max(-1), lg.std(-1), jnp.argmax(lg, -1).astype(jnp.int32),
                jnp.take_along_axis(lg, score, axis=1))

    return embed, layer, head


def scores(params, m: Dict, tokens: np.ndarray, rows: np.ndarray,
           score: np.ndarray, quant: Optional[str] = None,
           pad_to: int = 0) -> Dict:
    """Run one sequence ``tokens`` (zero-padded to ``pad_to`` and to a
    multiple of Q_BLOCK, so that every sequence of a cell runs one compiled
    shape; causal, so padding never reaches an earlier row) and return, at
    positions ``rows``, the logits' max, std, argmax and the logits of the
    token ids ``score`` (len(rows), k)."""
    embed, layer, head = _fns(json.dumps(m, sort_keys=True), quant)
    S = len(tokens)
    Sp = -(-max(S, pad_to) // Q_BLOCK) * Q_BLOCK
    t = np.zeros(Sp, np.int32)
    t[:S] = tokens
    x = embed(params["embed"], jnp.asarray(t))
    for i in range(m["n_layers"]):
        x = layer(x, params["layers"], jnp.int32(i))
    out = {"max": [], "std": [], "argmax": [], "score": []}
    n = len(rows)
    for b in range(0, n, ROW_BLOCK):
        r = np.zeros(ROW_BLOCK, np.int32)
        sc = np.zeros((ROW_BLOCK, score.shape[1]), np.int32)
        k = min(ROW_BLOCK, n - b)
        r[:k], sc[:k] = rows[b:b + k], score[b:b + k]
        res = head(params, x, jnp.asarray(r), jnp.asarray(sc))
        for name, v in zip(("max", "std", "argmax", "score"), res):
            out[name].append(np.asarray(v)[:k])
    return {k: np.concatenate(v) for k, v in out.items()}


# -- the weights -------------------------------------------------------------

def layout(m: Dict) -> Dict:
    """Leaf -> (shape, init) where init is a std or "ones", in the tree
    layout the engine's parameters take:

        embed (V, D), final_norm (D,), head (D, V),
        layers: ln1 (L, D), ln2 (L, D),
                attn: wq (L, D, Hq, dh), wk/wv (L, D, Hkv, dh),
                      wo (L, Hq, dh, D),
                mlp:  wi (L, D, F), wo (L, F, D)

    with V the vocabulary rounded up to 256 (the padded head columns are
    masked by both sides).  Projections are normal with standard deviation
    fan_in ** -0.5, the two that write into the residual stream
    (attention's and the MLP's output) scaled by (2 L) ** -0.5; the
    embedding has standard deviation 1, norm scales are 1.  With a small
    embedding and unscaled residual writes, ten random layers drive every
    position to a handful of tokens, and a check of served tokens then sees
    little."""
    D, F, L = m["d_model"], m["d_ff"], m["n_layers"]
    Hq, Hkv, dh = m["n_heads"], m["n_kv_heads"], head_dim(m)
    V = costs.vocab_padded(m)
    res = (2 * L) ** -0.5
    if m["activation"] not in ("gelu", "squared_relu"):
        raise ValueError(f"dense reference covers gelu and squared_relu "
                         f"MLPs, not {m['activation']!r}")
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
            "mlp": {"wi": ((L, D, F), D ** -0.5),
                    "wo": ((L, F, D), F ** -0.5 * res)},
        },
    }


# -- operations and bytes ----------------------------------------------------
#
# ``n_layers`` layers of attention + MLP, then the output head; weights are
# bf16.  Prefill charges the head once per prompt, on its last token: a
# chunk step computes logits for one token per stream and only the last
# prompt token's logits are used.

def _glu(m: Dict) -> bool:
    return m["activation"] in ("swiglu", "gelu_glu", "relu_glu")


def head_dim(m: Dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def layer_params(m: Dict) -> int:
    """Matmul parameters of one layer (norm scales excluded)."""
    D, F = m["d_model"], m["d_ff"]
    Hq, Hkv, dh = m["n_heads"], m["n_kv_heads"], head_dim(m)
    attn = D * (Hq + 2 * Hkv) * dh + Hq * dh * D
    mlp = (3 if _glu(m) else 2) * D * F
    return attn + mlp


def head_params(m: Dict) -> int:
    return m["d_model"] * costs.vocab_padded(m)


def attn_flops(m: Dict, context: float) -> float:
    """Score and value FLOPs of one query token against ``context`` keys,
    over all layers."""
    return m["n_layers"] * 2 * 2 * m["n_heads"] * head_dim(m) * context


def token_flops(m: Dict, context: float, *, head: bool) -> float:
    """Forward FLOPs of one token whose query sees ``context`` keys: the
    layer matmuls, attention, and the head only when ``head``."""
    f = 2.0 * m["n_layers"] * layer_params(m) + attn_flops(m, context)
    if head:
        f += 2.0 * head_params(m)
    return f


def prefill_flops(m: Dict, tokens: float, prompts: float,
                  mean_keys: float) -> float:
    """FLOPs to prefill ``tokens`` prompt tokens that complete ``prompts``
    prompts, each query seeing ``mean_keys`` keys on average: the head runs
    once per prompt, on its last token."""
    return tokens * token_flops(m, mean_keys, head=False) \
        + prompts * 2.0 * head_params(m)


def kv_token_bytes(m: Dict) -> int:
    """K and V bytes one token holds over all layers."""
    return m["n_layers"] * 2 * m["n_kv_heads"] * head_dim(m) * costs.BF16


def weight_bytes(m: Dict) -> int:
    """Bytes a step reads once: every layer's weights, the norms and the
    head (the embedding gather is a few rows and is left out)."""
    D = m["d_model"]
    norms = (2 * m["n_layers"] + 1) * D
    return (m["n_layers"] * layer_params(m) + head_params(m) + norms) \
        * costs.BF16


def decode_bytes(m: Dict, steps: float, rows: float,
                 mean_keys: float) -> float:
    """Bytes ``steps`` decode steps of ``rows`` rows in all need: the
    weights once a step, each row's live KV (``mean_keys`` keys on
    average) read, and its new token's K and V written."""
    return steps * weight_bytes(m) + rows * (mean_keys + 1) \
        * kv_token_bytes(m)
