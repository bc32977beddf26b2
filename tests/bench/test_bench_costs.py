"""The benchmark's FLOP and byte functions against hand-computed cases:
those of every architecture (``bench/costs.py``) and the dense module's
(``bench/references/dense_gqa.py``), with the dense module's weight layout
and draw pinned to what they were before they moved there."""
import hashlib
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import costs, peaks, spec  # noqa: E402
from bench import model as bmodel  # noqa: E402

dense = spec.reference("dense_gqa")

# D=8, F=16, 4 query heads over 2 KV heads of width 2, 3 layers, vocab 300
# (padded to 512), a plain (non-gated) MLP
M = {"n_layers": 3, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 2, "d_ff": 16, "vocab": 300, "activation": "gelu"}


def test_layer_and_head_params():
    # attention: 8*(4+2*2)*2 + 4*2*8 = 128 + 64; MLP 2*8*16 = 256
    assert dense.layer_params(M) == 448
    assert costs.vocab_padded(M) == 512
    assert dense.head_params(M) == 8 * 512


def test_gated_mlp_counts_three_matrices():
    glu = dict(M, activation="swiglu")
    assert dense.layer_params(glu) == 128 + 64 + 3 * 8 * 16


def test_token_flops_head_only_when_asked():
    # matmuls 2*3*448 = 2688; attention 3 layers * 2*2 * 4 heads * 2 * 5 keys
    assert dense.token_flops(M, 5, head=False) == 2688 + 480
    assert dense.token_flops(M, 5, head=True) == 2688 + 480 + 2 * 4096


def test_prefill_charges_the_head_on_the_last_prompt_token_only():
    # one 4-token prompt: token t sees t+1 keys (mean 2.5), one head
    assert dense.prefill_flops(M, 4, 1, 2.5) == 4 * 2688 + 96 * 10 + 2 * 4096
    # 16 tokens of prompts still mid-prefill: no head at all
    assert dense.prefill_flops(M, 16, 0, 2.5) == 16 * (2688 + 96 * 2.5)


def test_kv_bytes_follow_the_kv_heads_not_the_query_heads():
    # 3 layers * (K and V) * 2 KV heads * 2 wide * 2 bytes
    assert dense.kv_token_bytes(M) == 48
    assert dense.kv_token_bytes(dict(M, n_kv_heads=4)) == 96


def test_decode_bytes():
    weights = (3 * 448 + 4096 + 7 * 8) * 2
    assert dense.weight_bytes(M) == weights
    # one step of two rows reading 3 and 5 keys (mean 4), each writing one
    # new token; a second step reads the weights again
    assert dense.decode_bytes(M, 1, 2, 4.0) == weights + 8 * 48 + 2 * 48
    assert dense.decode_bytes(M, 2, 2, 4.0) == 2 * weights + 10 * 48


def test_ring_kernel_cost_counts_entered_tiles():
    # one q block of 16 over [32 ring | 16 chunk (padded to 32)]: both tiles
    c = costs.ring_kernel_cost(8, 16, 2, 64, 2, ring=32, bq=16, bkv=32)
    assert c["flops"] == 4 * 8 * 16 * (2 * 32) * 2
    assert c["bytes"] == (2 * 8 * 16 * 2 + 2 * 2 * 64 * 2) * 2
    # a third KV tile starting 32 past the ring is beyond every query
    c = costs.ring_kernel_cost(8, 16, 2, 96, 2, ring=32, bq=8, bkv=32)
    assert c["flops"] == 4 * 8 * 16 * (2 * 32) * 2


def test_roofline_takes_the_larger_bound():
    assert costs.roofline_seconds(2e12, 1e9, 1e12, 1e12) == 2.0
    assert costs.roofline_seconds(1e9, 3e12, 1e12, 1e12) == 3.0


def test_peaks_by_device_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")


def test_mean_keys_by_hand():
    """A prompt of 8 with 4 from the prefix cache prefills positions 4..7:
    5..8 keys, mean 6.5; 3 outputs decode 2 rows at positions 8, 9: 9.5."""
    reqs = [{"prompt_len": 8, "prefix_tokens": 4, "n_out": 3}]
    assert costs.prefill_mean_keys(reqs) == 6.5
    assert costs.decode_mean_keys(reqs) == 9.5
    assert costs.prefill_mean_keys([{"prompt_len": 8, "prefix_tokens": 8,
                                     "n_out": 1}]) == 0.0
    assert costs.decode_mean_keys([{"prompt_len": 8, "prefix_tokens": 0,
                                    "n_out": 1}]) == 0.0


# nemotron-4-15b-l4's tree as bench/model.py laid it out before the dense
# layout moved into its module: (path, shape, std or "ones"), flatten order
NEMOTRON_LAYOUT = [
    ("['embed']", (256000, 6144), 1.0),
    ("['final_norm']", (6144,), "ones"),
    ("['head']", (6144, 256000), 0.01275775907699572),
    ("['layers']['attn']['wk']", (4, 6144, 8, 128), 0.01275775907699572),
    ("['layers']['attn']['wo']", (4, 48, 128, 6144), 0.004510548978043952),
    ("['layers']['attn']['wq']", (4, 6144, 48, 128), 0.01275775907699572),
    ("['layers']['attn']['wv']", (4, 6144, 8, 128), 0.01275775907699572),
    ("['layers']['ln1']", (4, 6144), "ones"),
    ("['layers']['ln2']", (4, 6144), "ones"),
    ("['layers']['mlp']['wi']", (4, 6144, 24576), 0.01275775907699572),
    ("['layers']['mlp']['wo']", (4, 24576, 6144), 0.002255274489021976),
]


def test_dense_layout_is_the_recorded_tree():
    lay = dense.layout(spec.config("nemotron-4-15b-l4")["model"])
    flat, _ = jax.tree.flatten_with_path(lay, is_leaf=bmodel._is_leaf)
    assert [(jax.tree_util.keystr(p), shape, init)
            for p, (shape, init) in flat] == NEMOTRON_LAYOUT


def test_dense_draw_matches_the_recorded_hash():
    """The same seed draws bit-identical weights: sha256 over each leaf's
    path, shape, dtype and bytes, recorded from bench/model.py before the
    layout moved."""
    m = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab": 300,
         "activation": "squared_relu"}
    w = bmodel.draw(dense.layout(m), 2 ** 31 + 12345, jax.devices("cpu")[0])
    h = hashlib.sha256()
    for p, a in jax.tree.flatten_with_path(w)[0]:
        a = np.asarray(a)
        h.update(f"{jax.tree_util.keystr(p)}{a.shape}{a.dtype}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == ("ab677af6ba547411e0940092d8ba5f80"
                             "29aa08f51985e5f6232882d3ea8b0fd2")
