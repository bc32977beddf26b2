"""bench/run.py refuses to report from anything but the chip, and takes a
new architecture as files only."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "nemotron4-15b.chat"


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    return not any(line.lstrip().startswith("{")
                   for line in stdout.splitlines())


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own
    directories has no system to measure."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


# A mixture-of-experts model (the registry's MoE family at toy widths:
# SwiGLU experts, 4 of them, top-2), which the dense module refuses; its
# architecture module is tests/bench/data/moe_gqa.py.  The limit is this
# size's own: sound runs read 0.0001-0.0045 row std on four seeds, the
# fp8 control 0.079-0.67.
MOE = {"name": "tiny-moe", "reference": "moe_gqa",
       "model": {"family": "moe", "n_layers": 2, "d_model": 64,
                 "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                 "vocab": 512, "activation": "swiglu", "n_experts": 4,
                 "top_k": 2, "rope_theta": 10000.0, "norm_eps": 1e-5,
                 "tie_embeddings": False, "param_dtype": "bfloat16",
                 "compute_dtype": "bfloat16"},
       "engine": {"max_len": 128, "domains": 1, "max_batch": 4,
                  "pool_streams": 8},
       "check": {"gap_max_std": 0.03, "sample_tokens": 512,
                 "sample_requests": 8}}
MOE_MIX = {"rate_per_s": 6.0, "strata": 4,
           "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 16, "max": 80},
           "output": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                      "min": 8, "max": 28}}


def _files(root: Path):
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_architecture_is_files_only(tmp_path):
    """A copy of bench/ gains a configuration, its architecture module, a
    mix and a cell, with no file it had edited: the weights are drawn in
    the engine's layout, a run is served and checked, and the per-layer
    readers count with the new module."""
    from bench import model as bmodel
    from bench import run, serve, spec
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "tests" / "bench" / "data" / "moe_gqa.py",
                bench / "references" / "moe_gqa.py")
    (bench / "configs" / "tiny-moe.json").write_text(json.dumps(MOE))
    (bench / "traffic" / "tiny-chat.json").write_text(json.dumps(MOE_MIX))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": "tiny-moe.chat", "config": "tiny-moe",
                            "traffic": "tiny-chat", "chips": 1,
                            "why": "added as files"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    had = _files(ROOT / "bench")
    have = _files(bench)
    assert {k: have[k] for k in had} == had

    m = MOE["model"]
    with pytest.raises(ValueError):
        spec.reference("dense_gqa").layout(m)
    c = spec.cell("tiny-moe.chat", root=tmp_path)
    seed = 2 ** 31 + 12345
    serve.check_layout(serve.model_config(c["config"]),
                       bmodel.draw(c["arch"].layout(m), seed,
                                   jax.devices("cpu")[0]))

    out = run.run_cell("tiny-moe.chat", seed, 2.0, False, require_tpu=False,
                       cell=c, cache=False, control=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 12
    assert out["control"]["correct"] is False, out["control"]

    # top-2 of 4 experts active a token; a decode step reads all four
    arch, dense = c["arch"], spec.reference("dense_gqa")
    glu = dict(m, n_experts=0)
    assert arch.token_flops(m, 5, head=False) == dense.token_flops(
        glu, 5, head=False) + 2 * 2 * (64 * 4 + 3 * 64 * 128)
    assert arch.weight_bytes(m) == dense.weight_bytes(glu) + 2 * 2 * (
        64 * 4 + 3 * 3 * 64 * 128)
    rec = {"trace": {"window_s": 0.5}, "peaks": {"bf16_flops": 1e12},
           "model": m, "arch": arch,
           "counters": {"tokens_processed": 35, "decode_committed_tokens": 3,
                        "prefills": 1, "decode_row_forwards": 3},
           "requests": [{"prompt_len": 8, "prefix_tokens": 0, "n_out": 3}]}
    step = arch.prefill_flops(m, 32, 1, 4.5) \
        + 3 * arch.token_flops(m, 9.5, head=True)
    read = spec.metric_reader("step_mfu", bench)
    assert read(rec) == pytest.approx(100 * step / (0.5 * 1e12))
    assert read(dict(rec, arch=dense)) != pytest.approx(read(rec))
