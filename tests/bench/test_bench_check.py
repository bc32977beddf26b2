"""The check that decides ``correct``, driven through a whole run at a size a
CPU holds: a sound run passes, the fp8 control fails, and each fault the
served path can have makes ``correct`` false.

The run skips only the harness's look for a chip (``require_tpu=False``):
weights, engine, warm-up, lead-in, the open-loop window, the drain and the
check against the plain reference all run.  The tiny model keeps the cell's
architecture with small widths, so its gaps are smaller than the chip's; the
limit here is this size's own, set between its readings on four seeds
(sound runs read 0.0-0.004 row std, the fp8 control 0.11-0.18, the faults
below 0.15 or more).
"""
import copy
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import run, spec  # noqa: E402

SEED = 2 ** 31 + 12345
LIMIT = 0.03


E2E = {"ttft_p50_ms": "ms", "ttft_p95_ms": "ms", "tpot_p95_ms": "ms",
       "out_tok_s": "tokens/s", "setup_s": "s"}


def tiny_cell():
    """The code-completion mix (sessions, prefix hits) on StarCoder2's
    architecture at small widths, reporting every end-to-end metric the
    harness computes."""
    c = {"workload": {"name": "tiny"}, "chips": 1,
         "config": copy.deepcopy(spec.config("starcoder2-15b-l10")),
         "arch": spec.reference("dense_gqa"),
         "mix": copy.deepcopy(spec.mix("code-prefix")),
         "end_to_end": [{"name": k, "unit": u} for k, u in E2E.items()],
         "per_layer": []}
    c["config"]["model"].update(n_layers=2, d_model=64, n_heads=4,
                                n_kv_heads=2, head_dim=16, d_ff=128,
                                vocab=512)
    c["config"]["engine"] = {"max_len": 128, "domains": 1, "max_batch": 4,
                             "pool_streams": 8}
    c["config"]["check"] = {"gap_max_std": LIMIT, "sample_tokens": 512,
                            "sample_requests": 8}
    m = c["mix"]
    m.update(rate_per_s=6.0,
             prompt={"dist": "lognormal", "median": 40, "sigma": 0.5,
                     "min": 16, "max": 80},
             output={"dist": "lognormal", "median": 16, "sigma": 0.5,
                     "min": 8, "max": 28})
    m["sessions"].update(append={"dist": "uniform", "min": 4, "max": 16},
                         retire_at=100, lead_in=2, max_open=4)
    return c


def _run(**kw):
    return run.run_cell("tiny", SEED, 2.0, False, require_tpu=False,
                        cell=tiny_cell(), cache=False, **kw)


@pytest.fixture(scope="module")
def sound():
    return _run(control=True)


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] == 12
    assert list(sound)[-1] == "checks"
    assert sound["checks"]["gap_max_std"]["value"] < LIMIT / 5
    assert set(sound["metrics"]) == set(E2E)


def test_fp8_control_fails_the_limit(sound):
    ctrl = sound["control"]
    assert ctrl["correct"] is False, ctrl
    assert ctrl["checks"]["gap_max_std"]["limit"] == LIMIT
    assert ctrl["checks"]["gap_max_std"]["value"] > 3 * LIMIT


def _alter_tokens(monkeypatch):
    """A token altered where it is produced: the greedy pick is off by one."""
    from repro.models import decode as dec
    orig = dec.next_token_ids

    def shifted(logits, n_tokens):
        t = orig(logits, n_tokens)
        return jnp.where(t >= 0, (t + 1) % logits.shape[-1], t)

    monkeypatch.setattr(dec, "next_token_ids", shifted)
    return lambda eng: None


def _stale(step_name):
    """A step that returns its state unchanged: the pool comes back as it
    went in, so the step's KV writes are lost."""
    def fault(eng):
        step = getattr(eng, step_name)

        def unchanged(params, storage, *args):
            keep = jax.tree.map(jnp.copy, storage)
            logits, _ = step(params, storage, *args)
            return logits, keep

        setattr(eng, step_name, unchanged)
    return fault


@pytest.mark.parametrize("fault", ["token_altered", "decode_state_unchanged",
                                   "chunk_state_unchanged"])
def test_faults_make_correct_false(fault, monkeypatch):
    if fault == "token_altered":
        f = _alter_tokens(monkeypatch)
    elif fault == "decode_state_unchanged":
        f = _stale("_paged_decode")
    else:
        f = _stale("_paged_chunk")
    out = _run(fault=f)
    assert not out["correct"], out["checks"]
    assert out["checks"]["gap_max_std"]["value"] > LIMIT


def test_sample_holds_the_longest_and_a_prefix_hit():
    from bench import check

    class R:
        def __init__(self, p, g, pre):
            self.prompt = np.zeros(p, np.int32)
            self.generated = list(range(g))
            self.prefix_tokens = pre

    reqs = [R(10, 5, 0) for _ in range(30)] + [R(300, 40, 0), R(50, 5, 48)]
    smp = check.sample(reqs, 3)
    assert smp[0] is reqs[30] and smp[1] is reqs[31]
    assert smp == check.sample(reqs, 3)
    assert len(smp) == 8
    assert len(check.sample(reqs, 3, tokens=10 ** 6, requests=64)) == 32
