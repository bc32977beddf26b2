"""The traffic generator: deterministic by seed, the stated lengths and
sharing, the same work for every seed."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec, traffic  # noqa: E402

BIG_SEED = 2 ** 31 + 977


@pytest.fixture(scope="module")
def chat():
    return spec.mix("chat")


@pytest.fixture(scope="module")
def code():
    return spec.mix("code-prefix")


def _key(reqs):
    return [(r.due_s, r.max_new, r.session, r.continues, r.prompt.tobytes())
            for r in reqs]


@pytest.mark.parametrize("mix_name", ["chat", "code-prefix"])
def test_same_seed_same_requests(mix_name):
    mix = spec.mix(mix_name)
    a = traffic.generate(mix, BIG_SEED, 20.0, 49152)
    b = traffic.generate(mix, BIG_SEED, 20.0, 49152)
    assert _key(a[0]) == _key(b[0]) and _key(a[1]) == _key(b[1])
    c = traffic.generate(mix, BIG_SEED + 1, 20.0, 49152)
    assert _key(a[1]) != _key(c[1])


def test_every_seed_gets_the_same_work(chat):
    a = traffic.generate(chat, 1, 40.0, 256000)[1]
    b = traffic.generate(chat, 2, 40.0, 256000)[1]
    assert len(a) == len(b) == round(chat["rate_per_s"] * 40.0)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)


def test_chat_lengths_match_the_mix(chat):
    _, reqs = traffic.generate(chat, 7, 400.0, 256000)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    assert p.min() >= chat["prompt"]["min"] and p.max() <= chat["prompt"]["max"]
    assert o.min() >= chat["output"]["min"] and o.max() <= chat["output"]["max"]
    assert abs(np.median(p) - chat["prompt"]["median"]) <= 2
    assert abs(np.median(o) - chat["output"]["median"]) <= 2
    due = np.array([r.due_s for r in reqs])
    assert due[0] == 0.0 and np.all(np.diff(due) >= 0) and due[-1] < 400.0
    assert all(0 < t < 256000 for r in reqs[:20] for t in r.prompt)
    assert not any(r.continues for r in reqs)


def test_code_sessions_share_their_prefix(code):
    lead, reqs = traffic.generate(code, 11, 60.0, 49152)
    s = code["sessions"]
    assert len(lead) == s["lead_in"] and all(r.due_s == 0 for r in lead)
    last = {r.session: r.prompt for r in lead}
    cont = 0
    for r in reqs:
        assert len(r.prompt) <= s["retire_at"]
        if r.continues:
            cont += 1
            prev = last[r.session]
            assert np.array_equal(r.prompt[:len(prev)], prev)
            grown = len(r.prompt) - len(prev)
            assert s["append"]["min"] <= grown <= s["append"]["max"]
        else:
            assert code["prompt"]["min"] <= len(r.prompt) \
                <= code["prompt"]["max"]
        last[r.session] = r.prompt
    share = cont / len(reqs)
    assert s["p_continue"] - 0.15 <= share <= s["p_continue"] + 0.01


def test_longest_request_fits_the_ring(code, chat):
    assert traffic.max_tokens(code) <= spec.config(
        "starcoder2-15b-l10")["engine"]["max_len"]
    assert traffic.max_tokens(chat) <= spec.config(
        "nemotron-4-15b-l4")["engine"]["max_len"]


def test_code_seeds_get_the_same_work_in_their_own_order(code):
    """Each seed draws its own order from the same sets: the same arrival
    gaps, outputs, lead-in and opener contexts and appends."""
    def work(seed):
        lead, reqs = traffic.generate(code, seed, 51.0, 49152)
        due = np.array([r.due_s for r in reqs] + [51.0])
        return {"gaps": sorted(np.round(np.diff(due), 9)),
                "outs": sorted(r.max_new for r in reqs),
                "lead": sorted(len(r.prompt) for r in lead),
                "lead_outs": sorted(r.max_new for r in lead),
                "opens": sorted(len(r.prompt) for r in reqs
                                if not r.continues),
                "appends": sum(len(r.prompt) for r in reqs if r.continues),
                "order": [(r.continues, r.max_new) for r in reqs]}
    a, b = work(3), work(BIG_SEED)
    for k in ("gaps", "outs", "lead", "lead_outs", "opens"):
        assert a[k] == b[k], k
    assert a["order"] != b["order"]


def test_strata_give_every_block_one_value_of_each_run(chat):
    """With ``strata`` s, each block of s consecutive places holds one value
    of each 1/s of the sorted set, and the chat mix orders its gaps,
    prompts and outputs so."""
    s = chat["strata"]
    assert s > 1
    vals = np.arange(41) * 10
    runs = np.array_split(vals, s)
    run_of = {int(v): i for i, r in enumerate(runs) for v in r}
    out = traffic.order(vals, np.random.default_rng(BIG_SEED), s)
    assert sorted(out) == list(vals)
    for b in range(0, len(out), s):
        block = [run_of[int(v)] for v in out[b:b + s]]
        assert sorted(block) == list(range(len(block)))
    reqs = traffic.generate(chat, BIG_SEED, 51.0, 256000)[1]
    rng = np.random.default_rng(BIG_SEED)
    due = traffic.arrivals(chat["rate_per_s"], 51.0, rng, s)
    outs = traffic.lengths(chat["output"], len(due), rng, s)
    plen = traffic.lengths(chat["prompt"], len(due), rng, s)
    assert [r.due_s for r in reqs] == list(due)
    assert [r.max_new for r in reqs] == list(outs)
    assert [len(r.prompt) for r in reqs] == list(plen)
    plain = dict(chat, strata=1)
    assert _key(traffic.generate(plain, BIG_SEED, 51.0, 256000)[1]) != \
        _key(reqs)
