"""The schedule is a pure function of ``--seed``; every seed gets the same
traffic shape (lengths, gaps, order) and its own token ids."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmarks.harness import traffic  # noqa: E402


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


BIG = 2 ** 31 + 12345  # the driver's seeds do not fit 32 signed bits


@pytest.mark.parametrize("name,load", [("chat-open", {"rate_rps": 10.0}), ("backlog-closed", {})])
def test_schedule_is_a_pure_function_of_the_seed(name, load):
    a = traffic.build_schedule(mix(name), load, seed=BIG, seconds=30, vocab=92544)
    b = traffic.build_schedule(mix(name), load, seed=BIG, seconds=30, vocab=92544)
    c = traffic.build_schedule(mix(name), load, seed=BIG + 1, seconds=30, vocab=92544)
    assert a == b and a != c
    shape = lambda s: [{k: v for k, v in r.items()} for r in s["requests"]]  # noqa: E731
    assert shape(a) == shape(c)  # lengths, gaps and their order do not depend on the seed
    assert traffic.request_prompt(a, a["requests"][3]) == traffic.request_prompt(b, b["requests"][3])
    assert traffic.request_prompt(a, a["requests"][3]) != traffic.request_prompt(c, c["requests"][3])


def test_open_loop_lengths_rates_and_bounds():
    s = traffic.build_schedule(mix("chat-open"), {"rate_rps": 10.0}, seed=7, seconds=30, vocab=1000)
    reqs = s["requests"]
    assert s["loop"] == "open" and s["stream"] and s["endpoint"] == "/generate/stream"
    due = [r["due"] for r in reqs]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 35.0
    assert 280 <= len(reqs) <= 420                       # Poisson at 10/s over 35 s
    assert all(32 <= r["prompt_len"] <= 1024 and 16 <= r["new_tokens"] <= 256 for r in reqs)
    assert all(r["prompt_len"] + r["new_tokens"] <= 1280 for r in reqs)  # fits max_len
    toks = traffic.request_prompt(s, reqs[0])
    assert len(toks) == reqs[0]["prompt_len"] and all(3 <= t < 1000 for t in toks)


def test_closed_loop_pool():
    s = traffic.build_schedule(mix("backlog-closed"), {}, seed=7, seconds=30, vocab=1000)
    assert s["loop"] == "closed" and s["clients"] == 80 and not s["stream"]
    assert len(s["requests"]) == 4096 and "due" not in s["requests"][0]


def test_generator_options_later_mixes_will_use():
    spec = dict(mix("chat-open"), burst={"on_s": 2, "off_s": 4, "on_factor": 3, "off_factor": 0.3},
                shared_prefix={"groups": 4, "len": {"dist": "fixed", "value": 64}, "zipf_s": 1.0},
                prompt_len={"dist": "mixture", "parts": [
                    {"weight": 9, "dist": "uniform", "min": 100, "max": 200},
                    {"weight": 1, "dist": "fixed", "value": 900}]})
    s = traffic.build_schedule(spec, {"rate_rps": 10.0}, seed=7, seconds=30, vocab=1000)
    reqs = s["requests"]
    assert 340 <= len(reqs) <= 500                       # mean rate 12/s over 35 s
    in_on = sum(1 for r in reqs if r["due"] % 6 < 2)
    assert in_on > 0.7 * len(reqs)                       # 6 of every 7.2 arrivals land in the bursts
    assert {r["prompt_len"] for r in reqs} & {900} and all(
        100 <= r["prompt_len"] <= 200 or r["prompt_len"] == 900 for r in reqs)
    same = [r for r in reqs if r["prefix_group"] == 0]
    a, b = traffic.request_prompt(s, same[0]), traffic.request_prompt(s, same[1])
    assert a[:64] == b[:64] and a[64:70] != b[64:70] and len(a) == same[0]["prompt_len"]
    with pytest.raises(ValueError):
        traffic.draw_lengths({"dist": "nope"}, 1, None)
