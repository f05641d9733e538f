import json

import numpy as np
import pytest

import traffic
from cells import BENCH

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _take(mix, seed, n):
    src = traffic.requests(mix, 151936, seed)
    return [next(src) for _ in range(n)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a, b = _take(mix, 2**31 + 5, 50), _take(mix, 2**31 + 5, 50)
    assert [(r.prompt, r.max_new, r.gap_s) for r in a] == \
        [(r.prompt, r.max_new, r.gap_s) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_every_deck_is_the_same_work_on_the_grid(name):
    mix = _mix(name)
    n = mix["deck"]
    work = lambda reqs: sorted((len(r.prompt), r.max_new, r.gap_s)
                               for r in reqs)
    decks = [_take(mix, s, 3 * n) for s in (1, 2)]
    first = work(decks[0][:n])
    for reqs in decks:
        for k in range(3):
            assert work(reqs[k * n:(k + 1) * n]) == first
    assert {len(r.prompt) for r in decks[0]} <= \
        set(mix["prompt_len"]["values"])
    outs = [r.max_new for r in decks[0]]
    assert mix["output_len"]["min"] <= min(outs)
    assert max(outs) <= mix["output_len"]["max"]
    assert [r.prompt for r in decks[0][:n]] != [r.prompt
                                               for r in decks[1][:n]]


def test_prompt_lengths_follow_the_weights():
    mix = _mix("chat-backlog")
    d = traffic.deck(mix)
    w = np.asarray(mix["prompt_len"]["weights"], float)
    want = w / w.sum() * mix["deck"]
    got = [sum(p == v for p, _, _ in d) for v in mix["prompt_len"]["values"]]
    assert np.all(np.abs(np.asarray(got) - want) < 1)


def test_poisson_deck_spans_deck_over_rate():
    mix = _mix("chat-poisson")
    gaps = [g for _, _, g in traffic.deck(mix)]
    assert sum(gaps) == pytest.approx(mix["deck"] / mix["rate_rps"])
    assert min(gaps) > 0
