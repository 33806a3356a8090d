"""The traffic generator repeats by seed and gives every seed the same
work on the same schedule, with token ids of its own."""
import json
from collections import Counter

import numpy as np

from chip_cells import CHIP
from benchmarks.chip import traffic

CHAT = json.loads((CHIP / "traffic" / "chat.json").read_text())


def _sig(reqs):
    return [(r.due, len(r.prompt), r.max_new, r.prompt[:4].tolist())
            for r in reqs]


def test_open_loop_repeats_by_seed():
    a = traffic.open_loop(CHAT, 2**31 + 5, 50.0, 32000)
    b = traffic.open_loop(CHAT, 2**31 + 5, 50.0, 32000)
    assert _sig(a) == _sig(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.prompt, y.prompt)
    c = traffic.open_loop(CHAT, 2**31 + 6, 50.0, 32000)
    assert _sig(a) != _sig(c)


def test_every_seed_gets_the_same_work():
    runs = [traffic.open_loop(CHAT, s, 50.0, 32000) for s in (1, 2, 9**9)]
    rate = CHAT["arrival"]["rate_per_s"]
    for reqs in runs:
        assert len(reqs) == round(rate * 50.0)
        due = [r.due for r in reqs]
        assert due == sorted(due) and due[0] == 0.0 and due[-1] < 50.0
        assert all(r.prompt.min() >= 0 and r.prompt.max() < 32000
                   for r in reqs)
    multisets = [(Counter(len(r.prompt) for r in reqs),
                  Counter(r.max_new for r in reqs),
                  np.round(np.sort(np.diff([r.due for r in reqs])), 6))
                 for reqs in runs]
    for m in multisets[1:]:
        assert m[0] == multisets[0][0] and m[1] == multisets[0][1]
    assert set(multisets[0][0]) == {128, 256, 512, 1024, 2048}
    assert min(multisets[0][1]) >= 16 and max(multisets[0][1]) <= 1024


def test_prompt_shares_follow_the_weights():
    reqs = traffic.open_loop(CHAT, 3, 100.0, 32000)
    n = len(reqs)
    got = Counter(len(r.prompt) for r in reqs)
    for v, w in zip(CHAT["prompt_len"]["values"],
                    CHAT["prompt_len"]["weights"]):
        assert abs(got[v] - w * n) <= 1


def test_token_batches_differ_by_step_and_repeat_by_seed():
    mix = {"global_batch": 4, "seq": 16}
    a0 = traffic.token_batch(mix, 7, 0, 100)
    assert a0.shape == (4, 16) and a0.dtype == np.int32
    np.testing.assert_array_equal(a0, traffic.token_batch(mix, 7, 0, 100))
    assert not np.array_equal(a0, traffic.token_batch(mix, 7, 1, 100))
    assert not np.array_equal(a0, traffic.token_batch(mix, 8, 0, 100))


def test_warmup_covers_every_prompt_length():
    ps = traffic.warmup_prompts(CHAT, 1, 16, 32000)
    assert len(ps) == 16
    assert {len(p) for p in ps} == {128, 256, 512, 1024, 2048}


def test_every_seed_gets_one_schedule():
    sched = lambda reqs: [(r.due, len(r.prompt), r.max_new) for r in reqs]
    a = traffic.open_loop(CHAT, 11, 50.0, 32000)
    b = traffic.open_loop(CHAT, 2**31 + 12, 50.0, 32000)
    assert sched(a) == sched(b)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
