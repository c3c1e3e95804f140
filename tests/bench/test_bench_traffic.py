"""The traffic generator: every seed sends the same arrivals and sizes."""
import numpy as np

import _tiny
from bench import spec
from bench.traffic import Traffic, warmup_lengths


def test_open_loop_same_arrivals_and_sizes_for_every_seed():
    mix = _tiny.MIX
    a = Traffic(mix, seed=1, vocab=256, slots=4).open_loop(1.6)
    b = Traffic(mix, seed=2 ** 33 + 7, vocab=256, slots=4).open_loop(1.6)
    assert len(a) > 10
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == \
        [(r.due, len(r.prompt), r.max_new) for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    again = Traffic(mix, seed=1, vocab=256, slots=4).open_loop(1.6)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))


def test_rate_and_lengths():
    mix = spec.cell("deepseek-llm-7b.collm.chat")["traffic"]
    t = Traffic(mix, seed=3, vocab=102400, slots=8)
    reqs = t.open_loop(51.0)
    assert abs(len(reqs) / 51.0 - mix["arrival"]["rate"]) < 0.3
    assert all(8 <= len(r.prompt) <= 128 and 60 <= r.max_new <= 120
               for r in reqs)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 102400
               for r in reqs)


def test_closed_loop_clients():
    mix = spec.cell("yi-6b.collm.summarize")["traffic"]
    t = Traffic(mix, seed=5, vocab=64000, slots=24)
    first = t.first_closed()
    assert [r.client for r in first] == list(range(24))
    assert first[0].due == 0.0 and first[-1].due < mix["stagger_s"]
    nxt = t.next_for(3, 0.0)
    assert nxt.client == 3 and nxt.index == 24


def test_warmup_lengths_cover_power_of_two_buckets():
    assert warmup_lengths({"min": 8, "max": 128}) == [8, 9, 16, 17, 32, 33,
                                                      64, 65, 128]
    assert warmup_lengths({"min": 200, "max": 1024}) == [200, 256, 257, 512,
                                                         513, 1024]
