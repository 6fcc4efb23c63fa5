"""The generator: the same seed gives the same requests; every seed offers
the same lengths per block, in another order."""
import json
from collections import Counter

import tiny
from bench import spec, traffic


def _mix(name):
    return json.loads((spec.BENCH_DIR / "traffic" / f"{name}.json").read_text())


def test_same_seed_same_requests():
    mix = tiny.MIX
    a = traffic.generate(mix, 2 ** 33 + 5, 10, 49155)
    b = traffic.generate(mix, 2 ** 33 + 5, 10, 49155)
    assert [(r.arrival_s, r.prompt, r.max_new, r.temperature) for r in a] \
        == [(r.arrival_s, r.prompt, r.max_new, r.temperature) for r in b]


def test_seeds_share_the_work_per_block():
    """Every block holds the same lengths; every seed sends them in the
    same order and at the same times, with its own token ids."""
    for mix in (tiny.MIX, _mix("batch")):
        blk = mix["block"]
        a = traffic.generate(mix, 11, 10, 49155)
        b = traffic.generate(mix, 12, 10, 49155)
        key = lambda r: (len(r.prompt), r.max_new, r.greedy)  # noqa: E731
        assert Counter(map(key, a[:blk])) == Counter(map(key, a[blk:2 * blk]))
        assert [key(r) for r in a] == [key(r) for r in b]
        assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
        assert a[0].prompt != b[0].prompt


def test_lengths_within_the_mix():
    mix = tiny.MIX
    for r in traffic.generate(mix, 3, 10, 49155):
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
