"""The traffic generator: deterministic per seed, budget-filling, and the
same document sizes for every seed."""

import json
from pathlib import Path

import numpy as np
import pytest

from traffic import gen

DATA = Path(__file__).resolve().parent / "data"
# every mix of bench/traffic, and the CPU tests' own
MIXES = [(p.stem, p.parent) for p in sorted(gen.HERE.glob("*.json"))] + [
    ("tiny-mix", DATA)]


@pytest.mark.parametrize("name,where", MIXES)
def test_batches_fill_the_budget(name, where):
    mix = gen.load_mix(name, where)
    sizes = gen.batch_sizes(mix)
    assert len(sizes) == mix["batches"]
    for lens in sizes:
        assert sum(lens) == mix["budget"]
        assert max(lens) <= mix["max_len"]
        assert all(n >= 1 for n in lens)


@pytest.mark.parametrize("name,where", MIXES)
def test_same_seed_same_inputs(name, where):
    mix = gen.load_mix(name, where)
    seed = 2 ** 33 + 17
    o1, b1 = gen.replay_set(mix, seed, 151936)
    o2, b2 = gen.replay_set(mix, seed, 151936)
    assert o1 == o2
    for x, y in zip(b1, b2):
        for dx, dy in zip(x, y):
            np.testing.assert_array_equal(dx, dy)


def test_seeds_share_sizes_not_tokens():
    mix = gen.load_mix("github-1k")
    o1, b1 = gen.replay_set(mix, 1, 151936)
    o2, b2 = gen.replay_set(mix, 2, 151936)
    assert sorted(o1) == sorted(o2) == list(range(mix["batches"]))
    assert [[len(d) for d in b] for b in b1] == \
        [[len(d) for d in b] for b in b2] == gen.batch_sizes(mix)
    assert not np.array_equal(b1[0][0], b2[0][0])
    for b in b1:
        for d in b:
            assert d.dtype == np.int32 and d.min() >= 0 and d.max() < 151936


def test_long_documents_and_real_tokens():
    mix = gen.load_mix("github-1k")
    # documents are cut at the mix's limit
    assert max(max(b) for b in gen.batch_sizes(mix)) == mix["max_len"]
    docs = [np.zeros(n, np.int32) for n in (5, 1, 10)]
    assert gen.real_tokens(docs) == 4 + 0 + 9


def test_negative_seed_is_refused():
    with pytest.raises(ValueError):
        gen.replay_set(gen.load_mix("github-1k"), -1, 100)


def test_a_mix_lacking_a_parameter_is_refused(tmp_path):
    mix = gen.load_mix("github-1k")
    del mix["budget"]
    (tmp_path / "bad.json").write_text(json.dumps(mix))
    with pytest.raises(ValueError, match="budget"):
        gen.load_mix("bad", tmp_path)
