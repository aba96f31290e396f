"""The seconds-denominated open-loop generator and the training batches."""
import numpy as np
import pytest

from yardstick import loadgen, registry

CHAT = registry.load_json(registry.BENCH / "traffic" / "chat.json")


def _key(s):
    return [(a.t, a.prompt.tolist(), a.max_new) for a in s]


def test_same_seed_same_schedule():
    a = loadgen.schedule(CHAT, 4.0, 30.0, 2**40 + 7, 151936)
    b = loadgen.schedule(CHAT, 4.0, 30.0, 2**40 + 7, 151936)
    assert _key(a) == _key(b)
    assert len(a) == 120


def test_seeds_share_the_work_in_another_order():
    a = loadgen.schedule(CHAT, 4.0, 30.0, 1, 151936)
    b = loadgen.schedule(CHAT, 4.0, 30.0, 2, 151936)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    ta, tb = (np.array([x.t for x in s]) for s in (a, b))
    assert not np.array_equal(ta, tb)  # arrival times of the seed's own
    # the same gaps in another order (each run leaves out the one after its last arrival)
    ga, gb = (set(np.round(np.diff(t), 9)) for t in (ta, tb))
    assert len(ga) == len(gb) == 119 and len(ga ^ gb) <= 2
    assert list(ta) == sorted(ta) and ta[0] == 0.0 and ta[-1] < 30.0


def test_lengths_follow_the_mix():
    s = loadgen.schedule(CHAT, 40.0, 100.0, 3, 151936)
    p = np.array([len(x.prompt) for x in s])
    o = np.array([x.max_new for x in s])
    assert p.min() >= 64 and p.max() <= 1024 and o.min() >= 16 and o.max() <= 512
    assert np.mean(p) == pytest.approx(192, rel=0.03)
    assert np.mean(o) == pytest.approx(88, rel=0.03)
    assert len({tuple(x.prompt.tolist()) for x in s}) == len(s)  # unshared prompts


def test_train_batches_are_seeded_and_distinct():
    t = {"batch_per_node": 2, "seq_len": 16, "token_zipf": 1.2}
    a = loadgen.train_batches(t, 2, 1000, 3, 5)
    b = loadgen.train_batches(t, 2, 1000, 3, 5)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert a[0].shape == (2, 2, 16) and a[0].dtype == np.int32
    assert not np.array_equal(a[0], a[1])
    # the first batches do not depend on how many are drawn
    assert np.array_equal(loadgen.train_batches(t, 2, 1000, 1, 5)[0], a[0])
