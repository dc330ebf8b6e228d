"""The input generators: the same seed gives the same inputs, and every
seed gives the same client sizes."""
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import inputs  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _model():
    c = _load("tiny_config.json")
    return {"vocab_size": c["vocab_size"], "n_classes": c["num_labels"],
            "seq_len": c["max_position_embeddings"]}


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_same_seed_same_inputs(seed):
    traffic = _load("tiny_feddf.json")
    a = inputs.make_inputs(seed, _model(), traffic)
    b = inputs.make_inputs(seed, _model(), traffic)
    for s in ("train", "val", "test"):
        np.testing.assert_array_equal(getattr(a, s).x, getattr(b, s).x)
        np.testing.assert_array_equal(getattr(a, s).y, getattr(b, s).y)
    np.testing.assert_array_equal(a.pool, b.pool)
    for p, q in zip(a.parts, b.parts, strict=True):
        np.testing.assert_array_equal(p, q)


def test_seeds_differ_but_sizes_do_not():
    traffic = _load("tiny_feddf.json")
    a = inputs.make_inputs(1, _model(), traffic)
    b = inputs.make_inputs(2, _model(), traffic)
    assert not np.array_equal(a.train.x, b.train.x)
    assert sorted(map(len, a.parts)) == sorted(map(len, b.parts))
    assert [len(p) for p in a.parts] != [len(p) for p in b.parts] or \
        any(not np.array_equal(p, q) for p, q in zip(a.parts, b.parts))


def test_partition_is_disjoint_and_complete():
    traffic = _load("tiny_feddf.json")
    inp = inputs.make_inputs(3, _model(), traffic)
    allidx = np.concatenate(inp.parts)
    assert len(allidx) == len(np.unique(allidx)) == len(inp.train.y)
    assert min(map(len, inp.parts)) >= traffic["local_batch_size"]
    n = traffic["n_samples"]
    assert len(inp.test.y) == int(n * traffic["test_frac"])
    assert len(inp.val.y) == int(n * traffic["val_frac"])
    m = _model()
    assert inp.train.x.max() < m["vocab_size"] and inp.train.x.min() >= 0
    assert inp.pool.shape == (traffic["pool"], m["seq_len"])


def test_markers_carry_the_class():
    m = _model()
    split = inputs.token_sequences(inputs.rng_for(5, 0), 400, m["n_classes"],
                                   m["vocab_size"], m["seq_len"], 0.5)
    first_marker = m["vocab_size"] - m["n_classes"]
    for x, y in zip(split.x, split.y):
        marks = x[x >= first_marker]
        assert np.all(marks == first_marker + y)
