"""The traffic generator: every seed gets the same batches, its
documents in another order, and the documents the program reads are the
ones the reference reads."""
import hashlib
import json

import numpy as np
import pytest

from traffic import Traffic, load_mix

BIG_SEED = 2 ** 31 + 12345


# the batches the github2k limits were read on (sha256 of the pool's
# length lists as JSON): a change to the generator or the mix that moves
# them has to read the limits anew on the chip
MEASURED_POOLS = {
    "github2k": "951858d567ca65192aa74454a383290ed7d8804d5804f7b83e5a719291bbfb68",
}


@pytest.mark.parametrize("name", ["github2k"])
def test_every_seed_trains_on_the_same_batches(name):
    mix = load_mix(name)
    n_steps = 2 * mix["pool_steps"]
    batches = []
    for seed in (0, 7, BIG_SEED):
        tr = Traffic(mix, 8192, seed)
        steps = [tr.step_lengths(s) for s in range(n_steps)]
        for ls in steps:
            assert sum(ls) == mix["tokens_per_step"]
            assert min(ls) >= mix["min_len"] and max(ls) <= mix["max_len"]
        batches.append([sorted(ls) for ls in steps])
    assert batches[0] == batches[1] == batches[2]


@pytest.mark.parametrize("name", sorted(MEASURED_POOLS))
def test_pool_is_the_measured_pool(name):
    pool = Traffic(load_mix(name), 8192, BIG_SEED).pool
    digest = hashlib.sha256(json.dumps(pool).encode()).hexdigest()
    assert digest == MEASURED_POOLS[name]


def test_seed_orders_batches_and_fills_tokens():
    mix = load_mix("github2k")
    a, b = Traffic(mix, 8192, 1), Traffic(mix, 8192, BIG_SEED)
    assert [a.step_lengths(s) for s in range(6)] != \
        [b.step_lengths(s) for s in range(6)]
    assert a.step_lengths(3) == Traffic(mix, 8192, 1).step_lengths(3)
    ta = a.tokens(0, 0, 0, 64)
    assert ta.dtype == np.int32 and ta.min() >= 0 and ta.max() < 8192
    assert not np.array_equal(ta, b.tokens(0, 0, 0, 64))
    assert np.array_equal(ta, Traffic(mix, 8192, 1).tokens(0, 0, 0, 64))


def test_labels_are_the_next_tokens_of_the_same_document():
    tr = Traffic(load_mix("github2k"), 512, 3)
    tok, lab = tr.document(2, 1)
    assert np.array_equal(tok[1:], lab[:-1])
    assert np.array_equal(tr.tokens(2, 1, 5, 9), tok[5:9])
