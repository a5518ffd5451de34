"""Traffic is made from the seed: the same seed gives the same batches,
another seed gives others."""

import json
import os

import numpy as np

from lib import cell as cell_lib


def _mix(name):
    with open(os.path.join(cell_lib.BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)["parameters"]


def test_training_batches_reproduce_from_the_seed():
    gen = cell_lib.load_plugin("generators", "zipf_batches")
    mix = dict(_mix("train-b512x20"), global_batch=16, distinct_batches=3)
    a, b, c = (gen.make(mix, seed=s, vocab_size=793470) for s in (1, 1, 2))
    assert len(a) == 3 and a[0]["x"].shape == (16, 20)
    assert all(np.array_equal(x["x"], y["x"]) for x, y in zip(a, b))
    assert not np.array_equal(a[0]["x"], c[0]["x"])
    assert np.array_equal(a[0]["y"], np.roll(a[0]["x"], -1, axis=1))
    assert a[0]["w"].sum() == gen.tokens_per_step(mix) == 320
    assert a[0]["x"].max() < 793470 and a[0]["x"].min() >= 0
    # Zipf(1.3): the most frequent id takes about a quarter of the draws
    big = gen.make(dict(mix, global_batch=4096), seed=1, vocab_size=793470)
    assert 0.2 < np.mean(big[0]["x"] == 0) < 0.3
