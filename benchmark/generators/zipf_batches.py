"""Training batches: Zipf-distributed word ids, made from the seed.

Parameters (the traffic file's ``parameters``):

- ``global_batch``: sequences per step over all chips of the cell.
- ``num_steps``: tokens per sequence (the unroll length).
- ``zipf_a``: the exponent of the id distribution. The skew decides how
  many distinct table rows a step touches, which is what the sparse
  path's gather, exchange and scatter cost.
- ``distinct_batches``: how many batches are made before the window;
  the run cycles through them.

The arithmetic is ``models/lm1b.make_batch``'s, copied so that the
yardstick does not move with the program: ids ``(zipf(a) - 1) mod
vocab``, the label of a position is the next id of the row, weights 1.
"""

from __future__ import annotations

import numpy as np


def _batch(rng: np.random.Generator, sequences: int, num_steps: int,
           zipf_a: float, vocab_size: int) -> dict:
    x = (rng.zipf(zipf_a, size=(sequences, num_steps)) - 1) % vocab_size
    y = np.roll(x, -1, axis=1)
    return {"x": x.astype(np.int32), "y": y.astype(np.int32),
            "w": np.ones((sequences, num_steps), np.float32)}


def make(mix: dict, seed: int, vocab_size: int) -> list:
    rng = np.random.default_rng([int(seed), 1])
    return [_batch(rng, int(mix["global_batch"]), int(mix["num_steps"]),
                   float(mix["zipf_a"]), vocab_size)
            for _ in range(int(mix["distinct_batches"]))]


def make_eval(mix: dict, seed: int, vocab_size: int, sequences: int) -> dict:
    """A batch of ``sequences`` rows from the same distribution and
    another stream of the seed, for the reference comparison."""
    rng = np.random.default_rng([int(seed), 2])
    return _batch(rng, sequences, int(mix["num_steps"]),
                  float(mix["zipf_a"]), vocab_size)


def tokens_per_step(mix: dict) -> int:
    """Predicted words of one step: ``sum(w)``, and every weight is 1."""
    return int(mix["global_batch"]) * int(mix["num_steps"])
