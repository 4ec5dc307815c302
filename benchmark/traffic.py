"""The one general generator: tokens for training steps, from a seed.

A traffic mix is a JSON file of parameters under ``benchmark/traffic/``;
this module turns (mix, configuration, seed) into the samples the program's
own loader asks for.  Every seed gives the same sizes (sequence length,
sequences per step, step count is set by the window): only the token values
and the weights change, so no seed changes the work.
"""

from __future__ import annotations

from typing import Callable, Dict

import numpy as np


def sample_fn(
    vocab: int, seq_len: int, seed: int
) -> Callable[[int], Dict[str, np.ndarray]]:
    """``fn(index) -> {"inputs", "targets"}``: sequence ``index`` of the
    stream of ``seed``: ``seq_len + 1`` tokens uniform over ``vocab``, the
    targets the inputs shifted by one."""
    seed = int(seed)

    def fn(index: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([seed, int(index)])
        tokens = rng.integers(0, vocab, size=seq_len + 1, dtype=np.int32)
        return {"inputs": tokens[:-1], "targets": tokens[1:]}

    return fn


def first_sequences(fn, count: int) -> Dict[str, np.ndarray]:
    """The first ``count`` sequences of the stream, stacked."""
    rows = [fn(i) for i in range(count)]
    return {k: np.stack([r[k] for r in rows]) for k in rows[0]}
