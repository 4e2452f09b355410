"""The one general traffic generator. A mix is a data file of parameters
under benchmark/traffic/; nothing here knows a mix by name.

`token_batches` mixes (training): every step's batch is drawn on the host
from the run's seed and the step's index, so the same seed gives the same
inputs whatever the step time. Tokens follow a Zipf law over the
vocabulary's ranks (rank r with probability ~ r**-exponent), ranks mapped
to token ids by a seeded permutation, so the loss has somewhere to fall
(the unigram entropy is far under log(vocab)).
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


class TokenBatches:
    def __init__(self, mix: Dict[str, Any], vocab_size: int, seed: int):
        if mix.get("kind") != "token_batches":
            raise ValueError(f"not a token_batches mix: {mix.get('kind')!r}")
        law = mix["unigram"]
        if law["law"] != "zipf":
            raise ValueError(f"unknown unigram law {law['law']!r}")
        self.sequences = int(mix["sequences_per_step"])
        self.tokens = int(mix["tokens_per_sequence"])
        self.seed = int(seed)
        ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
        weights = ranks ** -float(law["exponent"])
        self._cdf = np.cumsum(weights / weights.sum())
        self._ids = np.random.default_rng(
            [self.seed, 0x1D5]).permutation(vocab_size).astype(np.int32)
        p = weights / weights.sum()
        self.unigram_entropy_nats = float(-(p * np.log(p)).sum())

    @property
    def tokens_per_step(self) -> int:
        return self.sequences * self.tokens

    def draw(self, stream: int, index: int, sequences: int,
             tokens: int) -> np.ndarray:
        """[sequences, tokens] int32, a pure function of (seed, stream,
        index)."""
        rng = np.random.default_rng([self.seed, stream, index])
        u = rng.random((sequences, tokens))
        ranks = np.minimum(np.searchsorted(self._cdf, u),
                           len(self._cdf) - 1)
        return self._ids[ranks]

    def batch(self, step: int) -> np.ndarray:
        """The training batch of step `step`: [sequences, tokens + 1]
        (inputs and next-token targets overlap by one)."""
        return self.draw(1, step, self.sequences, self.tokens + 1)

    def reference_sample(self, sequences: int, tokens: int) -> np.ndarray:
        """The seeded sample the system is compared with the plain
        reference on: [sequences, tokens + 1]."""
        return self.draw(2, 0, sequences, tokens + 1)
