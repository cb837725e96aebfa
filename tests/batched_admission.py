"""One ``GenerationEngine.admit`` of k requests against k serial ``join``s, for
the tests of each model family (tests/test_generate.py and the two hybrid
family files run it on their own small model) through the comparison the chip
run makes too (``chip_smoke.admission_matches_serial``): the first tokens, the
pools, the recurrent state, the host registers and every following token must
be the SAME, bit for bit: a prompt's row of the run computes what the prompt
computed alone, and the sampling key sees seed and position, never the batch."""

import chip_smoke
import numpy as np

from dmlc_tpu.generate.engine import Admission

#: (requests in the one call, temperature): a batch of one, of two, of every
#: slot; greedy and sampled with fixed seeds.
CASES = [(k, t) for k in (1, 2, 4) for t in (0.0, 0.8)]


def requests_of(k: int, vocab: int, max_prefill: int, temperature: float) -> list[Admission]:
    """k prompts of different lengths (the shortest one token, the longest the
    whole padded length), into slots that are not their rows of the run."""
    rng = np.random.default_rng(100 * k + int(10 * temperature))
    lengths = [1, max_prefill, max_prefill // 2 + 1, 5][:k]
    return [Admission(slot=k - 1 - i, prompt=rng.integers(0, vocab, size=n).astype(np.int32),
                      temperature=temperature, seed=4321 + i if temperature else None)
            for i, n in enumerate(lengths)]


def assert_batch_matches_serial(make_engine, vocab: int, k: int, temperature: float) -> None:
    batched, serial = make_engine(), make_engine()
    chip_smoke.admission_matches_serial(
        batched, serial, requests_of(k, vocab, batched.max_prefill, temperature))
