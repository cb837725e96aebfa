"""The one general traffic generator. A traffic mix is a data file of
parameters; every seed gets the SAME multiset of sizes in another order, so
that the seed changes the inputs and not the amount of work."""

from __future__ import annotations

import random


def spread(lo: int, hi: int, n: int) -> list[int]:
    """``n`` whole numbers evenly spread over [lo, hi], both ends included."""
    if n == 1:
        return [(lo + hi) // 2]
    return [lo + round(i * (hi - lo) / (n - 1)) for i in range(n)]


def requests(traffic: dict, seed: int, vocab: int) -> list[list[dict]]:
    """Per client, its list of requests for a closed loop. The pool has
    ``pool`` (prompt, output) pairs: prompt lengths evenly spread over
    ``prompt_tokens`` and output lengths over ``output_tokens``, paired after
    a shuffle each, dealt round-robin to ``clients`` clients."""
    rng = random.Random(int(seed))
    n = int(traffic["pool"])
    prompts = spread(*traffic["prompt_tokens"], n)
    lo, hi = traffic["output_tokens"]
    outputs = [lo + i % (hi - lo + 1) for i in range(n)]
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    clients = int(traffic["clients"])
    per: list[list[dict]] = [[] for _ in range(clients)]
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        per[i % clients].append({
            "prompt": [rng.randrange(vocab) for _ in range(p)],
            "max_new_tokens": o,
        })
    return per


def image_order(traffic: dict, seed: int) -> list[int]:
    """For an image job: the order in which the ``distinct_images`` files are
    listed, repeated until ``job_images`` queries are listed. Each repeat is
    the same shuffle, so every shard holds the same mix of files."""
    rng = random.Random(int(seed))
    ids = list(range(int(traffic["distinct_images"])))
    rng.shuffle(ids)
    reps = -(-int(traffic["job_images"]) // len(ids))
    return (ids * reps)[: int(traffic["job_images"])]
