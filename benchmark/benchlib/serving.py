"""What the clients' records say about the decode work in an interval."""

from __future__ import annotations


def decoded_contexts(records, t0: float, t1: float) -> list[int]:
    """For every token that a decode step produced (every token but a
    request's first, which its prefill produced) and that arrived in
    [t0, t1]: the number of cached positions the step attended, which is the
    prompt plus the tokens before it."""
    out = []
    for r in records:
        for k, t in enumerate(r["token_t"]):
            if k >= 1 and t0 <= t <= t1:
                out.append(len(r["prompt"]) + k)
    return out
