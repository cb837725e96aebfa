"""Driver for ``model_type: granitemoehybrid`` without experts (Mamba-2 and
grouped-query attention layers, two branches a layer under muP multipliers)
served as ``drivers/lm_hybrid`` serves its model: token streams through
``job.generate`` / ``job.generate_poll`` on the leader's GenRouter from a
closed loop of clients, weights drawn leaf by leaf, pools and recurrent state
freed before the reference runs.

Two things differ. The model's family file is ``models/granite_hybrid`` and
it reads the PUBLISHED keys of the configuration file as they stand (nothing
is cut); ``lm_hybrid.run`` looks ``register`` up in its own module, so a
private copy of that module is loaded here and given this file's. And
``tokens_per_s`` credits the work done INSIDE the window: a request's prompt
when its first token (the prefill's) arrives, each served token when it
arrives. The window's two instants, ``failed`` and ``correct`` are
``lm_hybrid``'s, which are ``drivers/lm``'s.

Why the crediting differs: at this mix a request lives about 20 s, so a
51 s window holds two and a half lifetimes of 64 streams, and crediting each
request's 1,400-2,700 tokens whole at its end makes the rate hang on how
the streams' ends fall against the window's edges. Crediting each token
where it arrives counts the same work and leaves the edges out of it.
"""

from __future__ import annotations

from benchlib import manifest, system


def register(cfg: dict):
    """The configuration as the program's family reads it: every published
    key the family names, the serving length from ``serving_positions``."""
    try:
        from dmlc_tpu.models.granite_hybrid import GraniteHybridConfig, register_granite_hybrid
    except ImportError as e:
        raise SystemExit(f"benchmark: this checkout's program has no granite_hybrid family ({e})")

    config = GraniteHybridConfig.from_published(cfg, max_len=int(cfg["serving_positions"]))
    return register_granite_hybrid(cfg["model"], config)


def delivered_work(records, t_open: float, t_close: float) -> int:
    """Tokens of work that arrived in (t_open, t_close]: the prompt of every
    request whose first token arrived there, and every served token that
    arrived there, of requests that did not fail; in flight at either edge
    or abandoned after it, a request counts for what arrived inside."""
    work = 0
    for r in records:
        if r["error"] or not r["token_t"]:
            continue
        if t_open < r["token_t"][0] <= t_close:
            work += len(r["prompt"])
        work += sum(1 for t in r["token_t"] if t_open < t <= t_close)
    return work


_hybrid = manifest.load_module("bench_drivers_lm_hybrid_for_granite_hybrid",
                               manifest.BENCH / "drivers" / "lm_hybrid.py")
_hybrid.register = register


def run(ctx) -> dict:
    result = _hybrid.run(ctx)
    window = result["readings"]["window"]
    work = delivered_work(result["readings"]["records"], window.t_open, window.t_close)
    result["end_to_end"]["tokens_per_s"] = work / window.seconds
    system.say(f"delivered in the window: {work} tokens, {work / window.seconds:.1f} tokens/s "
               f"(whole requests: {window.rate:.1f})")
    return result
