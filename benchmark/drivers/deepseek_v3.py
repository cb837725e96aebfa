"""Driver for ``model_type: deepseek_v3`` (latent attention over one cached
row a position, a dense layer, then gated experts beside shared ones) served
as ``drivers/lm_hybrid`` serves its model: token streams through
``job.generate`` / ``job.generate_poll`` on the leader's GenRouter from a
closed loop of clients, weights drawn leaf by leaf, the pool freed before the
reference runs (this family has ONE pool and no recurrent state:
``engine.cache.k_pages`` is it, and there is nothing else to free).

Only the registration differs: the model's family file is
``models/deepseek_v3`` and it reads the PUBLISHED keys of the configuration
file as they stand, the router's width from ``published`` and the experts
this chip holds from ``deployment`` (as ``lm_hybrid`` does for its own
family). ``lm_hybrid.run`` looks ``register`` up in its own module, so a
private copy of that module is loaded here and given this file's; the window,
``failed`` and ``correct`` are that run's, which are ``drivers/lm``'s.
"""

from __future__ import annotations

from benchlib import manifest


def register(cfg: dict):
    """The configuration as the program's family reads it: every published
    key the family names, the router's width from ``published``, the experts
    held from ``deployment``, the serving length from ``serving_positions``."""
    try:
        from dmlc_tpu.models.deepseek_v3 import DeepseekV3Config, register_deepseek_v3
    except ImportError as e:
        raise SystemExit(f"benchmark: this checkout's program has no deepseek_v3 family ({e})")

    config = DeepseekV3Config.from_published(
        cfg, n_routed_experts=int(cfg["published"]["n_routed_experts"]),
        experts_held=cfg["deployment"]["experts_held"], max_len=int(cfg["serving_positions"]))
    if config.held[1] != int(cfg["n_routed_experts"]):
        raise SystemExit("benchmark: n_routed_experts (held here) and deployment.experts_held disagree")
    return register_deepseek_v3(cfg["model"], config)


_hybrid = manifest.load_module("bench_drivers_lm_hybrid_for_deepseek_v3",
                               manifest.BENCH / "drivers" / "lm_hybrid.py")
_hybrid.register = register
run = _hybrid.run
