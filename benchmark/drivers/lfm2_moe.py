"""Driver for ``model_type: lfm2_moe`` (gated short convolutions 3:1 with
rotary grouped-query attention, gated experts after the leading dense layers)
served as ``drivers/lm_hybrid`` serves its model: token streams through
``job.generate`` / ``job.generate_poll`` on the leader's GenRouter from a
closed loop of clients, weights drawn leaf by leaf, pools and recurrent state
freed before the reference runs.

Only the registration differs: the model's family file is
``models/lfm2_moe`` and it reads the PUBLISHED keys of the configuration file
as they stand (the cut is ``layer_types`` itself; every expert of a layer is
held here). ``lm_hybrid.run`` looks ``register`` up in its own module, so a
private copy of that module is loaded here and given this file's; the window,
``failed`` and ``correct`` are that run's, which are ``drivers/lm``'s.
"""

from __future__ import annotations

from benchlib import manifest


def register(cfg: dict):
    """The configuration as the program's family reads it: every published
    key the family names, the serving length from ``serving_positions``."""
    from dmlc_tpu.models.lfm2_moe import Lfm2MoeConfig, register_lfm2_moe

    config = Lfm2MoeConfig.from_published(cfg, max_len=int(cfg["serving_positions"]))
    if list(cfg["deployment"]["experts_held"]) != [0, config.num_experts]:
        raise SystemExit("benchmark: this family holds every expert of a layer; "
                         "deployment.experts_held says otherwise")
    return register_lfm2_moe(cfg["model"], config)


_hybrid = manifest.load_module("bench_drivers_lm_hybrid_for_lfm2_moe",
                               manifest.BENCH / "drivers" / "lm_hybrid.py")
_hybrid.register = register
run = _hybrid.run
