"""Driver for ``model_type: olmo_hybrid`` (gated delta-rule linear attention
3:1 with full attention) served as ``drivers/lm_hybrid`` serves its model:
token streams through ``job.generate`` / ``job.generate_poll`` on the
leader's GenRouter from a closed loop of clients, weights drawn leaf by leaf,
pools and recurrent state freed before the reference runs.

Only the registration differs: the model's family file is
``models/olmo_hybrid`` and it reads the PUBLISHED keys of the configuration
file as they stand (the cut is ``layer_types`` itself). ``lm_hybrid.run``
looks ``register`` up in its own module, so a private copy of that module is
loaded here and given this file's; the window, ``failed`` and ``correct`` are
that run's, which are ``drivers/lm``'s.
"""

from __future__ import annotations

from benchlib import manifest


def register(cfg: dict):
    """The configuration as the program's family reads it: every published
    key the family names, the serving length from ``serving_positions``."""
    from dmlc_tpu.models.olmo_hybrid import OlmoHybridConfig, register_olmo_hybrid

    config = OlmoHybridConfig.from_published(cfg, max_len=int(cfg["serving_positions"]))
    return register_olmo_hybrid(cfg["model"], config)


_hybrid = manifest.load_module("bench_drivers_lm_hybrid_for_olmo_hybrid",
                               manifest.BENCH / "drivers" / "lm_hybrid.py")
_hybrid.register = register
run = _hybrid.run
