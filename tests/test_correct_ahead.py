"""The benchmark's ``correct`` comes out false for a wrong served token on the
path the language-model cells time.

``benchmark/tests/test_correct.py`` plants its wrong token in
``GenerationEngine.step``; since the decode loop keeps a turn's runs in flight
(``SlotScheduler._turn``) it calls the halves, ``dispatch_*`` then
``collect_*``, and never ``step`` or ``admit``, so that fault is no longer
injected. This is the same guard on the halves the loop calls: the token is
altered where the loop reads it, a step's row in ``collect_step`` and a run's
first token in ``collect_admit``. The run is the benchmark's own (driver,
window, reference, judge) at its toy size on the CPU.
"""

import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from benchlib import manifest, system  # noqa: E402

from dmlc_tpu.generate.engine import GenerationEngine  # noqa: E402

DATA = BENCH / "tests" / "data"


def drive(seed):
    cfg = manifest.read_json(DATA / "lm_tiny.json")
    mix = manifest.read_json(DATA / "lm_tiny_traffic.json")
    ctx = SimpleNamespace(
        seed=seed, seconds=1.5, trace=False, cell={"name": "toy.cell", "chips": 1, "traffic": "toy"},
        config=cfg, traffic=mix, t_start=time.perf_counter(), compiles=system.CompileCounter(),
        limits=cfg["limits"]["default"], peaks={}, control=None)
    result = manifest.plugin("drivers", "lm").run(ctx)
    shutil.rmtree(result["workdir"], ignore_errors=True)
    return result


def step_row_altered(sound):
    def collect_step(self, run):
        tokens = sound(self, run).copy()
        tokens[0] = (int(tokens[0]) + 1) % self.vocab    # slot 0 streams a token the model did not pick
        return tokens
    return collect_step


def first_token_altered(sound):
    def collect_admit(self, run):
        firsts = list(sound(self, run))
        firsts[0] = (int(firsts[0]) + 1) % self.vocab    # the run's oldest request starts on a wrong token
        return firsts
    return collect_admit


@pytest.mark.parametrize("half,altered", [("collect_step", step_row_altered),
                                          ("collect_admit", first_token_altered)])
def test_lm_token_altered_where_the_loop_reads_it_is_not_correct(half, altered, monkeypatch):
    judge = manifest.load_module("bench_run_for_tests", BENCH / "run.py").judge
    monkeypatch.setattr(GenerationEngine, half, altered(getattr(GenerationEngine, half)))
    result = drive(seed=5)
    mean = result["checks"]["logit_gap_mean"]
    assert mean["value"] > mean["limit"], mean     # the committed number is the one that fails
    assert not judge(result["checks"]), result["checks"]
