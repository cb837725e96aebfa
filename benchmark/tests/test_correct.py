"""``correct`` comes out true for a sound run, false for the control, and
false when the timed path is broken underneath.

Each test skips the harness's look for a chip and drives the rest of a run
(driver, window, taps, reference, judge) on the CPU at a toy size. The control
is the plain reference computed in the nearest precision below the
configuration's (fp8 for bfloat16), read over the same served sample; the
fault is a token, or an answer, altered where it is produced.
"""

import shutil
import time
from types import SimpleNamespace

import pytest

from benchlib import manifest, system

DATA = manifest.BENCH / "tests" / "data"


def load_run():
    return manifest.load_module("bench_run_for_tests", manifest.BENCH / "run.py")


def drive(config_file, traffic_file, driver, seed, seconds, control=None):
    system.import_program()
    cfg = manifest.read_json(DATA / config_file)
    mix = manifest.read_json(DATA / traffic_file)
    ctx = SimpleNamespace(
        seed=seed, seconds=seconds, trace=False, cell={"name": "toy.cell", "chips": 1, "traffic": "toy"},
        config=cfg, traffic=mix, t_start=time.perf_counter(), compiles=system.CompileCounter(),
        limits=cfg["limits"]["default"], peaks={}, control=control)
    result = manifest.plugin("drivers", driver).run(ctx)
    shutil.rmtree(result["workdir"], ignore_errors=True)
    return result


@pytest.fixture(scope="module")
def judge():
    return load_run().judge


def test_lm_sound_run_is_correct_and_its_control_is_not(judge):
    result = drive("lm_tiny.json", "lm_tiny_traffic.json", "lm", seed=2**31 + 77, seconds=1.5,
                   control="fp8")
    assert result["failed"] == 0
    assert judge(result["checks"]), result["checks"]
    # the number the real cells compare (configs/gpt2-large.json): the mean gap
    program, control = result["checks"]["logit_gap_mean"], result["control_checks"]["logit_gap_mean"]
    assert program["limit"] is not None and control["limit"] == program["limit"]
    assert control["value"] > program["limit"] >= program["value"], (control, program)
    assert control["value"] >= 3 * max(program["value"], program["limit"] / 3), (control, program)
    assert not judge(result["control_checks"])


def test_lm_token_altered_where_it_is_produced_is_not_correct(judge, monkeypatch):
    system.import_program()
    from dmlc_tpu.generate import engine as engine_module

    sound_step = engine_module.GenerationEngine.step

    def altered(self):
        tokens = sound_step(self)
        tokens = tokens.copy()
        tokens[0] = (int(tokens[0]) + 1) % self.vocab    # slot 0 streams a token the model did not pick
        return tokens

    monkeypatch.setattr(engine_module.GenerationEngine, "step", altered)
    result = drive("lm_tiny.json", "lm_tiny_traffic.json", "lm", seed=5, seconds=1.5)
    mean = result["checks"]["logit_gap_mean"]
    assert mean["value"] > mean["limit"], mean     # the committed number is the one that fails
    assert not judge(result["checks"]), result["checks"]


def test_vision_sound_run_is_correct_and_its_control_is_not(judge):
    result = drive("vision_tiny.json", "vision_tiny_traffic.json", "vision", seed=2**31 + 9,
                   seconds=2.0, control="fp8")
    assert result["failed"] == 0
    assert judge(result["checks"]), result["checks"]
    assert not judge(result["control_checks"]), result["control_checks"]


def test_vision_answer_altered_where_it_is_produced_is_not_correct(judge, monkeypatch):
    system.import_program()
    from dmlc_tpu.parallel import inference

    sound = inference.InferenceEngine._materialize

    def altered(self, n, out):
        n, (idx, top) = sound(self, n, out)
        return n, (idx, top * 1.5)                       # every served probability is off by half

    monkeypatch.setattr(inference.InferenceEngine, "_materialize", altered)
    result = drive("vision_tiny.json", "vision_tiny_traffic.json", "vision", seed=11, seconds=2.0)
    assert not judge(result["checks"]), result["checks"]


def test_a_failed_or_missing_number_is_not_correct(judge):
    assert not judge({"x": {"value": float("nan"), "limit": 1.0}})
    assert not judge({"x": {"value": float("inf"), "limit": 1.0}})
    assert not judge({"n": {"value": 0, "limit": 1, "sense": "min"}})
    assert judge({"x": {"value": 0.5, "limit": 1.0}, "info": {"value": 9, "limit": None}})
