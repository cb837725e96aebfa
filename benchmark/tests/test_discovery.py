"""A later PR adds a configuration, a traffic mix, a per-layer metric, a
reader and a kernel as NEW files plus manifest entries. The harness's
discovery finds them in a copy of the tree in which no file that was there
has been edited."""

import hashlib
import json
import shutil

from benchlib import manifest


def digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_new_cell_metric_and_kernel_need_no_edit(tmp_path):
    bench = tmp_path / "benchmark"
    shutil.copytree(manifest.BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", ".work"))
    before = digest(bench)

    # what the later PR adds: files only
    config = manifest.read_json(manifest.BENCH / "configs" / "gpt2-large.json")
    config["model"] = "gpt2-xl"
    config["n_layer"], config["n_embd"], config["n_head"] = 48, 1600, 25
    (bench / "configs" / "gpt2-xl.json").write_text(json.dumps(config))
    mix = manifest.read_json(manifest.BENCH / "traffic" / "docs.json")
    mix["prompt_tokens"], mix["output_tokens"] = [16, 64], [64, 256]
    (bench / "traffic" / "longchat.json").write_text(json.dumps(mix))
    (bench / "readers" / "span_count.py").write_text(
        "def read(ctx, name):\n    return float(sum(1 for s in ctx.spans if s['name'] == name))\n")
    (bench / "kernels" / "ragged_attention.py").write_text("EVENTS = r'ragged'\n")
    metric = {"name": "steps.longchat", "unit": "steps", "better": "higher",
              "source": "program_span", "layer": "generation engine",
              "moves": "tokens_per_s", "workloads": ["gpt2-xl.longchat"],
              "reader": "span_count", "args": {"name": "gen/step"}}
    (bench / "metrics" / "steps.longchat.json").write_text(json.dumps(metric))

    # ... and entries in BENCHMARK.json
    m = manifest.load()
    m["configs"].append({"name": "gpt2-xl", "source": "https://huggingface.co/openai-community/gpt2-xl",
                         "file": "benchmark/configs/gpt2-xl.json", "reduced": [], "why": "wider"})
    m["workloads"].append({"name": "gpt2-xl.longchat", "config": "gpt2-xl", "traffic": "longchat",
                           "chips": 1, "why": "long outputs"})
    next(e for e in m["end_to_end"] if e["name"] == "tokens_per_s")["workloads"].append("gpt2-xl.longchat")
    m["per_layer"].append({k: metric[k] for k in
                           ("name", "unit", "better", "source", "layer", "moves", "workloads")})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    # discovery, on the copy
    m2 = manifest.load(tmp_path)
    cell = manifest.cell(m2, "gpt2-xl.longchat")
    assert manifest.config_of(m2, cell, repo=tmp_path)["n_embd"] == 1600
    assert manifest.traffic_of(cell, bench=bench)["output_tokens"] == [64, 256]
    assert [e["name"] for e in manifest.wanted(m2, cell["name"], trace=False)] == [
        "tokens_per_s", "setup_s"]
    assert [p["name"] for p in manifest.wanted(m2, cell["name"], trace=True)] == ["steps.longchat"]
    spec = manifest.metric_files(bench)["steps.longchat"]
    reader = manifest.plugins("readers", bench)[spec["reader"]]

    class Ctx:
        spans = [{"name": "gen/step"}, {"name": "gen/step"}, {"name": "gen/prefill"}]

    assert reader.read(Ctx, **spec["args"]) == 2.0
    assert manifest.plugins("kernels", bench)["ragged_attention"].EVENTS == "ragged"
    assert (bench / "drivers" / f"{config['driver']}.py").exists()

    # no file that was there has changed
    after = digest(bench)
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 5
