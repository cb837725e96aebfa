"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

One process (a chip belongs to one process at a time) drives the system's
main path once, through the entry points a user would call:

- **serve**: a localcluster node with the REAL backends its config names
  (``EngineBackend`` for ResNet-18 at its published width, batch 256,
  bfloat16) over a 1,000-class JPEG corpus generated from a seed;
  ``leader.predict()`` -> ``job.start`` -> ``job.predict`` -> the donating
  ``run_paths_stream`` program, plus one direct ``job.predict`` RPC through
  the plain program. Answers are checked against a float32 forward of the
  same seed-initialised weights computed on the host CPU.
- **generate**: the same node's ``lm_small`` generation plane at the config
  defaults: overlapping ``leader.generate`` calls through GenRouter ->
  GenerateWorker -> SlotScheduler -> GenerationEngine, every greedy token
  checked against a ``cache="contiguous", use_pallas=False`` engine fed the
  served prefix (its best token, or a bfloat16 near-tie of it); then the step
  and the prefill compiled at gpt2-large's cache geometry from abstract
  arguments, which must alias both KV pools and keep their temporaries under
  the size of one pool (``pool_memory``).
- **family**: a model family that keeps positions and recurrent state of its
  own (``models/lfm2_moe``: rotary positions, conv windows, gated experts) at
  a small size with the attention kernel's real head width: prefill, then
  decode steps with slots at DIFFERENT lengths in one step, every row's
  logits against the benchmark's plain reference's full forward.
- **kernels**: every ``pl.pallas_call`` site compiled (never interpreted)
  once at a serving/training shape and checked against its XLA reference.
- **multichip**: on a host with several chips, the engine's dp mesh, per-
  device memory, and the gang-sharded LM at full width.

It refuses to run anywhere but a TPU: the first thing it does is read
``jax.devices()``, and a CPU exits non-zero before any work. No flag or
environment switch makes it pass there — ``tests/test_chip_smoke.py`` calls
the phase functions below at a tiny size instead.

Stdout carries two JSON lines. The first is the report, ``{"report":
{versions, compile_cache, phases, census, decode, peak_hbm_bytes}}``; wall
seconds in it are set-up times, not metrics, and the script claims no rate.
The LAST line is the result and holds exactly ``{"ok": true, "device":
{"platform": ..., "kind": ..., "count": ...}}``, the device as JAX reports
it. A failed phase raises: there is neither line, and the exit code is
non-zero.
"""

from __future__ import annotations

import json
import logging
import sys
import tempfile
import threading
import time
from pathlib import Path

# bf16 carries 8 significand bits. Tolerances for comparing the chip's bf16
# answers with a float32 reference are fixed here from the dtype, before any
# run: 8 ulps of the largest value in play.
BF16_TOL = 8 * 2.0 ** -8


def say(msg: str) -> None:
    """Progress goes to stderr; stdout carries only the report and result lines."""
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# serve: JPEG -> top-1 through the cluster
# ---------------------------------------------------------------------------


def start_cluster(tmp: Path, *, model: str, n_classes: int, image_size: int,
                  gen_model: str, **overrides):
    """One localcluster node serving ``model`` and ``gen_model`` with the
    real, config-built backends, at the reference's 1 s / 3 s intervals
    (``scale=5``: the harness's 5x-compressed timers falsely FAIL a member
    whose compile holds the GIL). Returns (node list, synset ids, corpus
    image directory)."""
    from dmlc_tpu.cluster import localcluster
    from dmlc_tpu.utils import corpus

    data_dir, synset_path = corpus.generate(
        tmp / "corpus", n_classes=n_classes, images_per_class=1,
        size=image_size, seed=0,
    )
    nodes = localcluster.start_local_cluster(
        tmp, n_nodes=1, n_leader_candidates=1,
        backends=localcluster.CONFIGURED, scale=5.0,
        job_models=[model], generate_models=[gen_model],
        data_dir=str(data_dir), synset_path=synset_path,
        **overrides,
    )
    return nodes, [f"n{i:08d}" for i in range(n_classes)], data_dir


def reference_top1(model: str, paths, *, chunk: int = 64):
    """float32 forward of the registry's seed-initialised weights (the seed
    every engine inits from) on the HOST CPU device — off the chip.
    Returns (top-1 index, top-1 softmax probability, near-tie mask): an
    image is a near-tie when its top-2 logit margin is inside the bf16
    tolerance, i.e. when a correct bf16 forward may legitimately disagree."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dmlc_tpu.models import get_model
    from dmlc_tpu.ops import preprocess as pp

    spec = get_model(model)
    mean, std = pp.stats_for_model(model)
    with jax.default_device(jax.devices("cpu")[0]):
        module = spec.module(dtype=jnp.float32)
        _, variables = spec.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
        forward = jax.jit(lambda v, x: module.apply(v, x, train=False))
        logits = []
        for s in range(0, len(paths), chunk):
            u8 = pp.load_batch(paths[s:s + chunk], size=spec.input_size)
            x = (u8.astype(np.float32) / 255.0 - mean) / std
            logits.append(np.asarray(forward(variables, x)))
    logits = np.concatenate(logits).astype(np.float64)
    top2 = np.sort(logits, axis=1)[:, -2:]
    near_tie = (top2[:, 1] - top2[:, 0]) < BF16_TOL * np.abs(logits).max()
    z = np.exp(logits - logits.max(axis=1, keepdims=True))
    prob = z.max(axis=1) / z.sum(axis=1)
    return logits.argmax(axis=1), prob, near_tie


def _compare_top1(what: str, got_idx, got_prob, ref, rows) -> dict:
    """Chip answers for corpus rows ``rows`` against the reference: top-1
    index on every image that is not a near-tie, top-1 probability (which,
    unlike the index under random weights, differs image to image) on all."""
    import numpy as np

    ref_idx, ref_prob, near_tie = (np.asarray(r)[rows] for r in ref)
    got_idx = np.asarray(got_idx)
    if got_idx.shape != ref_idx.shape:
        raise AssertionError(f"{what}: {got_idx.shape} answers for {ref_idx.shape} images")
    decided = ~near_tie
    wrong = np.nonzero(decided & (got_idx != ref_idx))[0]
    if wrong.size:
        raise AssertionError(
            f"{what}: top-1 differs from the float32 reference on "
            f"{wrong.size}/{int(decided.sum())} decided images, first rows "
            f"{wrong[:5].tolist()}: got {got_idx[wrong[:5]].tolist()} "
            f"want {ref_idx[wrong[:5]].tolist()}"
        )
    out = {"compared": int(decided.sum()), "near_ties_skipped": int(near_tie.sum())}
    if got_prob is not None:
        got_prob = np.asarray(got_prob, np.float64)
        if not np.isfinite(got_prob).all():
            raise AssertionError(f"{what}: non-finite top-1 probabilities")
        rel = np.abs(got_prob - ref_prob) / ref_prob
        if rel.max() > BF16_TOL:
            raise AssertionError(
                f"{what}: top-1 probability off by {rel.max():.4f} relative "
                f"(bound {BF16_TOL:.4f}) at row {int(rel.argmax())}"
            )
        out["prob_max_rel_err"] = round(float(rel.max()), 5)
    return out


def serve_phase(node, *, model: str, synsets, data_dir,
                job_timeout_s: float = 600.0) -> dict:
    """``predict`` the whole corpus through the scheduler, send one direct
    ``job.predict`` of one full batch, and check what came back."""
    import numpy as np

    from dmlc_tpu.cluster.localcluster import wait_until
    from dmlc_tpu.ops import preprocess as pp
    from dmlc_tpu.utils import tracing

    paths = [str(pp.class_image_path(data_dir, s)) for s in synsets]
    ref = reference_top1(model, paths)
    total = len(synsets)
    direct_n = node.config.batch_size

    # The job: leader.predict() -> job.start -> dispatch loop -> job.predict
    # on the member -> (shards > batch_size) run_paths_stream. Traced, so
    # "zero failed shards" is read off the repo's own spans.
    tracer = tracing.tracer
    was_enabled, tracer.enabled = tracer.enabled, True
    seen = tracer.event_count
    try:
        node.predict()
        wait_until(
            lambda: node.jobs_report()[model]["finished"] == total,
            timeout=job_timeout_s, interval=0.25, msg=f"{model} job to finish",
        )
    finally:
        tracer.enabled = was_enabled
    report = node.jobs_report()[model]
    if report["last_error"]:
        raise AssertionError(f"job reported an error: {report['last_error']}")
    # Every dispatch span closed without an exception, and there are
    # exactly as many as shards (a failed shard is dispatched again).
    spans = tracer.events_wire(offset=seen)
    failed = [e for e in spans if e["attrs"].get("error")
              and e["name"].startswith(("scheduler/", "rpc/job.", "device/", "host/"))]
    if failed:
        raise AssertionError(f"failed spans on the serving path: {failed[:3]}")
    dispatches = sum(1 for e in spans if e["name"] == "scheduler/dispatch")
    want_shards = -(-total // node.config.dispatch_shard_size)
    if dispatches != want_shards:
        raise AssertionError(f"{dispatches} shard dispatches for {want_shards} shards")

    # One direct RPC of <= batch_size synsets: the plain (non-donating) program.
    rows = np.arange(direct_n)
    reply = node.rpc.call(
        node.self_member_addr, "job.predict",
        {"model": model, "synsets": synsets[:direct_n]},
        timeout=node.config.predict_deadline_s,
    )
    direct = _compare_top1("direct job.predict", reply["predictions"], None, ref, rows)

    # The RPC surface returns indices only, and under random weights every
    # image lands on the same class. The same two compiled programs, asked
    # in-process for their probabilities, are checked image by image.
    engine = node.worker.backends[model]._engine
    plain = engine.run_paths(paths[:direct_n])
    stream = engine.run_paths_stream(paths)
    return {
        "job": {"finished": report["finished"], "total": total,
                "shards": dispatches, "failed_shards": 0,
                "correct_vs_labels": report["correct"]},
        "direct_rpc": direct,
        "plain_program": _compare_top1(
            "infer program", plain.top1_index, plain.top1_prob, ref, rows),
        "stream_program": _compare_top1(
            "stream program", stream.top1_index, stream.top1_prob, ref,
            np.arange(total)),
        "distinct_top1_classes": int(len(set(np.asarray(ref[0]).tolist()))),
    }


# ---------------------------------------------------------------------------
# generate: overlapping streams through the router
# ---------------------------------------------------------------------------


def generate_phase(node, *, model: str, prompts, max_new) -> dict:
    """Overlapping ``leader.generate`` calls (so slots join and leave a
    running batch), every served token checked against a contiguous-cache,
    XLA-attention engine that is fed the served prefix."""
    import numpy as np

    from dmlc_tpu.generate.engine import GenerationEngine

    results: list = [None] * len(prompts)
    errors: list = []
    gate = threading.Barrier(len(prompts))

    def one(i: int) -> None:
        try:
            gate.wait(timeout=30)
            results[i] = node.generate(model, prompts[i], max_new_tokens=max_new[i])
        except BaseException as e:  # re-raised on the main thread below
            errors.append(e)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    if errors:
        raise errors[0]
    if any(t.is_alive() for t in threads):
        raise AssertionError("a generate call did not return in 300 s")
    if not all(r["routed"] for r in results):
        raise AssertionError("a generate call bypassed the leader's GenRouter")

    sched = node._gen_backends[model]._scheduler
    cfg = node.config
    ref = GenerationEngine(
        model, cache="contiguous", use_pallas=False, max_slots=1,
        max_prefill=cfg.gen_max_prefill, return_logits=True,
    )
    # The served engine's attention is a fused kernel with its own order of
    # float32 sums, and the model's matmuls round their inputs to bfloat16 on
    # the chip: a served token is right if it is the reference's best token
    # GIVEN THE SERVED PREFIX, or within a bfloat16 near-tie of it.
    worst_gap, same = 0.0, 0
    for i, prompt in enumerate(prompts):
        got = results[i]["tokens"]
        first = ref.join(0, prompt)  # the prefill is one program on both sides
        if len(got) != max_new[i] or got[0] != first:
            raise AssertionError(
                f"stream {i} (prompt of {len(prompt)}): {len(got)} tokens starting "
                f"{got[:1]}, want {max_new[i]} starting [{first}]")
        same += 1
        for j in range(1, len(got)):
            ref.last_tokens = [got[j - 1]]
            ref.step()
            logits = ref.last_logits[0]
            gap = float(logits.max() - logits[got[j]]) / float(np.abs(logits).max())
            if gap > BF16_TOL:
                raise AssertionError(
                    f"stream {i} (prompt of {len(prompt)}) token {j}: served {got[j]}, "
                    f"the contiguous reference prefers {int(logits.argmax())} by "
                    f"{gap:.3e} of its largest logit (near-tie bound {BF16_TOL:.3e})")
            worst_gap = max(worst_gap, gap)
            same += gap == 0.0
        ref.release(0)
    summary = sched.summary()
    serial_steps = sum(n - 1 for n in max_new)
    if not summary["steps"] < serial_steps:
        raise AssertionError(
            f"{summary['steps']} decode steps for {serial_steps} decoded tokens: "
            "the streams never shared a batch"
        )
    return {
        "streams": len(prompts),
        "tokens_checked": sum(max_new),
        "tokens_best_of_reference": same,
        "worst_near_tie": float(f"{worst_gap:.3e}"),
        "decode_steps": summary["steps"],
        "serial_steps": serial_steps,
        "use_pallas": summary["use_pallas"],
        "completions": summary["completions"],
    }


def admission_matches_serial(batched, serial, requests, steps: int = 4) -> list:
    """ONE ``batched.admit(requests)`` (one run of the prefill program, one
    blocking read) against as many serial ``serial.join``s on a twin engine:
    the same first tokens, then after the admission and after each of
    ``steps`` decode steps the same pools and recurrent state bit for bit, the
    same host registers, the same tokens (and logits, where the engines keep
    them). Raises AssertionError on the first difference; returns the first
    tokens. ``tests/batched_admission.py`` runs it on every model family."""
    import jax
    import numpy as np

    def same(what, got, want):
        if not np.array_equal(np.asarray(got), np.asarray(want)):
            raise AssertionError(f"one admission of {len(requests)}: {what} differs from serial joins")

    firsts = batched.admit(requests)
    same("first tokens", firsts, [
        serial.join(r.slot, r.prompt, temperature=r.temperature, seed=r.seed) for r in requests])
    for step in range(steps + 1):
        for name in ("lengths", "active", "temps", "seeds", "last_tokens", "tokens_out", "_joins"):
            same(f"{name} at step {step}", getattr(batched, name), getattr(serial, name))
        device = [jax.tree_util.tree_leaves((e._k_state, e._v_state, e._r_state))
                  for e in (batched, serial)]
        for i, (got, want) in enumerate(zip(*device, strict=True)):
            same(f"device array {i} at step {step}", got, want)
        if step == steps:
            break
        for engine in (batched, serial):
            for r in requests:
                engine.ensure_capacity(r.slot)
        same(f"tokens of step {step}", batched.step(), serial.step())
        if batched.return_logits:
            same(f"logits of step {step}", batched.last_logits, serial.last_logits)
    same("compiled programs", *(sorted(e.jit_cache_sizes().items()) for e in (batched, serial)))
    if batched.jit_cache_sizes() != {"step": 1, "prefill": 1}:
        raise AssertionError(f"programs recompiled: {batched.jit_cache_sizes()}")
    return firsts


def batched_admission(cfg, *, model: str, prompts) -> dict:
    """``admission_matches_serial`` on two engines built like the served one."""
    from dmlc_tpu.generate.engine import Admission, GenerationEngine

    batched, serial = (
        GenerationEngine(model, max_slots=cfg.gen_max_slots, page_size=cfg.gen_page_size,
                         num_pages=cfg.gen_num_pages, max_prefill=cfg.gen_max_prefill)
        for _ in range(2))
    firsts = admission_matches_serial(
        batched, serial, [Admission(slot, p) for slot, p in enumerate(prompts)])
    return {"prompts": len(prompts), "first_tokens": firsts, "steps_compared": 4}


def plain_reference(stem: str):
    """``benchmark/reference/<stem>.py`` loaded by path: the plain float32
    math that decides ``correct`` on the chip (``tests/plain_reference.py``
    hands the tier-1 tests this same copy)."""
    import importlib.util

    path = Path(__file__).resolve().parent / "benchmark" / "reference" / f"{stem}.py"
    spec = importlib.util.spec_from_file_location(f"bench_reference_{stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def flat_of(variables) -> dict:
    """A variables tree as the flat ``{path: leaf}`` dict a reference reads."""
    def walk(tree, prefix=""):
        for key, value in tree.items():
            path = f"{prefix}/{key}" if prefix else key
            if isinstance(value, dict):
                yield from walk(value, path)
            else:
                yield path, value
    return dict(walk(variables))


def family_phase(config=None, *, lengths=(5, 40, 97), steps: int = 4) -> dict:
    """``models/lfm2_moe`` through the engine against its plain reference
    (``benchmark/reference/lfm2_moe.py``, one full forward over prompt + served
    tokens, float32 at ``highest``): float32 weights from a seed, prompts of
    different ``lengths`` prefilled into slots that then decode TOGETHER, so
    one step turns each row's query and key at that row's own position. Every
    row's logits of every step must lie within a bfloat16 near-tie of the
    reference's (the chip's matmuls round their inputs to bfloat16)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dmlc_tpu.generate.engine import GenerationEngine
    from dmlc_tpu.models import lfm2_moe as lf
    from dmlc_tpu.models import registry

    if config is None:   # the kernel's served head width (64), everything else small
        config = lf.Lfm2MoeConfig(
            vocab_size=512, hidden_size=256, intermediate_size=512, moe_intermediate_size=128,
            layer_types=(lf.CONV, lf.CONV, lf.FULL, lf.CONV, lf.CONV, lf.CONV), num_dense_layers=2,
            num_experts=8, num_experts_per_tok=2, num_attention_heads=4, num_key_value_heads=2,
            max_len=256)
    reference = plain_reference("lfm2_moe")
    spec = lf.register_lfm2_moe("chip_smoke_lfm2_moe", config)
    try:
        _, variables = spec.init_params(jax.random.PRNGKey(7), dtype=jnp.float32)
        engine = GenerationEngine(spec.name, variables=variables, max_slots=len(lengths) + 1,
                                  page_size=16, num_pages=64, max_prefill=max(lengths),
                                  return_logits=True)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, config.vocab_size, n).astype(np.int32) for n in lengths]
        served = [[engine.join(slot + 1, p)] for slot, p in enumerate(prompts)]
        ref_cfg = {k: getattr(config, k) for k in config.__dataclass_fields__ if k != "max_len"}
        flat = flat_of(variables)
        worst = 0.0
        for _ in range(steps):
            for slot in range(len(prompts)):
                engine.ensure_capacity(slot + 1)
            out = engine.step()
            for slot, prompt in enumerate(prompts):
                seq = np.concatenate([prompt, np.asarray(served[slot], np.int32)])
                want = np.asarray(reference.logits_at(
                    ref_cfg, flat, jnp.asarray(seq[None]), jnp.asarray([[len(seq) - 1]]))[0, 0])
                got = np.asarray(engine.last_logits[slot + 1])
                gap = float(np.abs(got - want).max() / np.abs(want).max())
                if not gap <= BF16_TOL:
                    raise AssertionError(
                        f"family: slot {slot + 1} at position {len(seq) - 1}: logits "
                        f"{gap:.3e} of the reference's largest away (bound {BF16_TOL:.3e})")
                worst = max(worst, gap)
                served[slot].append(int(out[slot + 1]))
        return {"model": "lfm2_moe", "lengths": list(lengths), "steps": steps,
                "rows_checked": steps * len(lengths), "worst_rel_err": float(f"{worst:.3e}"),
                "use_pallas": bool(engine.use_pallas),
                "replaced_arrays_donated": replaced_arrays_donated(engine, free_slot=0)}
    finally:
        registry._REGISTRY.pop(spec.name, None)


def replaced_arrays_donated(engine, *, free_slot: int) -> int:
    """One run of each program by its halves: every array the call replaced
    (the pools, two or a latent family's one, and each leaf of the recurrent state) is DONATED, so the engine,
    which keeps them until its decode thread has time to let them go, keeps
    no device memory with them, and it took every one. Returns how many
    arrays a run replaces."""
    import jax
    import numpy as np

    from dmlc_tpu.generate.engine import Admission

    for dispatch, collect, args in (
            (engine.dispatch_admit, engine.collect_admit, ([Admission(free_slot, [1, 2, 3])],)),
            (engine.dispatch_step, engine.collect_step, ())):
        for slot in np.flatnonzero(engine.active):
            engine.ensure_capacity(int(slot))
        engine.release_replaced()
        state = jax.tree_util.tree_leaves((engine._k_state, engine._v_state, engine._r_state))
        run = dispatch(*args)
        kept = [a.shape for a in state if not a.is_deleted()]
        if kept or len(engine._replaced) != len(state):
            raise AssertionError(
                f"family: a call replaced {len(state)} arrays, {len(kept)} of them not donated "
                f"{kept}; the engine kept {len(engine._replaced)}")
        collect(run)
    engine.release(free_slot)
    engine.release_replaced()
    return len(state)


def abstract_program_args(engine, *, variables=None, pool=None, sharding=None) -> dict:
    """``{"step": args, "prefill": args}``: the shapes of what the engine
    hands its two programs (no live buffer is touched — the pools are
    donated). ``variables`` and ``pool`` stand in where the engine was built
    without weights or over a smaller pool than the one to compile for;
    ``sharding`` places every argument (a described device, off the chip)."""
    import jax
    import numpy as np

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)

    def scalar(dtype):
        return jax.ShapeDtypeStruct((), dtype, sharding=sharding)

    variables = abstract(engine._variables if variables is None else variables)
    k_state = abstract(engine._k_state if pool is None else pool)
    # A latent family keeps one pool: its programs' second pool is None.
    v_state = None if engine._v_state is None else abstract(
        engine._v_state if pool is None else pool)
    r_state, table = abstract(engine._r_state), engine.cache.page_table
    return {
        "step": (variables, k_state, v_state, r_state, abstract(engine._tokens),
                 abstract(engine.lengths), abstract(engine.active), abstract(table),
                 abstract(engine.seeds), abstract(engine.temps)),
        "prefill": (variables,
                    abstract(np.zeros((engine.max_slots, engine.max_prefill), np.int32)),
                    abstract(engine.lengths), k_state, v_state, r_state, abstract(table),
                    abstract(engine.lengths), abstract(engine.seeds), abstract(engine.temps),
                    scalar(np.int32), abstract(engine._tokens)),
    }


def lowered_step_text(engine) -> str:
    """StableHLO of the engine's decode-step program."""
    return engine._step.lower(*abstract_program_args(engine)["step"]).as_text()


#: gpt2-large's decoder and cache as its benchmark cell runs them
#: (benchmark/configs/gpt2-large.json): 36 layers of 20 heads x 64, 1,024
#: pages of 16 tokens, 24 slots, prompts padded to 640.
POOL_GEOMETRY = {
    "layers": 36, "heads": 20, "hidden": 1280, "vocab": 50257, "max_len": 1024,
    "max_slots": 24, "page_size": 16, "num_pages": 1024, "max_prefill": 640,
}


def pool_memory(geometry: dict, *, sharding=None, use_pallas: bool | None = None) -> dict:
    """Compile the generation engine's step and prefill at ``geometry`` from
    abstract arguments (no weight is drawn and no pool of that size is
    allocated: the programs take the pool's size from the pool they are
    handed) and read each program's ``memory_analysis()`` (``program_memory``,
    which a latent family's ONE pool goes through as well). Both must alias
    the two donated pools to their outputs and keep temporaries under the
    size of ONE pool: a layout the compiler re-lays around the writes shows
    as temporaries of several pools (PERF.md, PR 24 finding 2), and so would
    a pool that the prefill's loop over the admitted prompts (``loop``: the
    program holds a ``while``) carried as a copy. A check of
    the chip's compiler (or of a described chip's, tests/test_tpu_compile.py):
    the CPU backend widens a bfloat16 pool around a scatter and would fail it."""
    import jax
    import jax.numpy as jnp

    from dmlc_tpu.generate.engine import GenerationEngine
    from dmlc_tpu.models import registry
    from dmlc_tpu.parallel.sp_transformer import SPTransformerLM

    g = geometry

    def build(dtype=jnp.float32):
        return SPTransformerLM(
            vocab=g["vocab"], num_layers=g["layers"], num_heads=g["heads"],
            hidden=g["hidden"], mlp_dim=4 * g["hidden"], max_len=g["max_len"],
            schedule="dense", dtype=dtype)

    spec = registry.ModelSpec("pool_geometry_lm", build, g["max_len"], g["vocab"],
                              classifier=False, kind="lm")
    registry.register(spec)
    dtype = jnp.bfloat16
    engine = GenerationEngine(
        spec.name, variables={}, dtype=dtype, max_slots=g["max_slots"],
        page_size=g["page_size"], num_pages=2, max_prefill=g["max_prefill"],
        use_pallas=use_pallas)
    variables = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, dtype),
        jax.eval_shape(lambda: spec.init_params(jax.random.PRNGKey(0), dtype=dtype)[1]))
    pool = jax.ShapeDtypeStruct(
        (g["layers"] * g["num_pages"], g["page_size"], g["hidden"]), dtype)
    return program_memory(engine, variables, pool, sharding=sharding, what=f"gen at {g}")


def program_memory(engine, variables, pool, *, sharding=None, what: str = "gen") -> dict:
    """``pool_memory``'s check for ANY engine: compile its step and prefill
    from abstract ``variables`` and an abstract ``pool`` and hold them to the
    pools the engine keeps, two (K and V) or a latent family's ONE: every one
    aliased to its output, temporaries under the size of one."""
    import math

    pools = 1 if engine._v_state is None else 2
    pool_bytes = math.prod(pool.shape) * pool.dtype.itemsize
    args = abstract_program_args(engine, variables=variables, pool=pool, sharding=sharding)
    out: dict = {"pool_bytes": pool_bytes, "pools": pools}
    for name, program in (("step", engine._step), ("prefill", engine._prefill)):
        compiled = program.lower(*args[name]).compile()
        memory = compiled.memory_analysis()
        text = compiled.as_text()
        out[name] = {"temp_bytes": int(memory.temp_size_in_bytes),
                     "alias_bytes": int(memory.alias_size_in_bytes),
                     "argument_bytes": int(memory.argument_size_in_bytes),
                     "mosaic": MOSAIC_CALL in text, "loop": " while(" in text}
        say(f"pool_memory {name}: {out[name]} ({pools} pool(s) of {pool_bytes})")
        if memory.alias_size_in_bytes < pools * pool_bytes:
            raise AssertionError(
                f"{what} {name}: {memory.alias_size_in_bytes} bytes aliased, "
                f"{pools} pool(s) are {pools * pool_bytes}: a donated pool is not updated in place")
        if memory.temp_size_in_bytes >= pool_bytes:
            raise AssertionError(
                f"{what} {name}: temporaries of {memory.temp_size_in_bytes} bytes "
                f"reach one pool ({pool_bytes}): the program copies a pool")
    return out


# ---------------------------------------------------------------------------
# kernels: every pallas_call site, compiled, against its XLA reference
# ---------------------------------------------------------------------------

MOSAIC_CALL = "tpu_custom_call"

#: Shapes the kernels phase compiles at: the serving batch for the two
#: vision kernels, a training-grade bf16 Dh=128 attention on both sides of
#: the resident/streamed K/V switch, and the fused decode attention at the
#: forms the cells run: gpt2-large's heads (20 x 64, multi-head),
#: nemotron3-super's (32 query heads on 2 KV heads of 128),
#: olmo-hybrid-7b's (30 x 128, multi-head: pool rows of 3,840 lanes) and
#: lfm2-8b-a1b's (32 query heads on 8 KV heads of 64: rows of 512 lanes), and
#: its latent form at kanana-2-30b-a3b's (32 heads against one row of 640
#: lanes a position, the weighted sum over the first 512); and the prefill's
#: grouped expert matmul at nemotron3-super's expert widths (pair rows, k, n,
#: experts), a quarter of the rows past the last group.
KERNEL_SHAPES = {
    "images": (256, 224, 224, 3),
    "logits": (256, 1000),
    "attn_heads": 8,
    "attn_dh": 128,
    "s_resident": 2048,
    "s_streamed": 16384,
    "sp_s_local": 1024,
    "paged_mha": (20, 20, 64),   # (heads, kv_heads, head_dim)
    "paged_gqa": (32, 2, 128),
    "paged_mha_wide": (30, 30, 128),
    "paged_gqa_64": (32, 8, 64),
    "paged_latent": (32, 640, 512),   # (heads, lanes of a stored row, value lanes)
    "paged_slots": 24,
    "paged_table": 64,           # pages a slot's table names, 16 tokens each
    "grouped": (2048, 1024, 2688, 16),
}


def _attention_ref(q, k, v, *, causal: bool, chunk: int = 2048):
    """Dense attention in q-row chunks at full matmul precision: the XLA
    reference for sequences whose [S, S] score matrix should not be built
    whole."""
    import jax
    import jax.numpy as jnp

    s = q.shape[2]
    scale = q.shape[-1] ** -0.5
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    outs = []
    with jax.default_matmul_precision("highest"):
        for start in range(0, s, chunk):
            qc = q[:, :, start:start + chunk].astype(jnp.float32) * scale
            scores = jnp.einsum("bhqd,bhkd->bhqk", qc, k32)
            if causal:
                q_pos = start + jnp.arange(qc.shape[2])
                mask = jnp.arange(s)[None, :] <= q_pos[:, None]
                scores = jnp.where(mask[None, None], scores, -jnp.inf)
            outs.append(jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v32))
    return jnp.concatenate(outs, axis=2)


def _run_kernel(name: str, fn, args, ref_fn, tol: float) -> dict:
    """Lower ``fn`` (the lowered text must hold the Mosaic custom call —
    an interpreted kernel has none), compile, run, and compare every output
    leaf with ``ref_fn`` by max error relative to the reference's scale."""
    import jax
    import numpy as np

    jitted = jax.jit(fn)
    t0 = time.perf_counter()
    text = jitted.lower(*args).as_text()
    got = jax.block_until_ready(jitted(*args))
    seconds = time.perf_counter() - t0
    want = jax.block_until_ready(jax.jit(ref_fn)(*args))
    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        if g.shape != w.shape:
            raise AssertionError(f"{name}: shape {g.shape} != reference {w.shape}")
        if not np.isfinite(g).all():
            raise AssertionError(f"{name}: non-finite output")
        err = float(np.abs(g - w).max() / max(np.abs(w).max(), 1e-30))
        worst = max(worst, err)
    if worst > tol:
        raise AssertionError(f"{name}: max relative error {worst:.3e} > {tol:.3e}")
    return {"mosaic": MOSAIC_CALL in text, "rel_err": float(f"{worst:.3e}"),
            "compile_and_run_s": round(seconds, 2)}


def kernels_phase(devices, shapes: dict = KERNEL_SHAPES) -> dict:
    """Every ``pl.pallas_call`` site in ``ops/`` (and the two sequence-
    parallel schedules built on them, over a mesh of all local devices).
    Each kernel's failure is recorded with the compiler's message and the
    rest still run — a bring-up wants the whole list — but the phase raises
    if any failed."""
    import jax
    import jax.numpy as jnp

    import numpy as np

    from dmlc_tpu.ops import pallas_kernels as pk
    from dmlc_tpu.ops import preprocess as pp
    from dmlc_tpu.ops.grouped_matmul import grouped_matmul
    from dmlc_tpu.ops.ragged_decode import (
        gather_kv_pages,
        gather_latent_pages,
        latent_decode_attention,
        paged_decode_attention,
        paged_latent_decode_attention,
        ragged_decode_attention,
    )
    from dmlc_tpu.parallel.mesh import make_mesh
    from dmlc_tpu.parallel.ring_attention import ring_flash_attention
    from dmlc_tpu.parallel.ulysses import ulysses_attention

    # A second run of keys after the first 32, so a case added later leaves the
    # earlier cases' inputs as they were.
    keys = iter([*jax.random.split(jax.random.PRNGKey(7), 32),
                 *jax.random.split(jax.random.PRNGKey(8), 8)])
    h, dh = shapes["attn_heads"], shapes["attn_dh"]

    def qkv(s, dtype=jnp.bfloat16, heads=h):
        return tuple(jax.random.normal(next(keys), (1, heads, s, dh), dtype)
                     for _ in range(3))

    def causal_flash(q, k, v):
        return pk.flash_attention(q, k, v, causal=True)

    def causal_ref(q, k, v):
        return _attention_ref(q, k, v, causal=True).astype(q.dtype)

    def grads(attn):
        return jax.grad(
            lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))

    images = jax.random.randint(next(keys), shapes["images"], 0, 256, jnp.int32).astype(jnp.uint8)
    logits = jax.random.normal(next(keys), shapes["logits"], jnp.float32) * 3.0

    def pooled(n_pools, width, q_shape, page_size):
        """(queries, pools, table, lengths, pages a layer): bfloat16 pools of
        two layers (the second is read), float32 queries so that the float32
        sums show in the result; lengths of 1, a page, a page + 3, a full
        table, and whatever the key draws."""
        slots, per_slot = shapes["paged_slots"], shapes["paged_table"]
        n_pages = slots * per_slot + 1
        pools = [jax.random.normal(next(keys), (2 * n_pages, page_size, width), jnp.bfloat16)
                 for _ in range(n_pools)]
        table = 1 + jax.random.permutation(next(keys), n_pages - 1)[: slots * per_slot]
        lengths = jax.random.randint(next(keys), (slots,), 1, per_slot * page_size + 1)
        lengths = lengths.at[:4].set(
            jnp.asarray([1, page_size, page_size + 3, per_slot * page_size]))
        q = jax.random.normal(next(keys), (slots, *q_shape), jnp.float32)
        return q, pools, table.reshape(slots, per_slot).astype(jnp.int32), lengths, n_pages

    def paged_case(heads, kv_heads, head_dim, page_size=16):
        q, pools, table, lengths, n_pages = pooled(2, kv_heads * head_dim, (heads, head_dim),
                                                   page_size)
        args = (q, *pools, table, lengths)

        def fused(q, k_pool, v_pool, table, lengths):
            return paged_decode_attention(q, k_pool, v_pool, table, lengths,
                                          first_row=n_pages, kv_heads=kv_heads)

        def reference(q, k_pool, v_pool, table, lengths):
            ks, vs = (gather_kv_pages(pool, table, kv_heads, first_row=n_pages)
                      for pool in (k_pool, v_pool))
            with jax.default_matmul_precision("highest"):
                return ragged_decode_attention(q, ks, vs, lengths)

        return fused, args, reference, 1e-4

    def latent_case(heads, width, value_lanes, page_size=16):
        """``paged_case`` for the latent form: ONE pool whose rows are keys
        and values at once, queries of the rows' width, the scale of a
        192-lane head."""
        q, (pool,), table, lengths, n_pages = pooled(1, width, (heads, width), page_size)
        args = (q, pool, table, lengths)
        how = {"value_lanes": value_lanes, "scale": 192 ** -0.5}

        def fused(q, pool, table, lengths):
            return paged_latent_decode_attention(q, pool, table, lengths, first_row=n_pages, **how)

        def reference(q, pool, table, lengths):
            rows = gather_latent_pages(pool, table, first_row=n_pages)
            with jax.default_matmul_precision("highest"):
                return latent_decode_attention(q, rows, lengths, **how)

        return fused, args, reference, 1e-4

    def grouped_case(m, k, n, count):
        """Groups of random sizes over three quarters of the rows, the first
        and the last empty; rows outside every group are zeroed on both sides
        (the kernel leaves them as it found them)."""
        sizes = np.random.default_rng(11).multinomial(3 * m // 4, np.ones(count - 2) / (count - 2))
        sizes = jnp.asarray([0, *sizes, 0], jnp.int32)
        k_lhs, k_rhs = jax.random.split(jax.random.PRNGKey(9))
        args = (jax.random.normal(k_lhs, (m, k), jnp.bfloat16),
                (jax.random.normal(k_rhs, (count, k, n), jnp.float32) * 0.05).astype(jnp.bfloat16),
                sizes)
        in_group = (jnp.arange(m) < 3 * m // 4)[:, None]

        def fused(lhs, rhs, sizes):
            return jnp.where(in_group, grouped_matmul(lhs, rhs, sizes), 0.0)

        def reference(lhs, rhs, sizes):
            return jnp.where(in_group, jax.lax.ragged_dot(
                lhs, rhs, sizes, preferred_element_type=jnp.float32), 0.0)

        return fused, args, reference, 1e-4

    n = len(devices)
    mesh = make_mesh({"sp": n}, devices=devices)
    sp_args = qkv(shapes["sp_s_local"] * n)

    cases = [
        ("normalize_u8",
         lambda u8: pk.normalize_u8(u8, pp.IMAGENET_MEAN, pp.IMAGENET_STD),
         (images,),
         lambda u8: (u8.astype(jnp.float32) / 255.0 - pp.IMAGENET_MEAN) / pp.IMAGENET_STD,
         1e-5),
        ("softmax_top1", pk.softmax_top1, (logits,),
         lambda x: (jnp.argmax(x, -1).astype(jnp.int32), jnp.max(jax.nn.softmax(x, -1), -1)),
         1e-4),
        ("flash_fwd_resident", causal_flash, qkv(shapes["s_resident"]), causal_ref, BF16_TOL),
        ("flash_fwd_streamed", causal_flash, qkv(shapes["s_streamed"], heads=2),
         causal_ref, BF16_TOL),
        ("flash_bwd", grads(causal_flash), qkv(shapes["s_resident"]),
         grads(causal_ref), 2 * BF16_TOL),
        ("paged_attention_mha_bf16", *paged_case(*shapes["paged_mha"])),
        ("paged_attention_gqa_bf16", *paged_case(*shapes["paged_gqa"])),
        ("paged_attention_mha_30x128_bf16", *paged_case(*shapes["paged_mha_wide"])),
        ("paged_attention_gqa_32on8x64_bf16", *paged_case(*shapes["paged_gqa_64"])),
        ("paged_latent_attention_32x640_bf16", *latent_case(*shapes["paged_latent"])),
        ("grouped_expert_matmul_bf16", *grouped_case(*shapes["grouped"])),
        (f"ring_flash_sp{n}",
         lambda q, k, v: ring_flash_attention(q, k, v, mesh, causal=True),
         sp_args, causal_ref, BF16_TOL),
        (f"ulysses_flash_sp{n}",
         lambda q, k, v: ulysses_attention(q, k, v, mesh, causal=True, use_flash=True),
         sp_args, causal_ref, BF16_TOL),
    ]
    out: dict = {}
    failures: list[str] = []
    for name, fn, args, ref_fn, tol in cases:
        try:
            out[name] = _run_kernel(name, fn, args, ref_fn, tol)
            say(f"kernel {name}: {out[name]}")
        except Exception as e:  # recorded, and the phase raises below
            msg = f"{type(e).__name__}: {e}"
            failures.append(f"{name}: {msg[-1500:]}")
            say(f"kernel {name} FAILED: {msg[-4000:]}")
    if failures:
        raise AssertionError(
            f"{len(failures)} kernel(s) failed:\n" + "\n".join(failures))
    return out


# ---------------------------------------------------------------------------
# multichip: what one process sees on a host with several chips
# ---------------------------------------------------------------------------


def multichip_phase(node, model: str, gen_model: str, devices) -> dict:
    """The engine's default mesh is dp over every chip and its output's
    addressable shards sit on as many distinct devices; every device holds
    resident bytes; the gang-sharded lm_wide at full width is
    token-identical to the mesh-of-1 reference, in this process. Two known
    one-device behaviours are reported as observed, not asserted (ROADMAP
    D2): where the generation engine's arrays live, and which device the
    node's HBM gauge reads."""
    import jax
    import jax.numpy as jnp

    import __graft_entry__ as graft

    n = len(devices)
    engine = node.worker.backends[model]._engine
    if dict(engine.mesh.shape) != {"dp": n}:
        raise AssertionError(f"engine mesh is {dict(engine.mesh.shape)}, want dp={n}")
    u8 = jnp.zeros((engine.batch_size, engine.input_size, engine.input_size, 3), jnp.uint8)
    idx, _ = engine._forward(engine.variables, u8)
    shard_devices = {s.device.id for s in idx.addressable_shards}
    if len(shard_devices) != n:
        raise AssertionError(f"output shards sit on devices {sorted(shard_devices)}, want {n}")
    in_use = [d.memory_stats()["bytes_in_use"] for d in jax.local_devices()]
    if not all(b > 0 for b in in_use):
        raise AssertionError(f"a device holds no resident bytes: {in_use}")
    graft._gang_smoke_body(n)
    gen = node._gen_backends[gen_model]._scheduler.engine
    gen_devices = {d.id for leaf in jax.tree_util.tree_leaves(
        (gen._variables, gen._k_state, gen._v_state)) for d in leaf.devices()}
    return {"mesh": {"dp": n}, "output_shard_devices": sorted(shard_devices),
            "bytes_in_use_per_device": in_use, "gang_width": n,
            "gang_token_identical": True,
            "observed_gen_engine_devices": sorted(gen_devices),
            "observed_hbm_gauge_bytes_in_use":
                node.registry.snapshot()["gauges"]["hbm_bytes_in_use"]}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

VISION_MODEL = "resnet18"
GEN_MODEL = "lm_small"
REQUIRED_LABELS = (
    f"infer/{VISION_MODEL}", f"infer/{VISION_MODEL}/stream",
    f"gen/{GEN_MODEL}/step", f"gen/{GEN_MODEL}/prefill",
)


def _versions() -> dict:
    from importlib import metadata

    return {name: metadata.version(name) for name in ("jax", "jaxlib", "libtpu")}


def result_line(devices) -> str:
    """The last line of stdout: exactly ``ok`` and ``device``, the device as
    JAX reports it. Everything else the run learned is in the report line."""
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    return json.dumps({"ok": True, "device": device})


def main() -> int:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(
            f"chip_smoke: no TPU found — jax.devices() reports platform="
            f"{platform!r} ({devices[0].device_kind!r} x{len(devices)}). This "
            "script proves the serving path on the chip and will not run "
            "anywhere else; the CPU check is tests/test_chip_smoke.py.",
            file=sys.stderr,
        )
        return 1

    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    import numpy as np

    from dmlc_tpu import native
    from dmlc_tpu.cluster.devicemon import CENSUS
    from dmlc_tpu.cluster.localcluster import stop_local_cluster
    from dmlc_tpu.ops import pallas_kernels
    from dmlc_tpu.utils import compile_cache

    device = {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)}
    say(f"device {device}")
    if pallas_kernels.interpret_mode():
        raise AssertionError("Pallas would run interpreted on this backend")
    compile_cache.enable()
    cache_entries_at_start = compile_cache.counters()["entries"]
    phases: dict = {}

    def run(name: str, fn, *args, **kw):
        say(f"phase {name} ...")
        t0 = time.perf_counter()
        facts = fn(*args, **kw)
        phases[name] = {"status": "ok", "wall_s": round(time.perf_counter() - t0, 1), **facts}
        say(f"phase {name} ok in {phases[name]['wall_s']} s")

    native_stale_at_start = native._stale()
    nodes: list = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as tmp:
        try:
            t0 = time.perf_counter()
            nodes, synsets, data_dir = start_cluster(
                Path(tmp), model=VISION_MODEL, n_classes=1000, image_size=256,
                gen_model=GEN_MODEL, dispatch_shard_size=512,
            )
            node = nodes[0]
            phases["start"] = {"status": "ok", "wall_s": round(time.perf_counter() - t0, 1)}
            say(f"cluster up in {phases['start']['wall_s']} s")

            # What the node says about itself over the RPC surface.
            info = node.rpc.call(node.self_member_addr, "node.info", {}, timeout=10.0)
            status = node.rpc.call(node.self_member_addr, "node.status", {}, timeout=10.0)
            if info["platform"] != "tpu" or status["platform"] != "tpu":
                raise AssertionError(f"node reports platform {info['platform']!r}")
            if info["decode_backend"] != "native":
                raise AssertionError(
                    "the native decode library did not build on this host; "
                    "the node serves through PIL")
            if info["chips"] != len(devices):
                raise AssertionError(f"node.info chips={info['chips']}, jax sees {len(devices)}")
            if not status["generate"]["models"][GEN_MODEL]["use_pallas"]:
                raise AssertionError("node.status: the engine serves the XLA attention on the chip")
            engine = node._gen_backends[GEN_MODEL]._scheduler.engine
            if MOSAIC_CALL not in lowered_step_text(engine):
                raise AssertionError(
                    f"gen/{GEN_MODEL}/step lowered without a Mosaic custom call")
            # Eager warm-up compiled every program the node serves with.
            warmed = CENSUS.snapshot()["labels"]
            missing = [label for label in REQUIRED_LABELS if label not in warmed]
            if missing:
                raise AssertionError(f"census shows no compile for {missing} after start")

            run("serve", serve_phase, node, model=VISION_MODEL, synsets=synsets,
                data_dir=data_dir)
            rng = np.random.default_rng(0)
            lengths, max_new = [5, 17, 33, 48, 64], [32, 24, 16, 28, 20]
            prompts = [rng.integers(0, 1024, n).tolist() for n in lengths]
            run("generate", lambda: {
                **generate_phase(node, model=GEN_MODEL, prompts=prompts, max_new=max_new),
                "batched_admission": batched_admission(
                    node.config, model=GEN_MODEL, prompts=prompts[:3]),
                "pool_memory": pool_memory(POOL_GEOMETRY)})

            run("family", family_phase)
            run("kernels", kernels_phase, devices)
            interpreted = [k for k, v in phases["kernels"].items()
                           if isinstance(v, dict) and not v["mosaic"]]
            if interpreted:
                raise AssertionError(f"kernels lowered without Mosaic: {interpreted}")

            if len(devices) > 1:
                run("multichip", multichip_phase, node, VISION_MODEL, GEN_MODEL, devices)
            else:
                phases["multichip"] = {"status": "skipped", "why": "1 device"}
        finally:
            stop_local_cluster(nodes)
            native.pool_shutdown()

    census = CENSUS.snapshot()["labels"]
    stats = [d.memory_stats() for d in jax.local_devices()]
    report = {
        "versions": _versions(),
        # Hits with no entries at start are this run re-compiling HLO it
        # already compiled (e.g. the stream program, identical to the plain).
        "compile_cache": {"dir": compile_cache.cache_dir(),
                          "entries_at_start": cache_entries_at_start,
                          **compile_cache.counters()},
        "phases": phases,
        "census": {label: {"compiles": e["compiles"], "seconds": round(e["seconds"], 2)}
                   for label, e in census.items()},
        "decode": {"backend": info["decode_backend"],
                   "built_this_run": bool(native_stale_at_start)},
        "peak_hbm_bytes": [s["peak_bytes_in_use"] for s in stats],
    }
    print(json.dumps({"report": report}), flush=True)
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
