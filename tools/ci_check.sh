#!/usr/bin/env bash
# The one correctness-tooling gate (docs/LINT.md, docs/ANALYZE.md):
#
#   1. static analysis  — dmlc-lint (file-local invariants, tools/lint)
#                         + dmlc-analyze (whole-program concurrency,
#                         protocol, and device-semantics rules A1-A9,
#                         tools/analyze), gated through the findings
#                         ratchet (tools/ratchet.py vs the committed
#                         tools/analysis_baseline.json): any finding not
#                         in the baseline fails; entries that stop firing
#                         warn so the baseline only shrinks
#   2. model checker    — dmlc-mc (tools/mc, docs/MODELCHECK.md): bounded
#                         exhaustive DPOR exploration of the 2-node
#                         protocol scenarios (breaker, SDFS put/crash/heal,
#                         generate exactly-once ack) + a seeded random-walk
#                         leg on the 3-node membership tree (walk seeds
#                         offset by DMLC_CHAOS_SEED, like the chaos
#                         matrix); wall-clock capped inside tools/mc ci.
#                         Violations are shrunk to minimal schedules and
#                         gated through the same ratchet (--mc-findings),
#                         so a new interleaving bug fails the build with a
#                         replayable witness
#   3. ruff             — generic Python lint (ruff.toml)
#   4. mypy --strict    — types, strict on dmlc_tpu/cluster/,
#                         dmlc_tpu/generate/,
#                         dmlc_tpu/scheduler/placement.py, and
#                         dmlc_tpu/parallel/sharding.py (incremental
#                         adoption: other packages are not yet
#                         annotation-complete)
#   5. clang-tidy       — native/*.cpp static analysis (.clang-tidy)
#   6. native build     — the production .so (persistent decode pool)
#                         must compile from source
#   7. sanitizer smoke  — make sanitize + ASan/TSan decode over corrupt
#                         JPEG fixtures through the PERSISTENT pool, incl.
#                         concurrent submitters and pool shutdown/regrow
#                         (tests/test_native_sanitize.py)
#   8. trace smoke      — real localcluster run with tracing on: the
#                         merged Perfetto JSON must load and spans from
#                         >= 2 nodes must share one trace_id with correct
#                         parent ordering (tools/trace_smoke.py)
#   9. bench guard      — the committed bench_detail.json must keep every
#                         section README/PARITY cite, including the
#                         device-plane ledger (compile census, peak HBM,
#                         MFU vs roofline) with every MFU a ratio in
#                         (0, 1] — an MFU regression or a malformed
#                         device capture fails here, machine-visibly
#                         (tests/test_bench_guard.py)
#  10. loadgen smoke    — seeded flash-crowd replay through the sim fleet
#                         (tools/slo_cert.py): fails unless slo_cert.json
#                         validates against the schema, error traces were
#                         force-sampled into the merged fleet trace, and
#                         leader scrape cost held the 4*sqrt(N) tree
#                         bound; one leg per chaos seed base
#  10b. drift sentinel  — seeded drift replay (tools/slo_cert.py
#                         --critpath): a 5x decode slowdown on exactly one
#                         member mid-replay must leave critpath lane
#                         shares summing to 1 per model, every burn alert
#                         naming its culprit, and the sentinel naming
#                         (model, stage, member) within 3 fast windows,
#                         opening a forced-sampling window, and requesting
#                         a replan; one leg per chaos seed base
#  11. gang smoke       — sharded predict at 3 and 8 virtual devices must
#                         be token-identical to the mesh-of-1 reference
#                         and every served rule table must audit healthy
#                         (__graft_entry__.gang_smoke, docs/SHARDING.md);
#                         one leg per chaos seed base
#  12. chaos matrix     — the seeded fault-injection suites (crashes,
#                         partitions, failover, disk bit-rot/torn writes,
#                         overload: deadlines/shedding/breakers/gray
#                         ejection, the generation join/leave soak with
#                         exactly-once token delivery, and the placement
#                         soak: SLO burn -> profile-driven replan) across a
#                         3-seed-base matrix: each leg offsets every
#                         parametrized seed range into a disjoint region
#                         of the fault space (DMLC_CHAOS_SEED)
#
# Tools the image does not ship (ruff, mypy, clang-tidy) are SKIPPED with
# a notice instead of failing the gate — the repo must not depend on
# packages the container cannot install. dmlc-lint and the sanitizer
# smoke always run.
set -u
cd "$(dirname "$0")/.."

fail=0
note() { printf '== %s\n' "$*"; }

note "static analysis ratchet (dmlc-lint + dmlc-analyze vs tools/analysis_baseline.json)"
if python -m tools.ratchet; then
  note "static analysis OK (no findings outside the committed baseline)"
else
  note "static analysis FAILED (new findings above; fix or justify-suppress, docs/LINT.md + docs/ANALYZE.md)"
  fail=1
fi

note "model checker (dmlc-mc: exhaustive 2-node scenarios + seeded membership walks, docs/MODELCHECK.md)"
MC_SEED="${DMLC_CHAOS_SEED:-0}"
MC_JSON="/tmp/mc_findings_$MC_SEED.json"
if env JAX_PLATFORMS=cpu python -m tools.mc ci --seed "$MC_SEED" --json "$MC_JSON"; then
  if python -m tools.ratchet --mc-findings "$MC_JSON"; then
    note "model checker OK (no violations outside the committed baseline)"
  else
    note "model checker FAILED (shrunk schedules above; replay: python -m tools.mc replay <repro.json>)"
    fail=1
  fi
else
  note "model checker FAILED to run (tool error)"
  fail=1
fi

note "ruff"
if command -v ruff >/dev/null 2>&1; then
  ruff check dmlc_tpu/ tools/ tests/ || fail=1
elif python -c "import ruff" >/dev/null 2>&1; then
  python -m ruff check dmlc_tpu/ tools/ tests/ || fail=1
else
  note "ruff SKIPPED (not installed in this image)"
fi

note "mypy (strict on dmlc_tpu/cluster/ + dmlc_tpu/generate/ + dmlc_tpu/scheduler/placement.py + dmlc_tpu/parallel/sharding.py)"
if command -v mypy >/dev/null 2>&1 || python -c "import mypy" >/dev/null 2>&1; then
  python -m mypy --strict dmlc_tpu/cluster/ dmlc_tpu/generate/ \
    dmlc_tpu/scheduler/placement.py dmlc_tpu/parallel/sharding.py || fail=1
else
  note "mypy SKIPPED (not installed in this image)"
fi

note "clang-tidy (native/)"
if command -v clang-tidy >/dev/null 2>&1; then
  clang-tidy native/image_pipeline.cpp native/sanitize_main.cpp \
    -- -std=c++17 || fail=1
else
  note "clang-tidy SKIPPED (not installed in this image)"
fi

note "native build (persistent decode pool .so)"
if command -v g++ >/dev/null 2>&1 && command -v make >/dev/null 2>&1; then
  if make -s -C native; then
    note "native build OK"
  else
    fail=1
  fi
else
  note "native build SKIPPED (g++/make not in this image)"
fi

note "sanitizer smoke (make sanitize + corrupt-JPEG decode via the persistent pool)"
if env JAX_PLATFORMS=cpu python -m pytest tests/test_native_sanitize.py -q \
    -p no:cacheprovider; then
  note "sanitizer smoke OK"
else
  fail=1
fi

note "trace smoke (localcluster + merged fleet Perfetto trace)"
if env JAX_PLATFORMS=cpu python tools/trace_smoke.py; then
  note "trace smoke OK"
else
  fail=1
fi

note "bench guard (bench_detail.json sections + device-plane ledger validation)"
if env JAX_PLATFORMS=cpu python -m pytest tests/test_bench_guard.py -q \
    -p no:cacheprovider; then
  note "bench guard OK"
else
  note "bench guard FAILED (bench_detail.json lost a section or carries a malformed/regressed device capture)"
  fail=1
fi

note "chaos suite (3-seed matrix: crashes/partitions/failover x disk faults x overload x generation soak x placement soak x decode-tier kills x loadgen SLO cert)"
for seed_base in 0 1000 2000; do
  note "loadgen SLO-cert smoke DMLC_CHAOS_SEED=$seed_base (seeded flash-crowd replay)"
  if env JAX_PLATFORMS=cpu python tools/slo_cert.py --members 24 --duration 90 \
      --base-rps 30 --flash 30:20:6 --sample-rate 0.01 --seed "$seed_base" \
      --out "/tmp/slo_cert_$seed_base.json"; then
    note "loadgen smoke $seed_base OK (/tmp/slo_cert_$seed_base.json)"
  else
    note "loadgen smoke $seed_base FAILED (replay: python tools/slo_cert.py --seed $seed_base --out /tmp/slo_cert_$seed_base.json)"
    fail=1
  fi
  note "tenant-isolation smoke DMLC_CHAOS_SEED=$seed_base (two-tenant flash-crowd replay + autoscaler convergence, docs/OVERLOAD.md)"
  if env JAX_PLATFORMS=cpu python tools/slo_cert.py --tenants --members 6 \
      --sample-rate 1.0 --seed "$seed_base" \
      --out "/tmp/slo_cert_tenants_$seed_base.json"; then
    note "tenant-isolation smoke $seed_base OK (/tmp/slo_cert_tenants_$seed_base.json)"
  else
    note "tenant-isolation smoke $seed_base FAILED (replay: python tools/slo_cert.py --tenants --seed $seed_base --out /tmp/slo_cert_tenants_$seed_base.json)"
    fail=1
  fi
  note "session-churn smoke DMLC_CHAOS_SEED=$seed_base (generate-heavy churn: seeded kills mid-stream + drain, exactly-once tokens, docs/GENERATE.md)"
  if env JAX_PLATFORMS=cpu python tools/slo_cert.py --sessions --members 4 \
      --seed "$seed_base" --out "/tmp/slo_cert_sessions_$seed_base.json"; then
    note "session-churn smoke $seed_base OK (/tmp/slo_cert_sessions_$seed_base.json)"
  else
    note "session-churn smoke $seed_base FAILED (replay: python tools/slo_cert.py --sessions --members 4 --seed $seed_base --out /tmp/slo_cert_sessions_$seed_base.json)"
    fail=1
  fi
  note "drift-sentinel smoke DMLC_CHAOS_SEED=$seed_base (5x decode slowdown on one member mid-replay: critpath shares sum to 1, every burn carries its culprit, sentinel names the member within the detection bound, docs/OBSERVABILITY.md section 9)"
  if env JAX_PLATFORMS=cpu python tools/slo_cert.py --critpath --members 4 \
      --seed "$seed_base" --out "/tmp/slo_cert_critpath_$seed_base.json"; then
    note "drift-sentinel smoke $seed_base OK (/tmp/slo_cert_critpath_$seed_base.json)"
  else
    note "drift-sentinel smoke $seed_base FAILED (replay: python tools/slo_cert.py --critpath --members 4 --seed $seed_base --out /tmp/slo_cert_critpath_$seed_base.json)"
    fail=1
  fi
  note "gang smoke DMLC_CHAOS_SEED=$seed_base (sharded predict vs mesh-of-1 reference at 3 and 8 virtual devices, docs/SHARDING.md)"
  if env DMLC_CHAOS_SEED="$seed_base" python -c \
      "import __graft_entry__ as g; g.gang_smoke(3); g.gang_smoke(8)"; then
    note "gang smoke $seed_base OK"
  else
    note "gang smoke $seed_base FAILED (gang result diverged from the single-chip reference or a rule table went unhealthy)"
    fail=1
  fi
  note "chaos matrix leg DMLC_CHAOS_SEED=$seed_base"
  if env JAX_PLATFORMS=cpu DMLC_CHAOS_SEED="$seed_base" python -m pytest \
      tests/test_chaos.py tests/test_sdfs_faults.py tests/test_overload.py \
      tests/test_generate_cluster.py tests/test_placement.py \
      tests/test_scrapetree.py tests/test_loadgen.py \
      tests/test_decodetier.py tests/test_tenant.py \
      tests/test_autoscaler.py tests/test_genrouter.py \
      -q -p no:cacheprovider; then
    note "chaos leg $seed_base OK"
  else
    note "chaos leg $seed_base FAILED (replay: DMLC_CHAOS_SEED=$seed_base pytest tests/test_chaos.py tests/test_sdfs_faults.py tests/test_overload.py tests/test_generate_cluster.py tests/test_placement.py tests/test_decodetier.py)"
    fail=1
  fi
done

if [ "$fail" -ne 0 ]; then
  note "ci_check FAILED"
  exit 1
fi
note "ci_check OK"
