"""Weather-proofing guards in bench.py (round-3 post-mortem).

The round-3 driver capture ran in a degraded window: every config
measured ~1/20th of its known rate, the bench blew its own budget, and the
artifact writer overwrote committed e2e/flash/train sections with nulls.
These tests pin the pure-logic guards that prevent a recurrence:

- ``degraded_vs_best``: >3x-off-best detection (latency OR throughput).
- ``update_history_best``: degraded runs never improve the record.
- ``merge_detail``: skipped sections keep previous data stamped stale.
"""

import json
import subprocess
import sys

import bench


def _cfg(model="resnet18", batch=1024, ips=30000.0, p50=140.0, **kw):
    return dict(
        {
            "model": model,
            "batch_size": batch,
            "images_per_sec_per_chip": ips,
            "p50_ms": p50,
        },
        **kw,
    )


HB = {"resnet18@1024": {"images_per_sec_per_chip": 31033.6, "p50_ms": 140.41}}


class TestDegradedVsBest:
    def test_healthy_run_not_flagged(self):
        assert not bench.degraded_vs_best(_cfg(ips=29000, p50=150), HB)

    def test_throughput_collapse_flagged(self):
        # The literal round-3 capture: 1407 img/s vs best 31033.
        assert bench.degraded_vs_best(_cfg(ips=1407.5, p50=821.04), HB)

    def test_latency_collapse_alone_flagged(self):
        assert bench.degraded_vs_best(_cfg(ips=29000, p50=600.0), HB)

    def test_unknown_config_never_flagged(self):
        assert not bench.degraded_vs_best(_cfg(model="vit_b16", ips=1.0), HB)

    def test_best_without_p50_uses_throughput(self):
        hb = {"resnet18@512": {"images_per_sec_per_chip": 20619.6, "p50_ms": None}}
        assert bench.degraded_vs_best(_cfg(batch=512, ips=5000, p50=None), hb)
        assert not bench.degraded_vs_best(_cfg(batch=512, ips=19000, p50=None), hb)


class TestConfigTailGuard:
    """VERDICT r4 weak #4: committed p99s must reflect chip behavior or
    carry an explicit degraded annotation."""

    HB_TAIL = {
        "resnet50@512": {
            "images_per_sec_per_chip": 12000.0,
            "p50_ms": 145.0,
            "p99_ms": 152.0,
            "tail_ratio": 1.05,
        }
    }

    def test_contaminated_tail_flagged_with_best_known(self):
        # The literal shipping artifact: resnet50 p99 314 ms over p50 147.
        r = _cfg(model="resnet50", batch=512, ips=11900.0, p50=147.0, p99_ms=314.0)
        bench.annotate_config_tails([r], self.HB_TAIL)
        assert r["tail_degraded_vs_history"]
        assert r["tail_ratio"] == 2.14
        assert r["best_p99_ms"] == 152.0

    def test_healthy_tail_not_flagged(self):
        r = _cfg(model="resnet50", batch=512, ips=12000.0, p50=145.0, p99_ms=155.0)
        bench.annotate_config_tails([r], self.HB_TAIL)
        assert "tail_degraded_vs_history" not in r
        assert r["best_p99_ms"] == 152.0

    def test_no_history_records_but_never_flags(self):
        # A genuinely heavy-tailed model gets an honest record, not a flag.
        r = _cfg(model="vit_b16", batch=256, ips=2200.0, p50=100.0, p99_ms=250.0)
        bench.annotate_config_tails([r], self.HB_TAIL)
        assert r["tail_ratio"] == 2.5
        assert "tail_degraded_vs_history" not in r

    def test_naturally_wide_tail_within_history_not_flagged(self):
        hb = {"vit_b16@256": {"p99_ms": 180.0, "tail_ratio": 1.8}}
        r = _cfg(model="vit_b16", batch=256, ips=2200.0, p50=100.0, p99_ms=190.0)
        bench.annotate_config_tails([r], hb)
        assert "tail_degraded_vs_history" not in r

    def test_history_folds_min_tail_and_skips_contaminated(self):
        healthy = _cfg(model="resnet50", batch=512, ips=11000.0, p50=146.0, p99_ms=150.0)
        out = bench.update_history_best(self.HB_TAIL, [healthy])
        assert out["resnet50@512"]["p99_ms"] == 150.0
        assert out["resnet50@512"]["tail_ratio"] < 1.05
        contaminated = _cfg(
            model="resnet50", batch=512, ips=11900.0, p50=147.0, p99_ms=314.0,
            tail_degraded_vs_history=True,
        )
        out = bench.update_history_best(self.HB_TAIL, [contaminated])
        assert out["resnet50@512"]["p99_ms"] == 152.0
        assert out["resnet50@512"]["tail_ratio"] == 1.05

    def test_throughput_advance_keeps_tail_record(self):
        # A new throughput best must not erase the p99/ratio reference.
        r = _cfg(model="resnet50", batch=512, ips=12500.0, p50=144.0)
        out = bench.update_history_best(self.HB_TAIL, [r])
        assert out["resnet50@512"]["images_per_sec_per_chip"] == 12500.0
        assert out["resnet50@512"]["p99_ms"] == 152.0
        assert out["resnet50@512"]["tail_ratio"] == 1.05


class TestHistoryBest:
    def test_degraded_never_improves_record(self):
        out = bench.update_history_best(HB, [_cfg(ips=1407.5, p50=821.0)])
        assert out["resnet18@1024"]["images_per_sec_per_chip"] == 31033.6

    def test_better_run_advances_record(self):
        out = bench.update_history_best(HB, [_cfg(ips=32000.0, p50=135.0)])
        assert out["resnet18@1024"] == {
            "images_per_sec_per_chip": 32000.0,
            "p50_ms": 135.0,
        }

    def test_new_config_added(self):
        out = bench.update_history_best(HB, [_cfg(model="vit_b16", batch=256, ips=2227.8)])
        assert "vit_b16@256" in out and len(out) == 2


class TestMergeDetail:
    OLD = {
        "configs": [_cfg(), _cfg(model="resnet50", batch=512, ips=11583.9, p50=145.8)],
        "e2e": {"model": "resnet18", "e2e_img_s": 31.5},
        "batch_curve": {
            "resnet18": [
                {"batch_size": 512, "images_per_sec_per_chip": 20619.6},
                {"batch_size": 1024, "images_per_sec_per_chip": 31033.6},
            ]
        },
        "flash": {"s2048_h8": {"flash_ms": 5.73}},
        "train": {"vit_b16_train": {"images_per_sec": 846.6}},
        "history_best": HB,
    }

    def test_skipped_sections_kept_and_stamped_stale(self):
        # A budget-truncated run: only the headline config landed.
        new = {"configs": [_cfg(ips=30500)], "e2e": None, "batch_curve": {}, "flash": {}, "train": {}}
        out = bench.merge_detail(new, self.OLD)
        assert out["e2e"]["e2e_img_s"] == 31.5 and out["e2e"]["stale"] is True
        # Staleness is stamped INSIDE each kept entry, never at section
        # level where consumers iterate entries.
        assert out["flash"]["s2048_h8"] == {"flash_ms": 5.73, "stale": True}
        assert "stale" not in out["flash"]
        assert out["train"]["vit_b16_train"]["stale"] is True
        assert "stale" not in out["train"]
        # Un-re-measured config kept stale; fresh one not stamped.
        by_model = {r["model"]: r for r in out["configs"]}
        assert by_model["resnet50"]["stale"] is True
        assert "stale" not in by_model["resnet18"]

    def test_partial_section_keeps_missing_entries(self):
        # Deadline truncation mid-section: train reached only vit_b16_train,
        # flash only s2048_h8 — the un-reached entries must survive.
        old = dict(self.OLD, train={"vit_b16_train": {"images_per_sec": 846.6},
                                    "lm_flash_train": {"tokens_per_sec": 89356.0}},
                   flash={"s2048_h8": {"flash_ms": 5.73}, "s8192_h2": {"flash_ms": 6.85}})
        new = {"configs": [_cfg()],
               "flash": {"s2048_h8": {"flash_ms": 5.6}},
               "train": {"vit_b16_train": {"images_per_sec": 850.0}}}
        out = bench.merge_detail(new, old)
        assert out["train"]["vit_b16_train"] == {"images_per_sec": 850.0}
        assert out["train"]["lm_flash_train"]["tokens_per_sec"] == 89356.0
        assert out["train"]["lm_flash_train"]["stale"] is True
        assert out["flash"]["s8192_h2"]["stale"] is True
        assert "stale" not in out["flash"]["s2048_h8"]

    def test_partial_e2e_fields_fall_back(self):
        # bench_e2e truncated after decode: device fields are None and must
        # fall back to the previous run's values, stamped stale.
        old = dict(self.OLD, e2e={"model": "resnet18", "decode_only_img_s": 300.0,
                                  "e2e_img_s": 31.5, "serial_img_s": 47.0})
        new = {"configs": [_cfg()],
               "e2e": {"model": "resnet18", "decode_only_img_s": 310.0,
                       "e2e_img_s": None, "serial_img_s": None}}
        out = bench.merge_detail(new, old)
        assert out["e2e"]["decode_only_img_s"] == 310.0
        assert out["e2e"]["e2e_img_s"] == 31.5
        assert out["e2e"]["stale"] is True

    def test_configs_keyed_by_model_and_batch(self):
        # A --batch-size 256 fallback run must not erase the batch-1024
        # headline row README cites.
        new = {"configs": [_cfg(batch=256, ips=26000, p50=38.0)]}
        out = bench.merge_detail(new, self.OLD)
        rows = {(r["model"], r["batch_size"]): r for r in out["configs"]}
        assert ("resnet18", 256) in rows and "stale" not in rows[("resnet18", 256)]
        assert rows[("resnet18", 1024)]["stale"] is True

    def test_degraded_curve_point_cannot_replace_healthy(self):
        new = {"configs": [],
               "batch_curve": {"resnet18": [
                   {"batch_size": 1024, "images_per_sec_per_chip": 1400.0,
                    "degraded_vs_history": True},
                   {"batch_size": 2048, "images_per_sec_per_chip": 27000.0}]}}
        out = bench.merge_detail(new, self.OLD)
        pts = {p["batch_size"]: p for p in out["batch_curve"]["resnet18"]}
        assert pts[1024]["images_per_sec_per_chip"] == 31033.6  # healthy kept
        assert pts[1024]["stale"] is True
        assert pts[2048]["images_per_sec_per_chip"] == 27000.0  # new batch ok
        # And the degraded point never feeds history_best; the healthy one does.
        assert out["history_best"]["resnet18@1024"]["images_per_sec_per_chip"] == 31033.6
        assert out["history_best"]["resnet18@2048"]["images_per_sec_per_chip"] == 27000.0

    def test_degraded_config_cannot_replace_healthy_row(self):
        # A round-3-style run: the headline is still >3x off after the retry
        # and lands flagged. The committed healthy row must survive; the
        # garbage number lives in the driver's BENCH_r*.json, not here.
        new = {"configs": [_cfg(ips=1407.5, p50=821.0, degraded_vs_history=True)],
               "degraded_window": True}
        out = bench.merge_detail(new, self.OLD)
        rows = {(r["model"], r["batch_size"]): r for r in out["configs"]}
        row = rows[("resnet18", 1024)]
        assert row["images_per_sec_per_chip"] == 30000.0
        assert row["stale"] is True
        # But with no healthy history, the degraded row is kept (flagged).
        out2 = bench.merge_detail(new, {})
        assert out2["configs"][0]["degraded_vs_history"] is True

    def test_partial_e2e_for_different_model_keeps_old_whole(self):
        old = dict(self.OLD, e2e={"model": "resnet18", "decode_only_img_s": 300.0,
                                  "e2e_img_s": 31.5})
        new = {"configs": [],
               "e2e": {"model": "resnet50", "decode_only_img_s": 250.0,
                       "e2e_img_s": None}}
        out = bench.merge_detail(new, old)
        # resnet18's rates must not be attributed to resnet50.
        assert out["e2e"]["model"] == "resnet18"
        assert out["e2e"]["e2e_img_s"] == 31.5 and out["e2e"]["stale"] is True
        # A COMPLETE section for the new model replaces the old outright.
        new2 = {"configs": [],
                "e2e": {"model": "resnet50", "decode_only_img_s": 250.0,
                        "e2e_img_s": 28.0}}
        out2 = bench.merge_detail(new2, old)
        assert out2["e2e"]["model"] == "resnet50" and "stale" not in out2["e2e"]

    def test_curve_best_preserves_p50_reference(self):
        # A curve point (no latency loop) that beats the record must not
        # erase the p50 the latency-degradation check compares against.
        new = {"configs": [],
               "batch_curve": {"resnet18": [
                   {"batch_size": 1024, "images_per_sec_per_chip": 32000.0}]}}
        out = bench.merge_detail(new, self.OLD)
        hb = out["history_best"]["resnet18@1024"]
        assert hb["images_per_sec_per_chip"] == 32000.0
        assert hb["p50_ms"] == 140.41

    def test_fresh_sections_replace_without_stale(self):
        new = {
            "configs": [_cfg()],
            "e2e": {"model": "resnet18", "e2e_img_s": 40.0},
            "batch_curve": {"resnet18": [{"batch_size": 1024, "images_per_sec_per_chip": 31500.0}]},
            "flash": {"s2048_h8": {"flash_ms": 5.5}},
            "train": {"vit_b16_train": {"images_per_sec": 850.0}},
        }
        out = bench.merge_detail(new, self.OLD)
        assert "stale" not in out["e2e"] and out["e2e"]["e2e_img_s"] == 40.0
        assert "stale" not in out["flash"]
        # Curve merges per point: re-measured 1024 fresh, old 512 stale.
        pts = {p["batch_size"]: p for p in out["batch_curve"]["resnet18"]}
        assert "stale" not in pts[1024] and pts[1024]["images_per_sec_per_chip"] == 31500.0
        assert pts[512]["stale"] is True

    def test_history_best_carried_and_updated(self):
        new = {"configs": [_cfg(ips=32000.0, p50=135.0)]}
        out = bench.merge_detail(new, self.OLD)
        assert out["history_best"]["resnet18@1024"]["images_per_sec_per_chip"] == 32000.0

    def test_degraded_run_does_not_poison_history(self):
        new = {"configs": [_cfg(ips=1407.5, p50=821.0)], "degraded_window": True}
        out = bench.merge_detail(new, self.OLD)
        assert out["degraded_window"] is True
        assert out["history_best"]["resnet18@1024"]["images_per_sec_per_chip"] == 31033.6
        # And a later healthy merge drops the flag.
        out2 = bench.merge_detail({"configs": [_cfg()]}, out)
        assert "degraded_window" not in out2

    def test_partial_merge_keeps_roofline_notes(self):
        # A flash-only/manual merge without the notes must not drop them.
        old = dict(self.OLD, roofline_notes={"vit_b16": "bound note"})
        out = bench.merge_detail({"configs": [_cfg()]}, old)
        assert out["roofline_notes"] == {"vit_b16": "bound note"}
        # A run that DOES carry notes refreshes them.
        out2 = bench.merge_detail(
            {"configs": [], "roofline_notes": {"vit_b16": "new"}}, old
        )
        assert out2["roofline_notes"] == {"vit_b16": "new"}

    def test_empty_old_artifact(self):
        new = {"configs": [_cfg()], "e2e": None, "flash": {}, "train": {}}
        out = bench.merge_detail(new, {})
        assert out["e2e"] is None and out["flash"] == {}
        assert out["history_best"]["resnet18@1024"]["images_per_sec_per_chip"] == 30000.0

    def test_device_section_replaced_wholesale_or_kept_stale(self):
        # The device section is a whole-run delta ledger (ISSUE 15): a fresh
        # capture replaces it outright; a run that produced none (crashed
        # before section assembly, or a manual merge) keeps the previous
        # capture stamped stale.
        old = dict(self.OLD, device={"peak_flops": 197e12,
                                     "legs": {"configs": {"compiles": 3}}})
        fresh = {"configs": [_cfg()],
                 "device": {"peak_flops": 1e12, "legs": {"configs": {"compiles": 1}}}}
        out = bench.merge_detail(fresh, old)
        assert out["device"]["peak_flops"] == 1e12
        assert "stale" not in out["device"]
        out2 = bench.merge_detail({"configs": [_cfg()]}, old)
        assert out2["device"]["peak_flops"] == 197e12
        assert out2["device"]["stale"] is True
        # No capture on either side: no section invented.
        assert "device" not in bench.merge_detail({"configs": [_cfg()]}, self.OLD)


def test_load_prev_detail_preserves_corrupt_file(tmp_path, capsys):
    """A truncated/corrupt artifact is moved aside with a warning, never
    silently treated as absent (which would disable every guard)."""
    p = tmp_path / "bench_detail.json"
    p.write_text('{"configs": [trunca')
    out = bench.load_prev_detail(str(p))
    assert out == {}
    assert not p.exists()
    corrupt = tmp_path / "bench_detail.json.corrupt"
    assert corrupt.read_text().startswith('{"configs"')
    assert "unparseable" in capsys.readouterr().err
    # Valid JSON of the wrong shape is preserved the same way, not silently
    # treated as absent (the atomic replace would then destroy it).
    p2 = tmp_path / "shape.json"
    p2.write_text('["not", "an", "object"]')
    assert bench.load_prev_detail(str(p2)) == {}
    assert not p2.exists() and (tmp_path / "shape.json.corrupt").exists()
    assert "unparseable" in capsys.readouterr().err
    # A missing file stays silent.
    assert bench.load_prev_detail(str(tmp_path / "nope.json")) == {}
    assert capsys.readouterr().err == ""


def test_committed_artifact_has_all_sections_and_history():
    """The committed artifact must never again lose sections README/PARITY
    cite: every section present and non-empty, history_best populated."""
    detail = json.loads((bench.Path(__file__).parents[1] / "bench_detail.json").read_text())
    for key in ("configs", "e2e", "batch_curve", "flash", "train", "history_best",
                "roofline_notes", "device", "sharded"):
        assert detail.get(key), f"bench_detail.json[{key!r}] missing or empty"
    assert detail["history_best"].get("resnet18@1024", {}).get(
        "images_per_sec_per_chip", 0
    ) > 10000, "history_best lost the healthy headline record"
    # Device section (ISSUE 15): roofline + census + per-leg ledger, with
    # every MFU reading a ratio in (0, 1] against the platform peak — the
    # shape ci_check.sh's bench-guard step keys on.
    device = detail["device"]
    assert device.get("peak_flops", 0) > 0
    assert isinstance(device.get("legs"), dict) and device["legs"]
    assert isinstance(device.get("census", {}).get("labels"), dict)
    for config, mfu in device.get("mfu", {}).items():
        assert 0 < mfu <= 1.0, f"device.mfu[{config!r}] = {mfu} not a ratio"
    for name, leg in device["legs"].items():
        assert leg.get("compiles", 0) >= 0, name
        assert "peak_hbm_bytes" in leg, name  # present; None off-TPU
    # Sharded leg (ISSUE 17): the gang entry must record WHERE it ran
    # (platform + virtual_devices — the CLIP 2-chip 'speedup' on a 1-core
    # virtual mesh is honest, not a regression), that the gang result is
    # token-identical to the mesh-of-1 reference, and that sharding
    # actually shrank the per-chip resident footprint.
    gang = detail["sharded"]["lm_wide_gang"]
    assert gang["gang"] >= 2
    assert gang["token_identical_vs_ref"] is True
    assert gang["predictions_per_sec"] > 0
    assert gang["per_chip_resident_bytes"] < gang["replicated_bytes"]
    assert gang["platform"] and "virtual_devices" in gang
    tp = detail["sharded"]["clip_tp"]
    assert tp["img_s_1chip"] > 0 and tp["img_s_2chip"] > 0
    assert tp["speedup_2chip"] > 0 and "virtual_devices" in tp


def test_bench_py_compiles():
    subprocess.run(
        [sys.executable, "-m", "py_compile", str(bench.Path(bench.__file__))],
        check=True,
    )


class TestFlashEntryGuard:
    def test_best_tracking_and_degraded_flag(self):
        old = {"s2048_h8": {"flash_ms": 8.65, "dense_ms": 4.83, "best_flash_ms": 3.19,
                            "best_dense_ms": 4.83}}
        # Healthy new reading: advances best, no flag.
        out = bench.annotate_flash_entries(
            {"s2048_h8": {"flash_ms": 3.0, "dense_ms": 5.0, "dense_over_flash": 1.67}}, old
        )
        e = out["s2048_h8"]
        assert e["best_flash_ms"] == 3.0 and "degraded_vs_history" not in e
        # A >2x-off-best reading is flagged and never advances the record.
        out = bench.annotate_flash_entries(
            {"s2048_h8": {"flash_ms": 8.65, "dense_ms": 4.9}}, old
        )
        e = out["s2048_h8"]
        assert e["degraded_vs_history"] is True and e["best_flash_ms"] == 3.19

    def test_no_history_never_flags(self):
        out = bench.annotate_flash_entries({"s8192_h2": {"flash_ms": 9.9, "dense_ms": 9.0}}, {})
        assert "degraded_vs_history" not in out["s8192_h2"]
        assert out["s8192_h2"]["best_flash_ms"] == 9.9

    def test_untimed_entries_pass_through(self):
        out = bench.annotate_flash_entries(
            {"sp2_memory_s8192": {"ring_flash_temp_bytes": 14911496}}, {}
        )
        assert out["sp2_memory_s8192"] == {"ring_flash_temp_bytes": 14911496}

    def test_merge_keeps_healthy_entry_over_degraded(self):
        old = {"configs": [], "flash": {"s2048_h8": {"flash_ms": 3.19, "dense_ms": 5.0}}}
        new = {"configs": [], "flash": {"s2048_h8": {"flash_ms": 8.65, "dense_ms": 4.9,
                                                     "degraded_vs_history": True}}}
        out = bench.merge_detail(new, old)
        assert out["flash"]["s2048_h8"]["flash_ms"] == 3.19
        assert out["flash"]["s2048_h8"]["stale"] is True


class TestE2eGuard:
    OLD = {"model": "resnet18", "e2e_img_s": 113.2, "serial_img_s": 82.0,
           "decode_only_img_s": 684.0, "decode_raw_img_s": 1836.0,
           "overlap_speedup": 1.37}

    def test_healthy_advances_best(self):
        out = bench.annotate_e2e({"model": "resnet18", "e2e_img_s": 120.0,
                                  "serial_img_s": 85.0}, self.OLD)
        assert out["best_e2e_img_s"] == 120.0
        assert "degraded_vs_history" not in out

    def test_collapsed_window_flagged_and_merge_keeps_healthy(self):
        # The literal round-4 capture: e2e 46.3 / overlap 0.8 over 113 / 1.37.
        new = bench.annotate_e2e({"model": "resnet18", "e2e_img_s": 46.3,
                                  "serial_img_s": 58.0}, self.OLD)
        assert new["degraded_vs_history"] is True
        assert new["degraded_legs"] == ["e2e_img_s"]  # serial 58 > 82/2
        assert new["best_e2e_img_s"] == 113.2  # the record never degrades
        merged = bench.merge_detail({"configs": [], "e2e": new},
                                    {"configs": [], "e2e": self.OLD})
        assert merged["e2e"]["e2e_img_s"] == 113.2
        assert merged["e2e"]["stale"] is True
        # The device-crossing trio is repaired as one unit (no cross-window ratios).
        assert merged["e2e"]["repaired_legs"] == ["e2e_img_s", "serial_img_s"]

    def test_per_leg_repair_keeps_healthy_host_legs(self):
        # Round 5: the device-crossing legs collapsed in the SAME window that
        # captured a 3x host-decode improvement — the repair must keep the
        # fresh decode legs, splice the old device-crossing legs, and recompute the
        # derived overlap ratio from the repaired inputs.
        new = bench.annotate_e2e(
            {"model": "resnet18", "e2e_img_s": 56.3, "serial_img_s": 69.5,
             "decode_only_img_s": 1377.5, "decode_raw_img_s": 2357.6,
             "overlap_speedup": 0.81},
            self.OLD,
        )
        assert set(new["degraded_legs"]) == {"e2e_img_s"}
        merged = bench.merge_detail({"configs": [], "e2e": new},
                                    {"configs": [], "e2e": self.OLD})
        e = merged["e2e"]
        assert e["decode_only_img_s"] == 1377.5  # healthy improvement kept
        # The device-crossing trio is repaired as ONE unit: an old-window
        # e2e over a this-window serial is a ratio no run measured (and
        # 113.2/69.5 = 1.63 would exceed the best-known 1.37).
        assert e["e2e_img_s"] == 113.2
        assert e["serial_img_s"] == 82.0
        assert e["overlap_speedup"] == 1.37
        assert e["stale"] is True
        assert e["repaired_legs"] == ["e2e_img_s", "serial_img_s"]
        assert e["best_decode_only_img_s"] == 1377.5

    def test_repaired_label_does_not_leak_into_healthy_run(self):
        # A later fully-healthy run must not inherit the repaired_legs
        # label (or stale) from the previously committed repaired section.
        prev = dict(self.OLD, repaired_legs=["e2e_img_s", "serial_img_s"], stale=True)
        fresh = bench.annotate_e2e(
            {"model": "resnet18", "e2e_img_s": 140.0, "serial_img_s": 120.0,
             "decode_only_img_s": 1400.0, "overlap_speedup": 1.17},
            prev,
        )
        assert "degraded_vs_history" not in fresh
        merged = bench.merge_detail({"configs": [], "e2e": fresh},
                                    {"configs": [], "e2e": prev})
        assert "repaired_legs" not in merged["e2e"]
        assert "stale" not in merged["e2e"]
        assert merged["e2e"]["e2e_img_s"] == 140.0

    def test_no_history_never_flags(self):
        out = bench.annotate_e2e({"model": "resnet18", "e2e_img_s": 46.3}, None)
        assert "degraded_vs_history" not in out
        assert out["best_e2e_img_s"] == 46.3

    def test_none_passthrough(self):
        assert bench.annotate_e2e(None, self.OLD) is None

    STAGES = {"decode": 1.21, "stage": 0.34, "dispatch": 0.05, "sync": 0.41}

    def test_stage_seconds_ride_through_annotate_and_merge(self):
        # The per-stage breakdown (PR 2 ingest metrics) is diagnostic data,
        # not a guarded rate leg: it must pass annotate_e2e untouched and
        # merge fresh-over-old like any field.
        new = bench.annotate_e2e(
            {"model": "resnet18", "e2e_img_s": 120.0, "serial_img_s": 85.0,
             "stage_seconds": dict(self.STAGES)},
            self.OLD,
        )
        assert new["stage_seconds"] == self.STAGES
        assert "degraded_vs_history" not in new
        old = dict(self.OLD, stage_seconds={"decode": 9.0})
        merged = bench.merge_detail({"configs": [], "e2e": new},
                                    {"configs": [], "e2e": old})
        assert merged["e2e"]["stage_seconds"] == self.STAGES
        assert "stale" not in merged["e2e"]

    def test_stage_seconds_none_falls_back_stale(self):
        # A deadline-truncated run (stream leg skipped -> stage_seconds
        # None) keeps the previous breakdown, stamped stale like any
        # truncated field.
        old = dict(self.OLD, stage_seconds=dict(self.STAGES))
        new = {"model": "resnet18", "e2e_img_s": 118.0, "serial_img_s": 84.0,
               "stage_seconds": None}
        merged = bench.merge_detail({"configs": [], "e2e": new},
                                    {"configs": [], "e2e": old})
        assert merged["e2e"]["stage_seconds"] == self.STAGES
        assert merged["e2e"]["stale"] is True

    def test_model_change_judged_fresh(self):
        # A promoted-headline model (legitimately slower) must not be
        # flagged against the previous model's rates, nor inherit its
        # best-known records.
        out = bench.annotate_e2e({"model": "clip_vit_l14", "e2e_img_s": 50.0},
                                 self.OLD)
        assert "degraded_vs_history" not in out
        assert out["best_e2e_img_s"] == 50.0


class TestTrainGuard:
    OLD = {"lm_flash_train": {"batch": 8, "seq": 2048, "chips": 1,
                              "tokens_per_sec_per_chip": 88216.0, "step_ms": 185.7},
           "vit_b16_train": {"batch": 128, "chips": 1,
                             "images_per_sec_per_chip": 827.2, "step_ms": 154.7}}

    def test_collapsed_entry_flagged_and_merge_keeps_healthy(self):
        # The literal round-4 capture: 2845 tok/s over the healthy 88k.
        new = bench.annotate_train_entries(
            {"lm_flash_train": {"batch": 8, "seq": 2048, "chips": 1,
                                "tokens_per_sec_per_chip": 2845.0, "step_ms": 5759.2},
             "vit_b16_train": {"batch": 128, "chips": 1,
                               "images_per_sec_per_chip": 820.3, "step_ms": 156.0}},
            self.OLD)
        assert new["lm_flash_train"]["degraded_vs_history"] is True
        assert new["lm_flash_train"]["best_tokens_per_sec_per_chip"] == 88216.0
        assert "degraded_vs_history" not in new["vit_b16_train"]
        merged = bench.merge_detail({"configs": [], "train": new},
                                    {"configs": [], "train": self.OLD})
        assert merged["train"]["lm_flash_train"]["tokens_per_sec_per_chip"] == 88216.0
        assert merged["train"]["lm_flash_train"]["stale"] is True
        assert merged["train"]["vit_b16_train"]["images_per_sec_per_chip"] == 820.3

    def test_config_change_judged_fresh(self):
        # A deliberate batch/seq/chips change resets history: a legitimate
        # slower config must not be flagged forever.
        new = bench.annotate_train_entries(
            {"lm_flash_train": {"batch": 2, "seq": 2048, "chips": 1,
                                "tokens_per_sec_per_chip": 30000.0}},
            self.OLD)
        assert "degraded_vs_history" not in new["lm_flash_train"]
        assert new["lm_flash_train"]["best_tokens_per_sec_per_chip"] == 30000.0

    def test_no_history_never_flags(self):
        out = bench.annotate_train_entries(
            {"lm_flash_train": {"tokens_per_sec_per_chip": 2845.0}}, {})
        assert "degraded_vs_history" not in out["lm_flash_train"]


class TestLmDecodeGuard:
    """ISSUE 7: the lm_decode leg is guarded like flash/train — a degraded
    window's tok/s never replaces a healthy committed entry, and a
    deliberate slot/page-geometry change is judged fresh."""

    OLD = {"continuous8": {"slots": 8, "requests": 16, "prompt": 128,
                           "max_new": 128, "page_size": 64,
                           "tokens_per_sec": 5200.0, "token_p50_ms": 12.1,
                           "slot_occupancy": 0.81}}

    def test_collapsed_entry_flagged_and_merge_keeps_healthy(self):
        new = bench.annotate_lm_decode_entries(
            {"continuous8": {"slots": 8, "requests": 16, "prompt": 128,
                             "max_new": 128, "page_size": 64,
                             "tokens_per_sec": 240.0, "token_p50_ms": 260.0}},
            self.OLD)
        assert new["continuous8"]["degraded_vs_history"] is True
        assert new["continuous8"]["best_tokens_per_sec"] == 5200.0
        merged = bench.merge_detail({"configs": [], "lm_decode": new},
                                    {"configs": [], "lm_decode": self.OLD})
        assert merged["lm_decode"]["continuous8"]["tokens_per_sec"] == 5200.0
        assert merged["lm_decode"]["continuous8"]["stale"] is True

    def test_healthy_advances_best(self):
        new = bench.annotate_lm_decode_entries(
            {"continuous8": {"slots": 8, "requests": 16, "prompt": 128,
                             "max_new": 128, "page_size": 64,
                             "tokens_per_sec": 6100.0}},
            self.OLD)
        assert "degraded_vs_history" not in new["continuous8"]
        assert new["continuous8"]["best_tokens_per_sec"] == 6100.0
        merged = bench.merge_detail({"configs": [], "lm_decode": new},
                                    {"configs": [], "lm_decode": self.OLD})
        assert merged["lm_decode"]["continuous8"]["tokens_per_sec"] == 6100.0
        assert "stale" not in merged["lm_decode"]["continuous8"]

    def test_geometry_change_judged_fresh(self):
        new = bench.annotate_lm_decode_entries(
            {"continuous8": {"slots": 16, "requests": 16, "prompt": 128,
                             "max_new": 128, "page_size": 64,
                             "tokens_per_sec": 900.0}},
            self.OLD)
        assert "degraded_vs_history" not in new["continuous8"]

    def test_skipped_leg_keeps_previous_stamped_stale(self):
        merged = bench.merge_detail({"configs": [], "lm_decode": {}},
                                    {"configs": [], "lm_decode": self.OLD})
        assert merged["lm_decode"]["continuous8"]["tokens_per_sec"] == 5200.0
        assert merged["lm_decode"]["continuous8"]["stale"] is True

    def test_no_history_never_flags(self):
        out = bench.annotate_lm_decode_entries(
            {"continuous8": {"tokens_per_sec": 240.0}}, {})
        assert "degraded_vs_history" not in out["continuous8"]


class TestShardedGuard:
    """ISSUE 17: the gang-sharded leg is guarded like flash/train/lm_decode,
    and history resets whenever the mesh geometry OR platform changed — a
    first silicon capture must never be judged against virtual-device CPU
    numbers (where the 2-chip CLIP 'speedup' is honestly < 1) or vice versa."""

    OLD = {
        "lm_wide_gang": {"platform": "cpu", "devices": 8, "virtual_devices": True,
                         "model": "lm_wide", "gang": 4, "batch": 16, "prompt": 32,
                         "predictions_per_sec": 154.3,
                         "token_identical_vs_ref": True,
                         "per_chip_resident_bytes": 9741312,
                         "replicated_bytes": 25485312},
        "clip_tp": {"platform": "cpu", "devices": 8, "virtual_devices": True,
                    "model": "clip_vit_l14", "batch": 4,
                    "img_s_1chip": 0.43, "img_s_2chip": 0.40,
                    "speedup_2chip": 0.939},
    }

    def test_collapsed_gang_rate_flagged_and_merge_keeps_healthy(self):
        new = bench.annotate_sharded_entries(
            {"lm_wide_gang": dict(self.OLD["lm_wide_gang"],
                                  predictions_per_sec=12.0)},
            self.OLD)
        assert new["lm_wide_gang"]["degraded_vs_history"] is True
        assert new["lm_wide_gang"]["best_predictions_per_sec"] == 154.3
        merged = bench.merge_detail({"configs": [], "sharded": new},
                                    {"configs": [], "sharded": self.OLD})
        assert merged["sharded"]["lm_wide_gang"]["predictions_per_sec"] == 154.3
        assert merged["sharded"]["lm_wide_gang"]["stale"] is True

    def test_healthy_advances_best_on_both_clip_legs(self):
        new = bench.annotate_sharded_entries(
            {"clip_tp": dict(self.OLD["clip_tp"], img_s_1chip=0.5,
                             img_s_2chip=0.9, speedup_2chip=1.8)},
            self.OLD)
        e = new["clip_tp"]
        assert "degraded_vs_history" not in e
        assert e["best_img_s_1chip"] == 0.5 and e["best_img_s_2chip"] == 0.9

    def test_platform_or_geometry_change_resets_history(self):
        # First TPU capture: 10x the CPU rate either way, judged fresh.
        tpu = bench.annotate_sharded_entries(
            {"lm_wide_gang": dict(self.OLD["lm_wide_gang"], platform="tpu",
                                  devices=4, virtual_devices=False,
                                  predictions_per_sec=15.0)},
            self.OLD)
        assert "degraded_vs_history" not in tpu["lm_wide_gang"]
        assert tpu["lm_wide_gang"]["best_predictions_per_sec"] == 15.0
        wider = bench.annotate_sharded_entries(
            {"lm_wide_gang": dict(self.OLD["lm_wide_gang"], gang=8,
                                  predictions_per_sec=60.0)},
            self.OLD)
        assert "degraded_vs_history" not in wider["lm_wide_gang"]

    def test_skipped_leg_keeps_previous_stamped_stale(self):
        merged = bench.merge_detail({"configs": [], "sharded": {}},
                                    {"configs": [], "sharded": self.OLD})
        assert merged["sharded"]["clip_tp"]["img_s_2chip"] == 0.40
        assert merged["sharded"]["clip_tp"]["stale"] is True

    def test_no_history_never_flags(self):
        out = bench.annotate_sharded_entries(
            {"lm_wide_gang": {"model": "lm_wide", "predictions_per_sec": 1.0}}, {})
        assert "degraded_vs_history" not in out["lm_wide_gang"]


class TestCritpathGuard:
    """ISSUE 20: the e2e leg's critical-path breakdown is guarded like the
    device section — malformed share sums are flagged instead of trusted,
    a bottleneck handoff vs the committed artifact is stamped machine-
    visibly, and the merge keeps a previous capture stamped stale when a
    run produced none (the section is one coherent attribution of a single
    leg, so a fresh capture replaces it wholesale)."""

    OLD = {
        "models": {
            "resnet50": {
                "requests": 128, "total_s": 4.2, "max_lanes": 2,
                "lanes": [
                    {"stage": "decode", "member": "host", "crit_s": 2.9,
                     "share": 0.690476},
                    {"stage": "compute", "member": "tpu0", "crit_s": 1.3,
                     "share": 0.309524},
                ],
                "top_lane": "decode@host",
            }
        }
    }

    def test_healthy_section_stamps_top_lane_only(self):
        out = bench.annotate_critpath_entries(
            json.loads(json.dumps(self.OLD)), self.OLD)
        body = out["models"]["resnet50"]
        assert body["top_lane"] == "decode@host"
        assert "malformed" not in body and "malformed" not in out
        assert "bottleneck_shifted" not in body

    def test_share_sum_off_by_more_than_rounding_is_malformed(self):
        broken = {"models": {"resnet50": {
            "requests": 1, "total_s": 1.0, "max_lanes": 1,
            "lanes": [{"stage": "decode", "member": "host",
                       "crit_s": 0.5, "share": 0.5}],
        }}}
        out = bench.annotate_critpath_entries(broken, None)
        assert out["models"]["resnet50"]["malformed"] is True
        assert out["malformed"] is True

    def test_bottleneck_handoff_stamped_vs_previous_artifact(self):
        fresh = json.loads(json.dumps(self.OLD))
        fresh["models"]["resnet50"]["lanes"].reverse()  # compute now dominates
        del fresh["models"]["resnet50"]["top_lane"]
        out = bench.annotate_critpath_entries(fresh, self.OLD)
        body = out["models"]["resnet50"]
        assert body["top_lane"] == "compute@tpu0"
        assert body["prev_top_lane"] == "decode@host"
        assert body["bottleneck_shifted"] is True

    def test_none_and_no_history_pass_through(self):
        assert bench.annotate_critpath_entries(None, self.OLD) is None
        out = bench.annotate_critpath_entries(
            json.loads(json.dumps(self.OLD)), None)
        assert "bottleneck_shifted" not in out["models"]["resnet50"]

    def test_merge_replaces_wholesale_or_keeps_stale(self):
        fresh = {"models": {"resnet50": {
            "requests": 2, "total_s": 1.0, "max_lanes": 1,
            "lanes": [{"stage": "compute", "member": "tpu0",
                       "crit_s": 1.0, "share": 1.0}],
        }}}
        out = bench.merge_detail(
            {"configs": [], "critpath": fresh},
            {"configs": [], "critpath": self.OLD})
        assert out["critpath"]["models"]["resnet50"]["requests"] == 2
        assert "stale" not in out["critpath"]
        out2 = bench.merge_detail(
            {"configs": [], "critpath": None},
            {"configs": [], "critpath": self.OLD})
        assert out2["critpath"]["stale"] is True
        assert out2["critpath"]["models"]["resnet50"]["requests"] == 128
        # No capture on either side: no section invented.
        assert "critpath" not in bench.merge_detail({"configs": []},
                                                    {"configs": []})


class TestDeviceLegs:
    """bench.py's per-leg device-plane capture (ISSUE 15): census deltas
    bracketed around each leg, assembled into bench_detail.json["device"]."""

    def test_leg_captures_census_delta(self):
        from dmlc_tpu.cluster.devicemon import CENSUS

        dev = bench._DeviceLegs()
        dev.begin("configs")
        CENSUS.record("test/bench_guard_leg", seconds=0.25)
        dev.end("configs")
        leg = dev.legs["configs"]
        assert leg["compiles"] == 1
        assert leg["compile_seconds"] == 0.25
        assert leg["steady_recompiles"] == 0
        assert leg["wall_s"] >= 0
        assert "peak_hbm_bytes" in leg and "hbm_limit_bytes" in leg

    def test_end_without_begin_is_noop(self):
        dev = bench._DeviceLegs()
        dev.end("never_began")
        assert dev.legs == {}

    def test_section_shape_and_mfu_filter(self):
        dev = bench._DeviceLegs()
        dev.begin("configs")
        dev.end("configs")
        section = dev.section([
            {"model": "resnet18", "batch_size": 1024, "mfu": 0.41},
            {"model": "alexnet", "batch_size": 512, "mfu": None},
        ])
        assert section["mfu"] == {"resnet18@1024": 0.41}  # None rows dropped
        # The CPU mesh has no row in the device_kind-keyed peak table.
        assert section["peak_flops"] is None
        assert "configs" in section["legs"]
        assert "labels" in section["census"]


def test_bench_lm_decode_leg_smoke():
    """The leg itself runs (tiny lm_small geometry on CPU) and records the
    fields the guard keys on plus the gen/step span aggregates."""
    import pytest

    pytest.importorskip("jax")
    out = bench.bench_lm_decode(
        model="lm_small", slots=2, n_req=3, prompt_len=6, max_new=4,
        page_size=8, entry_name="smoke",
    )
    entry = out["smoke"]
    assert entry["tokens"] == 3 * 4
    assert entry["tokens_per_sec"] > 0
    assert entry["token_p50_ms"] is not None
    assert "gen/step" in entry["span_aggregates"]
    assert entry["sheds"] == 0
