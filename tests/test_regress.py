"""Regression-sentinel tests (docs/observability.md): the incremental
atomic BENCH artifact writer, the any-format loader (including the
truncated-tail recovery against a real truncated round artifact,
tests/fixtures/bench_truncated_tail.json), the spread-aware
comparator, and the CLI exit codes `make regress` gates CI on — the
seeded-regression fixture here is the proof the gate actually exits
nonzero."""

import json
import os

import pytest

from veles_tpu.observe.regress import (BenchArtifact, compare,
                                       compare_main, load_bench,
                                       recover_keys, regressions,
                                       sha256_of, verify_sidecar)

R05 = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "fixtures", "bench_truncated_tail.json")


class TestBenchArtifact:
    def test_incremental_updates_always_parseable(self, tmp_path):
        """Every update leaves a complete, loadable JSON on disk — the
        whole point: a kill between sections loses nothing already
        measured."""
        path = str(tmp_path / "bench.json")
        artifact = BenchArtifact(path)
        artifact.update({"a_tokens_per_sec": 100.0})
        first = json.load(open(path))
        assert first["schema"] == 1
        assert first["keys"] == {"a_tokens_per_sec": 100.0}
        artifact.update({"b_step_ms": 2.5})
        doc = json.load(open(path))
        assert doc["keys"] == {"a_tokens_per_sec": 100.0,
                               "b_step_ms": 2.5}
        # no torn temp files left behind
        leftovers = [n for n in os.listdir(tmp_path) if ".tmp" in n]
        assert leftovers == []

    def test_sidecar_verifies_and_detects_tamper(self, tmp_path):
        path = str(tmp_path / "bench.json")
        BenchArtifact(path).update({"x": 1.0})
        assert verify_sidecar(path) is True
        assert sha256_of(path) == open(path + ".sha256").read().split()[0]
        with open(path, "a") as fout:
            fout.write(" ")
        assert verify_sidecar(path) is False
        os.unlink(path + ".sha256")
        assert verify_sidecar(path) is None

    def test_artifact_carries_fingerprint_and_sha(self, tmp_path):
        path = str(tmp_path / "bench.json")
        BenchArtifact(path).update({"x": 1.0})
        doc = json.load(open(path))
        assert "device" in doc and "git_sha" in doc
        # in a git checkout the sha resolves; either way the KEY exists
        assert doc["git_sha"] is None or len(doc["git_sha"]) == 40


class TestLoader:
    def test_recovers_real_r05_truncated_tail(self):
        """The committed round artifact lost its headline to tail
        truncation; the loader must still salvage every
        complete key so the round stays comparable."""
        keys, info = load_bench(R05)
        assert info["recovered"] is True
        assert info["format"] == "driver-wrapper"
        # the keys AFTER the truncation point are all there
        for key in ("decode_tokens_per_sec", "decode_int8_step_ms",
                    "transformer_mfu", "longctx_pallas_speedup",
                    "decode_continuous_tokens_per_sec"):
            assert key in keys, key
        assert keys["decode_tokens_per_sec"] == 7506.3

    def test_sentinel_schema_roundtrip(self, tmp_path):
        path = str(tmp_path / "bench.json")
        BenchArtifact(path).update({"a_ms": 1.0, "b": "cfg"})
        keys, info = load_bench(path)
        assert keys == {"a_ms": 1.0, "b": "cfg"}
        assert info["format"] == "sentinel-v1"
        assert info["sidecar"] is True
        assert info["recovered"] is False

    def test_flat_and_wrapper_parsed_formats(self, tmp_path):
        flat = tmp_path / "flat.json"
        flat.write_text(json.dumps({"metric": "x", "value": 3.0}))
        keys, info = load_bench(str(flat))
        assert keys["value"] == 3.0 and info["format"] == "flat"
        wrapper = tmp_path / "wrap.json"
        wrapper.write_text(json.dumps(
            {"rc": 0, "tail": "garbage", "parsed": {"value": 5.0}}))
        keys, info = load_bench(str(wrapper))
        assert keys == {"value": 5.0}
        assert info["format"] == "driver-wrapper"

    def test_torn_file_salvaged(self, tmp_path):
        torn = tmp_path / "torn.json"
        torn.write_text('{"a_tokens_per_sec": 12.5, "b_step_ms": 3.0, '
                        '"trunca')
        keys, info = load_bench(str(torn))
        assert keys == {"a_tokens_per_sec": 12.5, "b_step_ms": 3.0}
        assert info["recovered"] is True

    def test_recover_keys_parses_value_kinds(self):
        text = ('"f": 1.5, "i": -3, "e": 1.2e-4, "t": true, '
                '"n": null, "s": "cfg", "torn": 12')
        out = recover_keys(text)
        assert out["f"] == 1.5 and out["i"] == -3
        assert out["e"] == pytest.approx(1.2e-4)
        assert out["t"] is True and out["n"] is None and out["s"] == "cfg"


class TestCompare:
    OLD = {"decode_tokens_per_sec": 1000.0, "decode_spread": 0.01,
           "decode_step_ms": 1.0,
           "noisy_tokens_per_sec": 1000.0, "noisy_spread": 0.4,
           "run_config": "b8", "ok_flag": True}

    def test_identical_runs_clean(self):
        assert regressions(compare(self.OLD, dict(self.OLD))) == []

    def test_throughput_drop_regresses(self):
        new = dict(self.OLD, decode_tokens_per_sec=500.0)
        bad = regressions(compare(self.OLD, new))
        assert [f["key"] for f in bad] == ["decode_tokens_per_sec"]
        assert bad[0]["verdict"] == "regressed"

    def test_time_increase_regresses(self):
        new = dict(self.OLD, decode_step_ms=2.0)
        assert [f["key"] for f in regressions(compare(self.OLD, new))] \
            == ["decode_step_ms"]

    def test_improvements_never_regress(self):
        new = dict(self.OLD, decode_tokens_per_sec=5000.0,
                   decode_step_ms=0.2)
        assert regressions(compare(self.OLD, new)) == []

    def test_spread_aware_tolerance(self):
        """A noisy key (spread 0.4 both sides) tolerates a 30% wobble
        that would fail a tight key — and the tight key still fails."""
        new = dict(self.OLD, noisy_tokens_per_sec=700.0,
                   decode_tokens_per_sec=700.0)
        bad = [f["key"] for f in regressions(compare(self.OLD, new))]
        assert bad == ["decode_tokens_per_sec"]

    def test_missing_key_is_a_regression(self):
        """Tail truncation deletes keys — a missing key must FAIL, not
        silently shrink the comparison (the r5 failure mode)."""
        new = dict(self.OLD)
        del new["decode_tokens_per_sec"]
        bad = regressions(compare(self.OLD, new))
        assert [f["key"] for f in bad] == ["decode_tokens_per_sec"]
        assert bad[0]["verdict"] == "missing"

    def test_new_keys_and_metadata_are_not_regressions(self):
        new = dict(self.OLD, extra_tokens_per_sec=1.0,
                   run_config="b16")
        findings = compare(self.OLD, new)
        assert regressions(findings) == []
        assert any(f["verdict"] == "new"
                   and f["key"] == "extra_tokens_per_sec"
                   for f in findings)

    def test_fleet_mapreduce_key_directions(self):
        """The fleet section's keys (docs/compiler_fleet.md) compare
        with the right better-directions: reduce/baseline/step times
        and wire bytes regress UP, MFU and the in-program speedup
        regress DOWN."""
        old = {"fleet_reduce_ms": 10.0, "fleet_reduce_bytes": 1000,
               "fleet_reduce_int8_bytes": 250,
               "fleet_host_baseline_ms": 100.0,
               "fleet_step_ms": 50.0, "fleet_step_mfu": 0.5,
               "fleet_inprogram_speedup": 10.0}
        worse = {"fleet_reduce_ms": 20.0, "fleet_reduce_bytes": 2000,
                 "fleet_reduce_int8_bytes": 500,
                 "fleet_host_baseline_ms": 200.0,
                 "fleet_step_ms": 100.0, "fleet_step_mfu": 0.25,
                 "fleet_inprogram_speedup": 5.0}
        bad = {f["key"] for f in regressions(compare(old, worse))}
        assert bad == set(old)
        better = {"fleet_reduce_ms": 5.0, "fleet_reduce_bytes": 500,
                  "fleet_reduce_int8_bytes": 100,
                  "fleet_host_baseline_ms": 100.0,
                  "fleet_step_ms": 25.0, "fleet_step_mfu": 0.9,
                  "fleet_inprogram_speedup": 20.0}
        assert regressions(compare(old, better)) == []

    def test_request_latency_and_burn_rate_directions(self):
        """The request-truth observability keys (ISSUE 10):
        per-request latency percentiles (decode_continuous_ttft_*/
        tpot_*_ms) and SLO burn rates are LOWER-better — a slower p99
        or a hotter error-budget burn regresses even while tokens/sec
        holds."""
        old = {"decode_continuous_ttft_p50_ms": 10.0,
               "decode_continuous_ttft_p95_ms": 25.0,
               "decode_continuous_ttft_p99_ms": 40.0,
               "decode_continuous_tpot_p95_ms": 2.0,
               "serve_slo_burn_rate": 0.5,
               "decode_continuous_tokens_per_sec": 1000.0}
        worse = {"decode_continuous_ttft_p50_ms": 20.0,
                 "decode_continuous_ttft_p95_ms": 50.0,
                 "decode_continuous_ttft_p99_ms": 80.0,
                 "decode_continuous_tpot_p95_ms": 4.0,
                 "serve_slo_burn_rate": 2.0,
                 "decode_continuous_tokens_per_sec": 1000.0}
        bad = {f["key"] for f in regressions(compare(old, worse))}
        assert bad == set(old) - {"decode_continuous_tokens_per_sec"}
        better = {key: value / 2 if key !=
                  "decode_continuous_tokens_per_sec" else value
                  for key, value in old.items()}
        assert regressions(compare(old, better)) == []

    def test_history_key_directions(self):
        """The metric-history keys (ISSUE 12):
        incident_mttd_ms rides the _ms rule (a slower detector
        regressed), the sampler-overhead _ns keys and the
        _anomaly_rate key are LOWER-better too (a pricier or noisier
        embedded recorder regresses even while throughput holds)."""
        old = {"incident_mttd_ms": 400.0,
               "history_sample_on_ns": 50000.0,
               "history_sample_off_ns": 20000.0,
               "history_anomaly_rate": 0.01}
        worse = {"incident_mttd_ms": 900.0,
                 "history_sample_on_ns": 150000.0,
                 "history_sample_off_ns": 60000.0,
                 "history_anomaly_rate": 0.2}
        bad = {f["key"] for f in regressions(compare(old, worse))}
        assert bad == set(old)
        better = {key: value / 2 for key, value in old.items()}
        assert regressions(compare(old, better)) == []

    def test_servescope_key_directions(self):
        """The serving goodput-observatory keys (observe/servescope.py):
        serve_goodput_fraction and the occupancy fraction are
        HIGHER-better (less useful work is a regression), every
        *_waste_share key — aggregate and per-cause — regresses UP,
        and the record-path overhead rides the _ns rule."""
        old = {"serve_goodput_fraction": 0.8,
               "serve_slot_occupancy_fraction": 0.7,
               "serve_waste_share": 0.2,
               "serve_dead_slot_waste_share": 0.1,
               "serve_group_dup_waste_share": 0.05,
               "serve_scope_note_ns": 500.0}
        worse = {"serve_goodput_fraction": 0.4,
                 "serve_slot_occupancy_fraction": 0.3,
                 "serve_waste_share": 0.6,
                 "serve_dead_slot_waste_share": 0.3,
                 "serve_group_dup_waste_share": 0.15,
                 "serve_scope_note_ns": 1500.0}
        bad = {f["key"] for f in regressions(compare(old, worse))}
        assert bad == set(old)
        better = {"serve_goodput_fraction": 0.95,
                  "serve_slot_occupancy_fraction": 0.9,
                  "serve_waste_share": 0.05,
                  "serve_dead_slot_waste_share": 0.02,
                  "serve_group_dup_waste_share": 0.01,
                  "serve_scope_note_ns": 250.0}
        assert regressions(compare(old, better)) == []

    def test_capacity_and_replay_key_directions(self):
        """The traffic record-replay + capacity keys (observe/
        replay.py, observe/capacity.py — docs/traffic_replay.md):
        sustained tokens/sec, the cliff warp
        and round-trip fidelity are HIGHER-better (a config that
        sustains less, cliffs earlier or loses replayed tokens
        regressed); the replayer's schedule skew rides the _ms rule."""
        old = {"capacity_sustained_tokens_per_sec": 1000.0,
               "capacity_cliff_warp_x": 8.0,
               "replay_fidelity_delivered_ratio": 1.0,
               "replay_schedule_skew_ms": 5.0}
        worse = {"capacity_sustained_tokens_per_sec": 600.0,
                 "capacity_cliff_warp_x": 3.0,
                 "replay_fidelity_delivered_ratio": 0.6,
                 "replay_schedule_skew_ms": 50.0}
        bad = {f["key"] for f in regressions(compare(old, worse))}
        assert bad == set(old)
        better = {"capacity_sustained_tokens_per_sec": 1500.0,
                  "capacity_cliff_warp_x": 12.0,
                  "replay_fidelity_delivered_ratio": 1.0,
                  "replay_schedule_skew_ms": 1.0}
        assert regressions(compare(old, better)) == []

    def test_fifteen_percent_capacity_loss_regresses(self):
        """The ISSUE-19 contract: a PR that silently costs 15% of peak
        throughput must fail the gate (base tolerance is 10%)."""
        old = {"capacity_sustained_tokens_per_sec": 1000.0}
        new = {"capacity_sustained_tokens_per_sec": 850.0}
        bad = regressions(compare(old, new))
        assert [f["key"] for f in bad] \
            == ["capacity_sustained_tokens_per_sec"]
        assert bad[0]["verdict"] == "regressed"

    def test_type_change_is_a_regression(self):
        new = dict(self.OLD, decode_step_ms="fast")
        assert regressions(compare(self.OLD, new))[0]["verdict"] \
            == "type-changed"


class TestSentinelCLI:
    def test_real_r05_self_comparison_exits_zero(self, capsys):
        """The `make regress` acceptance path: the committed r05
        artifact against itself through the full loader (exercising
        truncation recovery) is clean."""
        assert compare_main(R05, R05) == 0
        out = capsys.readouterr().out
        assert "0 regression(s)" in out
        assert "recovered from a truncated artifact" in out

    def test_seeded_regression_fixture_exits_nonzero(self, tmp_path,
                                                     capsys):
        """The other half of `make regress`: prove the gate actually
        FAILS on a regression — a gate that can't fail proves
        nothing."""
        keys, _ = load_bench(R05)
        seeded = dict(keys)
        seeded["decode_tokens_per_sec"] = \
            keys["decode_tokens_per_sec"] * 0.5
        new_path = str(tmp_path / "seeded.json")
        BenchArtifact(new_path).update(seeded)
        assert compare_main(R05, new_path) == 1
        assert "REGRESSED" in capsys.readouterr().out

    def test_seeded_capacity_loss_fixture_exits_one(self, tmp_path,
                                                    capsys):
        """The ISSUE-19 acceptance fixture: two artifacts identical
        but for a 15% capacity_sustained_tokens_per_sec loss — the
        full CLI path (artifact load, direction lookup, tolerance)
        exits 1 and names the key."""
        base = {"capacity_sustained_tokens_per_sec": 1200.0,
                "capacity_cliff_warp_x": 6.0,
                "replay_schedule_skew_ms": 4.0,
                "replay_fidelity_delivered_ratio": 1.0}
        old_path = str(tmp_path / "main.json")
        new_path = str(tmp_path / "pr.json")
        BenchArtifact(old_path).update(base)
        BenchArtifact(new_path).update(
            dict(base, capacity_sustained_tokens_per_sec=1020.0))
        assert compare_main(old_path, new_path) == 1
        out = capsys.readouterr().out
        assert "REGRESSED" in out
        assert "capacity_sustained_tokens_per_sec" in out

    def test_unreadable_artifact_exits_two(self, tmp_path):
        missing = str(tmp_path / "nope.json")
        assert compare_main(missing, R05) == 2

    def test_tampered_keys_exit_two(self, tmp_path, capsys):
        """Edited measurements fail the embedded keys hash — exit 2."""
        path = str(tmp_path / "bench.json")
        BenchArtifact(path).update({"a_tokens_per_sec": 1.0})
        doc = json.load(open(path))
        doc["keys"]["a_tokens_per_sec"] = 99.0  # forge the number
        with open(path, "w") as fout:
            json.dump(doc, fout)
        assert compare_main(path, path) == 2
        assert "INTEGRITY FAILURE" in capsys.readouterr().out

    def test_stale_sidecar_with_intact_keys_proceeds(self, tmp_path,
                                                     capsys):
        """The crash-window case: a kill between the artifact and
        sidecar writes leaves a stale sidecar beside an INTACT
        artifact — the embedded keys hash (atomic with the payload)
        vouches for it and the comparison proceeds with a warning
        instead of discarding a real measurement."""
        path = str(tmp_path / "bench.json")
        artifact = BenchArtifact(path)
        artifact.update({"a_tokens_per_sec": 1.0})
        stale = open(path + ".sha256").read()
        artifact.update({"b_step_ms": 2.0})
        with open(path + ".sha256", "w") as fout:
            fout.write(stale)  # the pre-crash sidecar
        assert verify_sidecar(path) is False
        assert compare_main(path, path) == 0
        assert "sidecar is stale" in capsys.readouterr().out

    def test_empty_sidecar_is_a_mismatch_not_a_crash(self, tmp_path):
        path = str(tmp_path / "bench.json")
        BenchArtifact(path).update({"a_tokens_per_sec": 1.0})
        open(path + ".sha256", "w").close()  # zero-byte sidecar
        assert verify_sidecar(path) is False

    def test_json_output(self, tmp_path, capsys):
        path = str(tmp_path / "bench.json")
        BenchArtifact(path).update({"a_tokens_per_sec": 1.0})
        assert compare_main(path, path, as_json=True) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["regressions"] == 0

    def test_observe_cli_routes_regress(self, tmp_path, capsys):
        from veles_tpu.observe.trace_export import main as observe_main

        path = str(tmp_path / "bench.json")
        BenchArtifact(path).update({"a_tokens_per_sec": 1.0})
        assert observe_main(["regress", path, path]) == 0

