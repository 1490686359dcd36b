"""Artifact-proof bench sentinel: incremental atomic writes + regression gate.

The complaint on record (round 5's artifact, now
tests/fixtures/bench_truncated_tail.json): a BENCH artifact lost its
headline keys to tail truncation — a number that cannot be re-read from
the artifact was never really measured. Two halves fix that:

- **writer** (:class:`BenchArtifact`): a measuring process streams each
  section's keys into a schema-versioned JSON as they are computed
  (the root bench script that did so is retired; nothing in the tree
  writes one today) —
  every write is temp + ``os.replace`` (a torn process never leaves a
  half-file) with a SHA-256 sidecar, and the doc carries the device
  fingerprint and git sha, so a BENCH json is self-identifying and
  integrity-checkable;
- **comparator** (:func:`compare` / ``veles_tpu observe regress OLD
  NEW``): per-key, direction-aware (time keys regress UP,
  throughput/MFU keys regress DOWN), with spread-aware tolerances —
  each key's allowance is the base tolerance plus the measured
  run-to-run spreads the bench already records (``*_spread``), so a
  noisy key needs a real move to fail the gate and a tight key cannot
  hide a real regression behind someone else's noise. Exit 0 clean,
  1 on regression (``make regress`` wires this into CI), 2 on
  unreadable artifacts.

The loader (:func:`load_bench`) reads every historical format: the
sentinel schema, the driver wrapper (``{"tail": ..., "parsed": ...}``),
a flat bench line — and RECOVERS keys from a truncated tail with a
scanning parser, because the round artifacts we must compare against
already lost their heads.

The elastic router keys (docs/elastic_serving.md) ride the existing
direction rules: ``elastic_failover_ms`` is lower-better via the
``_ms`` suffix; ``elastic_scale_x``, ``elastic_affinity_hit_rate``
and ``elastic_tokens_per_sec_*`` take the higher-better default, so
a dropped scale efficiency or affinity hit rate fails the gate
(directions pinned in tests/test_deploy.py).
"""

import hashlib
import json
import os
import re
import time

SCHEMA_VERSION = 1

#: numeric key suffixes where LOWER is better (times, overhead
#: shares). NOT "_sec" alone: throughput keys end in "tokens_per_sec";
#: "_sec_mean" covers the headline's epoch_sec_mean (seconds/epoch);
#: "_bytes" covers the reshard AND fleet-reduce keys (bytes on the
#: wire per transition/reduce — a schedule or reduce tier that starts
#: moving more data regressed; fleet_reduce[_bf16|_int8]_bytes,
#: docs/compiler_fleet.md);
#: "_hit_fraction" is the paged admission ratio (hit admit wall over
#: cold prefill wall — a cache that stops saving work regressed) and
#: "_flatness" the paged step-time max/min across the length sweep
#: (docs/paged_kv.md).
#: "_compiles" covers the AOT cold-start keys: coldstart_compiles counts live XLA compiles
#: booked against decode programs during an AOT-booted warmup — its
#: flat-zero value IS the zero-retrace proof, so any growth regressed;
#: coldstart_*_ms keys ride the "_ms" rule (docs/aot_artifacts.md).
#: The request-truth observability keys (observe/reqledger.py +
#: observe/slo.py): the per-request decode_continuous_ttft_p50/
#: p95/p99_ms and decode_continuous_tpot_p95_ms ride the "_ms" rule
#: (latency percentiles regress UP); "burn_rate" covers any exported
#: SLO burn-rate key (veles_slo_burn_rate snapshots in artifacts) —
#: burning MORE error budget is always a regression.
#: The fleet mapreduce keys' directions (docs/compiler_fleet.md):
#: fleet_reduce*_ms / fleet_host_baseline_ms / fleet_step_ms regress
#: UP via "_ms"; fleet_reduce*_bytes regress UP via "_bytes";
#: fleet_step_mfu and fleet_inprogram_speedup use the higher-is-better
#: default (and "_mfu"/"_speedup" carry spread siblings below).
#: The serving-governor keys (observe/governor.py):
#: governor_demote_to_recover_ms rides the "_ms" rule (a
#: slower fault->demote->recover loop regressed); "_transitions"
#: regresses UP (more ladder moves for the same seeded fault profile
#: is oscillation — the hysteresis got worse); the per-tier
#: governor_*_attainment keys use the higher-is-better default (SLO
#: attainment dropping at a tier is a regression).
#: The metric-history keys (observe/history.py): incident_mttd_ms (fault injection -> anomaly firing, the
#: mean-time-to-detect of the seeded chaos profile) rides the "_ms"
#: rule — a slower detector regressed; "_ns" covers the sampler
#: overhead keys (history_sample_on_ns / history_sample_off_ns:
#: steady-state nanoseconds per registry sample with the history
#: store on vs off — the embedded recorder growing its tax is a
#: regression); "_anomaly_rate" regresses UP (more rule firings for
#: the same seeded fault profile means the rules got noisier, the
#: detector equivalent of governor oscillation).
#: The fleet goodput-observatory keys (observe/fleetscope.py):
#: fleet_goodput_fraction uses the
#: higher-is-better default (less of the fleet's wall time doing
#: useful compute is a regression — the bare "_fraction" suffix is
#: deliberately NOT lower-better; only _hit_fraction /
#: _overhead_fraction are); fleet_straggler_detect_ms rides "_ms" (a
#: slower straggler detector regressed) and
#: fleet_span_ship_overhead_ns rides "_ns" (the span ring growing its
#: record-path tax is a regression).
#: The serving goodput-observatory keys (observe/servescope.py):
#: serve_goodput_fraction and the occupancy
#: fraction use the higher-is-better default (less of the dispatched
#: work being useful — or fewer lane-steps carrying a live request —
#: is a regression; the bare "_fraction" stays higher-better, the
#: fleetscope doctrine); "_waste_share" regresses UP — both the
#: aggregate serve_waste_share and the per-cause
#: serve_<cause>_waste_share keys, so a padding/overshoot/dead-slot
#: cause quietly growing its share fails the gate even while
#: tokens/sec holds; serve_scope_note_ns rides "_ns" (the accounting
#: ring growing its record-path tax is a regression);
#: "_shed_requests" regresses UP (deploy_swap_shed_requests is pinned
#: at 0 — any shed across the swap window breaks the zero-downtime
#: contract, enforced as a hard assert in tests/test_deploy.py since
#: a 0 baseline passes the ratio gate vacuously).
#: The fused paged-attention kernel keys (ops/paged_attention.py):
#: the per-length
#: decode_paged_kernel_step_len<L>_ms and the mixed-occupancy
#: decode_paged_{kernel,gather}_step_mixed_ms ride "_ms";
#: decode_paged_kernel_step_flatness rides "_flatness" (the kernel's
#: whole claim is that step cost tracks live tokens — flatness
#: drifting up means the live-page walk stopped paying);
#: decode_paged_kernel_speedup (gather/kernel at ragged occupancy)
#: uses the higher-is-better default via "_speedup", so the
#: kernel-vs-gather win is itself regress-gated.
#: The traffic record-replay + capacity keys (observe/replay.py,
#: observe/capacity.py — docs/traffic_replay.md): capacity_sustained_tokens_per_sec (what
#: the config sustains at the recorded mix before an SLO breach) and
#: capacity_cliff_warp_x (the warp factor where the cliff sits) use
#: the higher-is-better default — a PR that silently costs 15% of
#: peak throughput, or moves the cliff closer, fails the gate;
#: replay_schedule_skew_ms (planned-vs-actual arrival skew p95 of the
#: open-loop replayer) rides the "_ms" rule — a replayer that cannot
#: hold its own schedule invalidates every capacity number downstream;
#: replay_fidelity_delivered_ratio (delivered/recorded tokens on a 1x
#: round trip) uses the higher-is-better default — trace round-trip
#: fidelity decaying is a recorder or replayer bug, gated like any
#: throughput loss.
#: The memscope keys (observe/memscope.py — docs/memscope.md): the per-owner hbm_owner_*_bytes keys ride
#: "_bytes" (an owner's footprint quietly growing at fixed geometry is
#: a regression — the whole point of attribution is making that
#: visible per cause); "_untagged_fraction" regresses UP and needs its
#: OWN suffix entry because the bare "_fraction" is deliberately
#: higher-better (the fleetscope doctrine above) — untagged residue
#: growing means the accountants stopped explaining the device total,
#: i.e. attribution coverage decayed; headroom_forecast_s uses the
#: higher-is-better default (the pool exhausting SOONER at the same
#: admission profile is a regression).
_LOWER_BETTER = ("_ms", "_seconds", "_sec_mean", "_overhead_fraction",
                 "_overhead_pct", "_std", "_bytes", "_hit_fraction",
                 "_flatness", "_compiles", "burn_rate", "_transitions",
                 "_ns", "_anomaly_rate", "_waste_share",
                 "_shed_requests", "_untagged_fraction")
#: key suffixes that are measurement metadata, never compared
_SKIP_SUFFIXES = ("_config", "_spread", "_warn", "_spread_warn")
#: spread-carrying metric suffixes: "<base><suffix>" looks up
#: "<base>_spread" for its tolerance allowance
_SPREAD_METRIC_SUFFIXES = ("_tokens_per_sec", "_images_per_sec",
                           "_step_ms", "_device_ms", "_block_ms",
                           "_ms", "_mfu", "_gflops", "_speedup")

#: the scanning parser for truncated artifacts: complete
#: "key": <number|bool|null|"str"> pairs survive anywhere in the text
_KV_RE = re.compile(
    r'"([A-Za-z_][A-Za-z0-9_]*)"\s*:\s*'
    r'(-?(?:\d+\.?\d*|\.\d+)(?:[eE][+-]?\d+)?|true|false|null|"[^"]*")')


def sha256_of(path):
    digest = hashlib.sha256()
    with open(path, "rb") as fin:
        for block in iter(lambda: fin.read(1 << 16), b""):
            digest.update(block)
    return digest.hexdigest()


def _atomic_write(path, text):
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "w") as fout:
        fout.write(text)
    os.replace(tmp, path)


def _keys_digest(keys):
    """Canonical hash of the measured keys, embedded IN the artifact
    doc — atomic with the payload it protects, unlike the two-file
    sidecar pair (a kill between the artifact and sidecar replaces
    leaves a stale sidecar beside an intact artifact)."""
    return hashlib.sha256(
        json.dumps(keys, sort_keys=True, default=str).encode()
    ).hexdigest()


def device_fingerprint():
    """What machine produced this artifact — enough to refuse a
    cross-device comparison knowingly."""
    out = {}
    try:
        import jax
        out["backend"] = jax.default_backend()
        devices = jax.devices()
        out["device_kind"] = devices[0].device_kind
        out["device_count"] = len(devices)
        out["jax"] = jax.__version__
    except Exception:
        pass
    return out


def git_sha(cwd=None):
    import subprocess
    try:
        proc = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True,
            text=True, timeout=10,
            cwd=cwd or os.path.dirname(os.path.abspath(__file__)))
        if proc.returncode == 0:
            return proc.stdout.strip()
    except Exception:
        pass
    return None


class BenchArtifact:
    """Incremental, atomic, hash-sidecar'd bench artifact writer.

    ``update({...})`` merges keys and rewrites the file immediately —
    a bench process killed mid-run (or a captured stdout truncated at
    the tail) leaves every section completed so far on disk, intact."""

    def __init__(self, path, meta=None):
        self.path = path
        self.keys = {}
        self.meta = {
            "schema": SCHEMA_VERSION,
            "created": time.time(),
            "device": device_fingerprint(),
            "git_sha": git_sha(),
        }
        if meta:
            self.meta.update(meta)

    @property
    def sidecar_path(self):
        return self.path + ".sha256"

    def update(self, mapping):
        """Merge a section's keys and persist (atomic + sidecar)."""
        if not mapping:
            return self
        self.keys.update(mapping)
        self.write()
        return self

    def write(self):
        doc = dict(self.meta, updated=time.time(), keys=self.keys,
                   keys_sha256=_keys_digest(self.keys))
        try:
            directory = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(directory, exist_ok=True)
            text = json.dumps(doc, indent=1, default=str)
            _atomic_write(self.path, text)
            # hash the bytes just written, no re-read (the same
            # write-tee doctrine as the snapshotter's sidecars)
            digest = hashlib.sha256(text.encode()).hexdigest()
            _atomic_write(self.sidecar_path, "%s  %s\n" % (
                digest, os.path.basename(self.path)))
        except OSError:
            import logging
            logging.getLogger("BenchArtifact").exception(
                "bench artifact write failed: %s", self.path)
        return self.path


def verify_sidecar(path):
    """True when the ``.sha256`` sidecar matches, False on mismatch
    (an empty/torn sidecar is a mismatch, not a crash), None when
    there is no sidecar to check."""
    sidecar = path + ".sha256"
    if not os.path.isfile(sidecar):
        return None
    with open(sidecar, "r") as fin:
        fields = fin.read().split()
    if not fields:
        return False
    return fields[0].strip() == sha256_of(path)


def recover_keys(text):
    """Scan arbitrary (possibly truncated) text for complete
    ``"key": value`` pairs — the salvage path for artifacts that lost
    their head or tail."""
    out = {}
    for match in _KV_RE.finditer(text):
        key, raw = match.group(1), match.group(2)
        try:
            out[key] = json.loads(raw)
        except ValueError:
            continue
    return out


def load_bench(path):
    """Load any BENCH artifact shape into ``(keys, info)``.

    Handles: the sentinel schema (``{"schema", "keys"}``), the round
    driver wrapper (``{"tail", "parsed", ...}`` — a truncated tail
    degrades to the scanning parser), and a flat bench dict. ``info``
    records the format, truncation recovery and sidecar verdict."""
    info = {"path": path, "sidecar": verify_sidecar(path),
            "recovered": False}
    with open(path, "r") as fin:
        text = fin.read()
    try:
        doc = json.loads(text)
    except ValueError:
        # the file ITSELF is torn: salvage what scans
        info["format"] = "torn"
        info["recovered"] = True
        return recover_keys(text), info
    if not isinstance(doc, dict):
        raise ValueError("%s: not a JSON object" % path)
    if isinstance(doc.get("keys"), dict) and "schema" in doc:
        info["format"] = "sentinel-v%s" % doc.get("schema")
        info["meta"] = {k: doc.get(k)
                        for k in ("device", "git_sha", "created")}
        recorded = doc.get("keys_sha256")
        if recorded is not None:
            info["keys_intact"] = recorded == _keys_digest(doc["keys"])
        return dict(doc["keys"]), info
    if "tail" in doc or "parsed" in doc:
        info["format"] = "driver-wrapper"
        parsed = doc.get("parsed")
        if isinstance(parsed, dict):
            return dict(parsed), info
        tail = doc.get("tail") or ""
        try:
            line = json.loads(tail)
            if isinstance(line, dict):
                return line, info
        except ValueError:
            pass
        # the round-5 artifact's case: the tail lost its head — salvage the
        # complete pairs instead of declaring the round unmeasured
        info["recovered"] = True
        return recover_keys(tail), info
    info["format"] = "flat"
    return dict(doc), info


# -- comparison -------------------------------------------------------------

def _comparable(key, value):
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        return False
    return not key.endswith(_SKIP_SUFFIXES)


def _lower_is_better(key):
    return key.endswith(_LOWER_BETTER)


def _spread_for(keys, key):
    """The recorded run-to-run spread backing ``key``: its own
    ``<key>_spread`` sibling, or the shared ``<base>_spread`` after
    stripping a known metric suffix."""
    direct = keys.get(key + "_spread")
    if isinstance(direct, (int, float)) and not isinstance(direct, bool):
        return float(direct)
    for suffix in _SPREAD_METRIC_SUFFIXES:
        if key.endswith(suffix):
            sibling = keys.get(key[:-len(suffix)] + "_spread")
            if isinstance(sibling, (int, float)) \
                    and not isinstance(sibling, bool):
                return float(sibling)
    return 0.0


def compare(old, new, base_tolerance=0.1, allow_missing=()):
    """Compare two key dicts; returns the findings list, worst first.

    Each comparable key's allowance is ``base_tolerance`` plus both
    runs' recorded spreads (spread-aware: the noisy decode keys carry
    their own noise budget; tight keys stay tight). A key present in
    ``old`` but absent from ``new`` is itself a regression — that is
    exactly how tail truncation silently dropped r5's headline."""
    findings = []
    for key in sorted(old):
        old_value = old[key]
        if not _comparable(key, old_value):
            continue
        if key not in new:
            if key in allow_missing:
                continue
            findings.append({"key": key, "verdict": "missing",
                             "old": old_value, "new": None})
            continue
        new_value = new[key]
        if isinstance(new_value, bool) \
                or not isinstance(new_value, (int, float)):
            findings.append({"key": key, "verdict": "type-changed",
                             "old": old_value, "new": new_value})
            continue
        tolerance = base_tolerance + _spread_for(old, key) \
            + _spread_for(new, key)
        entry = {"key": key, "old": old_value, "new": new_value,
                 "tolerance": round(tolerance, 4)}
        if old_value == 0:
            entry["verdict"] = "ok"  # no meaningful ratio off zero
            findings.append(entry)
            continue
        ratio = new_value / old_value
        entry["ratio"] = round(ratio, 4)
        if _lower_is_better(key):
            regressed = ratio > 1.0 + tolerance and old_value > 0
        else:
            regressed = ratio < 1.0 - tolerance and old_value > 0
        entry["verdict"] = "regressed" if regressed else "ok"
        findings.append(entry)
    for key in sorted(set(new) - set(old)):
        if _comparable(key, new[key]):
            findings.append({"key": key, "verdict": "new",
                             "old": None, "new": new[key]})
    order = {"missing": 0, "type-changed": 0, "regressed": 1, "ok": 2,
             "new": 3}
    findings.sort(key=lambda f: (order.get(f["verdict"], 2), f["key"]))
    return findings


def regressions(findings):
    return [f for f in findings
            if f["verdict"] in ("regressed", "missing", "type-changed")]


def compare_main(old_path, new_path, tolerance=0.1, as_json=False,
                 allow_missing=()):
    """``veles_tpu observe regress OLD NEW`` — exit 0 clean, 1 on
    regression, 2 on unreadable/forged artifacts."""
    try:
        old, old_info = load_bench(old_path)
        new, new_info = load_bench(new_path)
    except (OSError, ValueError) as exc:
        print("cannot load artifacts: %s" % exc)
        return 2
    for info in (old_info, new_info):
        if info.get("keys_intact") is False:
            print("INTEGRITY FAILURE: %s embedded keys hash does not "
                  "match its keys" % info["path"])
            return 2
        if info["sidecar"] is False:
            if info.get("keys_intact"):
                # the crash-window case: a kill between the artifact
                # and sidecar replaces leaves a stale sidecar beside
                # an intact artifact — the embedded hash is atomic
                # with the keys, so trust it and say so
                print("warning: %s .sha256 sidecar is stale (the "
                      "embedded keys hash verifies); proceeding"
                      % info["path"])
            else:
                print("INTEGRITY FAILURE: %s does not match its "
                      ".sha256 sidecar" % info["path"])
                return 2
        if info["recovered"]:
            print("note: %s recovered from a truncated artifact "
                  "(%d keys salvaged)"
                  % (info["path"],
                     len(old if info is old_info else new)))
    if not old:
        print("no comparable keys in %s" % old_path)
        return 2
    findings = compare(old, new, base_tolerance=tolerance,
                       allow_missing=allow_missing)
    bad = regressions(findings)
    if as_json:
        print(json.dumps({"old": old_info, "new": new_info,
                          "regressions": len(bad),
                          "findings": findings}, indent=1,
                         default=str))
    else:
        for finding in findings:
            if finding["verdict"] == "ok":
                continue
            print("%-12s %-45s old=%s new=%s%s" % (
                finding["verdict"].upper(), finding["key"],
                finding.get("old"), finding.get("new"),
                (" (ratio %.3f, tol %.3f)"
                 % (finding["ratio"], finding["tolerance"]))
                if "ratio" in finding else ""))
        ok = sum(1 for f in findings if f["verdict"] == "ok")
        print("%d keys compared ok, %d new, %d regression(s)" % (
            ok, sum(1 for f in findings if f["verdict"] == "new"),
            len(bad)))
    return 1 if bad else 0
