"""Regression tests for bench.py's bounded-time failure paths: every
failure mode must print ONE parseable JSON line with an "error" field
and per-phase wall-clock history, and a backend that is not a TPU is an
error unless the caller pinned the CPU."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_bench(env_extra, timeout=120):
    env = dict(os.environ)
    # hermetic persistent caches: the stall/watchdog premises assume the
    # subprocess actually PAYS its compiles — a developer/CI home dir
    # whose JAX disk cache (or AOT executable cache) is already warm at
    # these shapes would silently collapse warmup below the stall bound
    # and flip the expected rc (observed: the cache warmed by one run
    # broke the next).  Tests that exercise the caches point them at a
    # tmp path explicitly.
    env.setdefault("CYCLONUS_JAX_CACHE", "0")
    env.setdefault("CYCLONUS_AOT_CACHE", "0")
    env.setdefault("CYCLONUS_AUTOTUNE_CACHE", "0")
    # hermetic cache-key registry too: a developer shell that exported
    # CYCLONUS_KEYHARNESS=1 (the key-mutation harness env) would arm the
    # registry in the subprocess and flip the key_audit/strip-proof
    # asserts — hard-pin, not setdefault, because an exported "1"
    # survives setdefault
    env["CYCLONUS_KEYHARNESS"] = "0"
    # the CPU is pinned in-process below as well as by the suite's
    # JAX_PLATFORMS env: the pin is what lets bench run off a TPU at all
    env.update(env_extra)
    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu'); "
        "import bench; bench.main()"
    )
    return subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=REPO,
        env=env,
    )


def last_json_line(stdout):
    sys.path.insert(0, REPO)
    from bench import last_json_line as parse

    out = parse(stdout)
    assert out is not None, f"no JSON line in output: {stdout[-500:]}"
    return out


class TestBenchGuards:
    def test_watchdog_emits_error_json(self):
        proc = run_bench(
            {
                "BENCH_DEADLINE_S": "2",
                "BENCH_PODS": "30000",
                "BENCH_POLICIES": "3000",
            }
        )
        assert proc.returncode == 2
        out = last_json_line(proc.stdout)
        assert "watchdog" in out["error"]
        assert out["value"] == 0
        assert out["vs_baseline"] == 0.0
        # a watchdog kill inside the measured pipeline is an
        # ENGINE-side failure class
        assert out["failure_class"] == "watchdog_stall"
        phases = [p[0] for p in out["detail"]["phase_history_s"]]
        assert "startup" in phases  # history present and labeled
        # detail.pack rides FAILURE lines too (env-resolved plan; no
        # engine means no winner/autotune forensics yet)
        pack = out["detail"]["pack"]
        assert pack["active"] is True  # CYCLONUS_PACK default
        assert pack["dtype"] == "packed32"
        assert pack["winner"] is None

    def test_stall_bound_fires_inside_one_phase(self):
        """The per-phase stall trigger: total deadline generous, but a
        phase that stops advancing (here: encode or warmup, each well
        over a second at this shape on the CPU) trips BENCH_STALL_S with
        the phase named in the error."""
        proc = run_bench(
            {
                "BENCH_STALL_S": "1",
                "BENCH_DEADLINE_S": "600",
                "BENCH_PODS": "30000",
                "BENCH_POLICIES": "3000",
                "BENCH_MESH": "0",
                "BENCH_PARITY": "0",
            }
        )
        assert proc.returncode == 2
        out = last_json_line(proc.stdout)
        assert "stalled" in out["error"]

    def test_crash_emits_error_json_then_raises(self):
        # an invalid counts backend crashes inside _bench: the JSON error
        # line must still be printed before the traceback propagates
        proc = run_bench(
            {
                "BENCH_COUNTS_BACKEND": "not-a-backend",
                "BENCH_PODS": "64",
                "BENCH_POLICIES": "8",
                "BENCH_DEADLINE_S": "0",
                "BENCH_MESH": "0",
                "BENCH_PARITY": "0",
            }
        )
        assert proc.returncode != 0
        out = last_json_line(proc.stdout)
        assert "error" in out
        assert "not-a-backend" in out["error"]

    def test_unpinned_non_tpu_backend_is_an_error(self):
        """No CPU leg to fall back to: when JAX's default backend is not
        a TPU and the caller did not pin the CPU, the bench prints the
        backend_init error line, names the platform it found, and exits
        4 before building anything."""
        env = dict(os.environ)
        # unpinned: the CPU plugin is the only one that can initialise
        # on the test host, so JAX lands on it the way it would on any
        # chipless machine
        env["JAX_PLATFORMS"] = ""
        env.update({"BENCH_PODS": "64", "BENCH_POLICIES": "8"})
        proc = subprocess.run(
            [sys.executable, "-c", "import bench; bench.main()"],
            capture_output=True,
            text=True,
            timeout=120,
            cwd=REPO,
            env=env,
        )
        assert proc.returncode == 4, proc.stdout[-500:] + proc.stderr[-500:]
        out = last_json_line(proc.stdout)
        assert out["failure_class"] == "backend_init"
        assert out["value"] == 0
        assert "not tpu" in out["error"]
        assert out["detail"]["device"]["platform"] in out["error"]
        phases = [p[0] for p in out["detail"]["phase_history_s"]]
        assert phases[-1] == "backend_attach"

    def test_roofline_refuses_a_device_without_peaks(self):
        sys.path.insert(0, REPO)
        import bench
        import pytest

        assert "TPU v5 lite" in bench.DEVICE_PEAKS
        with pytest.raises(ValueError, match="no roofline peaks"):
            bench.roofline_model(None, 2, 1.0, "TPU v9000")

    def test_trace_dir_records_written_artifact(self, tmp_path):
        """BENCH_TRACE_DIR (= bench.py --trace-dir) wraps the eval phase
        in jax.profiler.trace; the JSON line's detail.trace block must
        point at the dir and confirm the profiler left an artifact."""
        cap_dir = str(tmp_path / "cap")
        proc = run_bench(
            {
                "BENCH_TRACE_DIR": cap_dir,
                "BENCH_PODS": "64",
                "BENCH_POLICIES": "8",
                "BENCH_SAMPLE": "3",
                "BENCH_MESH": "0",
                "BENCH_PARITY": "0",
                "BENCH_COUNTS_BACKEND": "xla",
            },
            timeout=300,
        )
        assert proc.returncode == 0, proc.stdout[-800:] + proc.stderr[-500:]
        out = last_json_line(proc.stdout)
        assert out["detail"]["trace"] == {"dir": cap_dir, "written": True}
        assert any(files for _, _, files in os.walk(cap_dir))

    def test_success_line_parses_with_detail_blocks(self):
        proc = run_bench(
            {
                "BENCH_PODS": "256",
                "BENCH_POLICIES": "20",
                "BENCH_SAMPLE": "3",
                "BENCH_MESH": "0",
                "BENCH_PARITY": "0",
                "BENCH_COUNTS_BACKEND": "xla",
            },
            timeout=300,
        )
        assert proc.returncode == 0, proc.stdout[-800:] + proc.stderr[-500:]
        out = last_json_line(proc.stdout)
        assert "error" not in out
        assert out["unit"] == "cells/sec"
        assert out["value"] > 0
        # healthy runs SAY so — "ok" is never inferred from an absent
        # error field
        assert out["failure_class"] == "ok"
        detail = out["detail"]
        # every line names the device it ran on, as JAX reports it
        # (here the pinned CPU)
        assert detail["device"]["platform"] == "cpu"
        assert detail["device"]["count"] >= 1
        assert detail["backend_init_s"] is not None
        # the per-phase wall-clock history rides success lines too
        phases = [p[0] for p in detail["phase_history_s"]]
        assert phases[0] == "startup"
        assert "backend_attach" in phases
        assert "warmup" in phases and "eval" in phases
        # AOT executable-cache counters as of the end of warmup (here:
        # cache pinned off by the hermetic run_bench env)
        aot = detail["aot_cache"]
        for k in ("hits", "misses", "adopted", "compiles"):
            assert aot[k] == 0
        assert aot["dir"] is None
        # the cache-key registry census (utils/cachekeys.py): inert
        # outside the key-mutation harness env, so the audit records
        # inactive with zero registrations
        assert detail["key_audit"] == {"active": False, "registered": 0}
        # the chaos kill/restart leg left the bench: its parent holds
        # the chip its serve children would need (`make chaos` keeps
        # the scenario on the CPU)
        assert "chaos" not in detail and "cold_start" not in detail
        # detail.wire rides EVERY line: the wire-protocol generation
        # plus the live registry skew sweep (worker/wireregistry.py) —
        # both skew directions for every registered message through the
        # real codecs, asserted clean inside the bench
        wire = detail["wire"]
        assert wire["schema_version"] >= 5
        assert wire["keys"] >= 30
        assert wire["skew_pairs_checked"] >= 10
        assert "eval_reps" in detail and len(detail["eval_reps"]) == 5
        # roofline only reports for the pallas backend
        assert detail["roofline"] is None
        # detail.pack rides EVERY success line: the dtype plan, the
        # packed word depths, and the autotune forensics slot (None on
        # CPU, where the auto search never engages)
        pack = detail["pack"]
        assert pack["active"] is True
        assert pack["dtype"] == "packed32"
        assert isinstance(pack["words"], list) and len(pack["words"]) == 2
        assert all(w >= 1 for w in pack["words"])
        assert "winner" in pack and "autotune" in pack
        # class compression rides EVERY line;
        # at 256 pods the auto mode stays on the legacy paths
        cc = detail["class_compression"]
        assert cc["active"] is False and cc["pods"] == 256
        assert cc["ratio"] is None
        # BENCH_MEGA defaults to auto = TPU-only; on this CPU run the
        # block records as absent-by-default
        assert detail["mega_class"] is None
        # detail.mesh rides EVERY line; with BENCH_MESH=0 the leg is
        # skipped but the block — and its schema — still appears, rows
        # empty
        mesh = detail["mesh"]
        assert mesh["rows"] == [] and mesh["skipped"] == "BENCH_MESH=0"
        assert mesh["schedule"] == "ring"
        # the precedence-tier leg rides EVERY line: a deterministic
        # ANP/BANP lattice
        # with oracle spot parity enforced inside the leg
        tiers = detail["tiers"]
        assert tiers["active"] is True
        assert tiers["anp_count"] == 3 and tiers["banp"] is True
        assert tiers["resolve_s"] > 0
        assert tiers["parity_spot_checks"] >= 1
        # the TSS/LPM CIDR pre-classification leg rides EVERY line: a
        # forced-TSS engine on
        # an ipBlock-heavy synthetic cluster with oracle spot parity and
        # the dense-counts cross-check enforced inside the leg
        cidr = detail["cidr"]
        assert cidr["active"] is True
        assert cidr["distinct_cidrs"] >= 1
        assert cidr["partitions"] >= 1
        assert cidr["classes"] >= 1
        assert cidr["ratio"] >= 1
        assert cidr["lpm_s"] is not None
        assert cidr["parity_spot_checks"] >= 1
        assert "speedup_vs_dense" in cidr
        # the telemetry block rides every BENCH line: metrics incl.
        # cache hit/miss counters + HBM watermarks, span aggregates,
        # and the flight window
        tel = detail["telemetry"]
        assert "cyclonus_tpu_pre_cache_hits_total" in tel["metrics"]
        assert "cyclonus_tpu_slab_hbm_bytes" in tel["metrics"]
        # the lock-discipline annotations (guarded _slab_choice /
        # _slab_ops_cache, locked reads in the dispatch path) must not
        # cost the telemetry block its cache-counter schema — the
        # counters live on exactly the code paths that were annotated
        assert "cyclonus_tpu_slab_ops_cache_hits_total" in tel["metrics"]
        assert "cyclonus_tpu_slab_ops_cache_misses_total" in tel["metrics"]
        # the tensor-contract counter only exists under
        # CYCLONUS_SHAPE_CHECK=1 (utils/contracts.py registers it on
        # first check) — its ABSENCE here proves the production strip
        # is real, not just cheap
        assert "cyclonus_tpu_contract_checks_total" not in tel["metrics"]
        # same strip proof for the cache-key registry instruments
        # (utils/cachekeys.py): they register only under
        # CYCLONUS_KEYHARNESS=1, so a production BENCH line never
        # carries them
        assert not any(
            name.startswith("cyclonus_tpu_cachekey")
            for name in tel["metrics"]
        )
        assert "engine.dispatch" in tel["phases"]
        assert any(
            e["path"].startswith("counts.") for e in tel["flight_recorder"]
        )
        # warmup_phases now sources from the same span registry (encode
        # happens before the warmup-start reset, so dispatch is the
        # marker phase)
        assert "engine.dispatch" in detail["warmup_phases"]
        # every BENCH line must record its device-profile provenance:
        # whether a --trace-dir/BENCH_TRACE_DIR jax-profiler artifact
        # was written this run (here: no capture requested)
        assert detail["trace"] == {"dir": None, "written": False}

    def test_mega_class_case_records_compression(self):
        """BENCH_MEGA=1 (shrunk for CI) runs the synthetic-cluster
        compression case: detail.mega_class.class_compression carries
        pods/classes/ratio/gather_s, the HBM-budget check, the oracle
        spot parity, and the class-reduction audit — the same block the
        1M-pod TPU run records."""
        proc = run_bench(
            {
                "BENCH_PODS": "128",
                "BENCH_POLICIES": "12",
                "BENCH_SAMPLE": "3",
                "BENCH_MESH": "0",
                "BENCH_PARITY": "0",
                "BENCH_COUNTS_BACKEND": "xla",
                "BENCH_MEGA": "1",
                "BENCH_MEGA_PODS": "4096",
                "BENCH_MEGA_POLICIES": "32",
                "BENCH_MEGA_NS": "8",
                "BENCH_MEGA_SAMPLE": "4",
            },
            timeout=300,
        )
        assert proc.returncode == 0, proc.stdout[-800:] + proc.stderr[-500:]
        out = last_json_line(proc.stdout)
        mega = out["detail"]["mega_class"]
        assert mega is not None and "status" not in mega, mega
        cc = mega["class_compression"]
        assert cc["active"] is True
        assert cc["pods"] == 4096
        assert 0 < cc["classes"] < 4096
        assert cc["ratio"] > 1.0
        assert cc["gather_s"] is not None
        assert mega["hbm_budget_ok"] is True
        assert mega["audit"]["ok"] is True
        assert mega["parity_spot_checks"] == 4
        assert mega["cells"] == 2 * 4096 * 4096
