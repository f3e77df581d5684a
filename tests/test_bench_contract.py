"""Contract tests for the benchmark harness the driver invokes.

The driver runs ``python bench.py`` and records (rc, last stdout line) as
the round's perf evidence — a wrong exit-code policy or a malformed JSON
line silently destroys the evidence chain (exactly what happened in round
2). These tests pin the orchestrator's merge/gate/exit behavior with
stubbed phases (no device work), plus the TTL cache the serving paths use.
"""

from __future__ import annotations

import json

import pytest

import bench


def _run_main(monkeypatch, capsys, phase_results):
    """Invoke bench.main() orchestrator-mode with _run_phase stubbed;
    returns (rc, parsed_json_line)."""

    def fake_run(name, timeout_s, retries=1):
        return phase_results.get(name, ({}, f"{name} stub missing"))

    monkeypatch.setattr(bench, "_run_phase", fake_run)
    monkeypatch.setattr("sys.argv", ["bench.py"])
    rc = bench.main()
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return rc, json.loads(line)


def test_healthy_run_merges_all_phases(monkeypatch, capsys):
    rc, out = _run_main(
        monkeypatch,
        capsys,
        {
            "als": (
                {
                    "scale_name": "ml100k",
                    "als_train_wall_s": 1.5,
                    "als_heldout_rmse": 0.35,
                    "als_rmse_gate_ok": True,
                },
                None,
            ),
            "serving": ({"serving_e2e_p50_ms": 5.0, "serving_e2e_qps": 100.0}, None),
            "twotower": ({"twotower_recall_at_10": 0.2, "twotower_recall_gate_ok": True}, None),
            "secondary": ({"naive_bayes_train_ms": 50.0}, None),
        },
    )
    assert rc == 0
    assert out["metric"] == "als_ml100k_train_wall_clock"
    assert out["value"] == 1.5
    assert out["vs_baseline"] == 0.5  # 5ms p50 / 10ms north star
    assert out["serving_e2e_qps"] == 100.0
    assert "als_error" not in out


def test_failed_phase_recorded_but_partial_numbers_ship(monkeypatch, capsys):
    rc, out = _run_main(
        monkeypatch,
        capsys,
        {
            "als": ({"platform": "tpu", "scale_name": "ml20m"}, "TPU device fault"),
            "serving": ({"serving_e2e_p50_ms": 8.0}, None),
            "twotower": ({}, "timeout"),
            "secondary": ({"cooccurrence_build_ms": 900.0}, None),
        },
    )
    # numbers shipped (serving + secondary) and no gate failed -> healthy,
    # with the failures visible in the line
    assert rc == 0
    assert out["als_error"] == "TPU device fault"
    assert out["twotower_error"] == "timeout"
    assert out["value"] is None  # als never produced the headline
    assert out["vs_baseline"] == 0.8


def test_gate_failure_fails_the_run_but_still_prints(monkeypatch, capsys):
    rc, out = _run_main(
        monkeypatch,
        capsys,
        {
            "als": (
                {
                    "scale_name": "ml100k",
                    "als_train_wall_s": 0.9,
                    "als_heldout_rmse": 1.2,
                    "als_rmse_gate_ok": False,  # junk factors
                },
                None,
            ),
            "serving": ({"serving_e2e_p50_ms": 5.0}, None),
            "twotower": ({}, None),
            "secondary": ({}, None),
        },
    )
    assert rc == 1  # a fast wall-clock over junk factors must not look healthy
    assert out["als_rmse_gate_ok"] is False
    assert out["value"] == 0.9  # forensics still printed


def test_fully_crashed_run_is_rc1(monkeypatch, capsys):
    rc, out = _run_main(
        monkeypatch,
        capsys,
        {
            # metadata-only fields (written before any timed region) must
            # not count as shipped numbers
            "als": ({"platform": "tpu", "scale": {}, "scale_name": "ml20m"}, "boom"),
            "serving": ({"serving_factors": "random_fallback"}, "boom"),
            "twotower": ({}, "boom"),
            "secondary": ({}, "boom"),
        },
    )
    assert rc == 1
    # evidence semantics (ROADMAP item 5): the headline metric is absent,
    # so vs_baseline is OMITTED — a null-paired ratio would invite a
    # reader to rate a measurement that never happened
    assert out["value"] is None
    assert "vs_baseline" not in out


def test_gateway_hop_fields_omitted_never_null(monkeypatch, capsys):
    """serving_gateway_* evidence is omit-on-absence too: a failed hop
    probe must leave NO gateway keys (not null-paired ones) while a
    successful serving phase that happened to null one is scrubbed."""
    rc, out = _run_main(
        monkeypatch,
        capsys,
        {
            "als": ({}, "boom"),
            "serving": (
                {
                    "serving_e2e_p50_ms": 5.0,
                    # simulated mispairing: a null hop next to a real p50
                    "serving_gateway_hop_p50_ms": None,
                },
                None,
            ),
            "twotower": ({}, "boom"),
            "secondary": ({}, "boom"),
        },
    )
    assert out["vs_baseline"] == 0.5  # headline present -> ratio present
    assert "serving_gateway_hop_p50_ms" not in out


def test_cpu_only_skips_probing_entirely(monkeypatch, capsys):
    """--cpu-only skips every device phase with an explicit marker (none
    reruns on the CPU under its device field names), runs the CPU-pinned
    phases, and a run that shipped their numbers is healthy."""
    calls = []

    def fake_run(name, timeout_s, retries=1):
        calls.append(name)
        assert name not in bench._DEVICE_PHASES, f"device phase {name} ran"
        return {f"{name}_stub_ms": 1.0}, None

    monkeypatch.setattr(bench, "_run_phase", fake_run)
    monkeypatch.setattr("sys.argv", ["bench.py", "--cpu-only"])
    rc = bench.main()
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0  # a requested CPU-only run that shipped numbers is healthy
    assert calls == [
        "serving_local", "batchpredict", "evalgrid", "elastic", "roofline",
        "sequential",
    ]
    assert out["bench_cpu_only"] is True
    for name in bench._DEVICE_PHASES:
        assert out[f"{name}_error"] == "skipped: --cpu-only"


def test_device_phase_without_a_chip_fails_the_run(monkeypatch, capsys):
    """No probe, no retry, no CPU rerun: a device phase that finds no
    accelerator refuses, and the run's exit code is non-zero even though
    the CPU-pinned phases shipped numbers."""

    def fake_run(name, timeout_s, retries=1):
        if name in bench._DEVICE_PHASES:
            return {}, bench._NO_ACCELERATOR + "JAX found platform 'cpu'"
        return {f"{name}_stub_ms": 1.0}, None

    monkeypatch.setattr(bench, "_run_phase", fake_run)
    monkeypatch.setattr("sys.argv", ["bench.py"])
    rc = bench.main()
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1
    assert out["als_error"].startswith(bench._NO_ACCELERATOR)
    assert out["serving_local_stub_ms"] == 1.0  # forensics still printed
    assert "ann_platform" not in out and "secondary_platform" not in out


def test_device_phase_refuses_the_cpu():
    """The refusal itself, in-process: conftest pins this run to the CPU."""
    with pytest.raises(SystemExit) as exc:
        bench._jax_setup(device_phase=True)
    assert str(exc.value).startswith(bench._NO_ACCELERATOR)
    assert "'cpu'" in str(exc.value)
    assert bench._jax_setup()[1] == "cpu"  # the CPU-pinned phases' form


def test_phase_als_bf16_extra_datapoint(monkeypatch, tmp_path):
    """The TPU-only bf16-gather extra measurement must not first execute on
    the judge's machine: spoof the platform so the branch runs here (on the
    CPU backend), and assert it ships its own wall/device/rmse fields
    without touching the headline gate fields."""
    monkeypatch.setenv("PIO_BENCH_SCALE", "ml100k")
    monkeypatch.setenv("PIO_BENCH_FACTORS", str(tmp_path / "factors.npz"))
    real_setup = bench._jax_setup

    def spoofed(device_phase=False):
        jax, _ = real_setup()
        return jax, "tpu"

    monkeypatch.setattr(bench, "_jax_setup", spoofed)
    ck = bench._Checkpoint(str(tmp_path / "out.json"))
    bench.phase_als(ck)
    d = ck.data
    assert d["als_rmse_gate_ok"] is True
    assert "als_bf16_error" not in d, d.get("als_bf16_error")
    assert d["als_bf16_wall_s"] > 0 and d["als_bf16_device_s"] > 0
    # the bf16 variant must match f32 quality within bf16 rounding
    assert abs(d["als_bf16_heldout_rmse"] - d["als_heldout_rmse"]) < 0.02


class TestTTLCache:
    def test_caches_within_ttl_and_counts(self):
        from predictionio_tpu.utils.ttl_cache import TTLCache

        c = TTLCache(ttl_s=60)
        calls = []
        assert c.get_or_load("k", lambda: calls.append(1) or "v") == "v"
        assert c.get_or_load("k", lambda: calls.append(1) or "v2") == "v"
        assert len(calls) == 1 and c.hits == 1 and c.misses == 1

    def test_ttl_zero_bypasses(self):
        from predictionio_tpu.utils.ttl_cache import TTLCache

        c = TTLCache(ttl_s=0)
        calls = []
        c.get_or_load("k", lambda: calls.append(1))
        c.get_or_load("k", lambda: calls.append(1))
        assert len(calls) == 2

    def test_expiry(self):
        import time

        from predictionio_tpu.utils.ttl_cache import TTLCache

        c = TTLCache(ttl_s=0.03)
        c.get_or_load("k", lambda: "old")
        time.sleep(0.04)
        assert c.get_or_load("k", lambda: "new") == "new"

    def test_lru_bound(self):
        from predictionio_tpu.utils.ttl_cache import TTLCache

        c = TTLCache(ttl_s=60, maxsize=2)
        for i in range(4):
            c.get_or_load(i, lambda i=i: i)
        assert len(c._entries) == 2

    def test_loader_exception_not_cached(self):
        from predictionio_tpu.utils.ttl_cache import TTLCache

        c = TTLCache(ttl_s=60)
        with pytest.raises(RuntimeError):
            c.get_or_load("k", lambda: (_ for _ in ()).throw(RuntimeError("x")))
        # the failure must not poison the key: next load succeeds and caches
        assert c.get_or_load("k", lambda: "ok") == "ok"
        assert c.get_or_load("k", lambda: "other") == "ok"

    def test_invalidate(self):
        from predictionio_tpu.utils.ttl_cache import TTLCache

        c = TTLCache(ttl_s=60)
        c.get_or_load("k", lambda: "v1")
        c.invalidate("k")
        assert c.get_or_load("k", lambda: "v2") == "v2"


# ---------------------------------------------------------------------------
# --compare: the perf-regression gate (ROADMAP item 5)
# ---------------------------------------------------------------------------


BASE = {
    "value": 10.0,
    "serving_local_e2e_p50_ms": 40.0,
    "serving_local_e2e_p95_ms": 80.0,
    "serving_local_e2e_qps": 500.0,
    "serving_local_phase_dispatch_p95_ms": 20.0,
    "serving_local_phase_fetch_p95_ms": 18.0,
    "serving_local_heldout_rmse": 0.38,  # not a gated field
}


class TestCompareBench:
    def test_unchanged_run_passes(self):
        verdict = bench.compare_bench(dict(BASE), [dict(BASE)])
        assert verdict["compare_ok"] is True
        assert verdict["compare_regressions"] == []
        assert verdict["compare_fields"] == 6

    def test_latency_regression_trips(self):
        cur = {**BASE, "serving_local_e2e_p50_ms": 60.0}  # +50% > 25% tol
        verdict = bench.compare_bench(cur, [dict(BASE)])
        assert verdict["compare_ok"] is False
        [reg] = verdict["compare_regressions"]
        assert reg["field"] == "serving_local_e2e_p50_ms"
        assert reg["ratio"] == 1.5

    def test_throughput_regression_trips(self):
        cur = {**BASE, "serving_local_e2e_qps": 300.0}  # -40%
        verdict = bench.compare_bench(cur, [dict(BASE)])
        assert verdict["compare_ok"] is False
        assert verdict["compare_regressions"][0]["field"] == "serving_local_e2e_qps"

    def test_phase_percentiles_are_gated(self):
        cur = {**BASE, "serving_local_phase_fetch_p95_ms": 30.0}
        verdict = bench.compare_bench(cur, [dict(BASE)])
        assert verdict["compare_ok"] is False
        assert (
            verdict["compare_regressions"][0]["field"]
            == "serving_local_phase_fetch_p95_ms"
        )

    def test_train_step_phases_are_gated(self):
        base = {**BASE, "train_step_sweep_ms": 100.0}
        cur = {**base, "train_step_sweep_ms": 150.0}  # +50% > 25% tol
        verdict = bench.compare_bench(cur, [base])
        assert verdict["compare_ok"] is False
        assert verdict["compare_regressions"][0]["field"] == "train_step_sweep_ms"

    def test_batchpredict_offline_qps_is_gated(self):
        # ISSUE 14: offline throughput regressing silently grows the
        # nightly precompute window
        base = {**BASE, "batchpredict_offline_qps": 10_000.0}
        cur = {**base, "batchpredict_offline_qps": 5_000.0}
        verdict = bench.compare_bench(cur, [base])
        assert verdict["compare_ok"] is False
        assert (
            verdict["compare_regressions"][0]["field"]
            == "batchpredict_offline_qps"
        )

    def test_batchpredict_phase_p50s_are_gated(self):
        base = {**BASE, "batchpredict_phase_dispatch_p50_ms": 4.0}
        cur = {**base, "batchpredict_phase_dispatch_p50_ms": 8.0}
        verdict = bench.compare_bench(cur, [base])
        assert verdict["compare_ok"] is False
        assert (
            verdict["compare_regressions"][0]["field"]
            == "batchpredict_phase_dispatch_p50_ms"
        )

    def test_evalgrid_fields_are_gated(self):
        # ISSUE 15: search throughput, the measured advantage over the
        # sequential MetricEvaluator, and the searched optimum's quality
        # are all higher-is-better gates
        for field in (
            "evalgrid_cells_per_hour",
            "evalgrid_speedup_x",
            "evalgrid_winner_score",
        ):
            base = {**BASE, field: 10.0}
            cur = {**base, field: 5.0}
            verdict = bench.compare_bench(cur, [base])
            assert verdict["compare_ok"] is False, field
            assert verdict["compare_regressions"][0]["field"] == field
        # improvements never trip
        verdict = bench.compare_bench(
            {**BASE, "evalgrid_speedup_x": 20.0},
            [{**BASE, "evalgrid_speedup_x": 10.0}],
        )
        assert verdict["compare_ok"] is True

    def test_batchpredict_users_per_s_is_gated(self):
        base = {**BASE, "batchpredict_offline_users_per_s": 10_000.0}
        cur = {**base, "batchpredict_offline_users_per_s": 2_000.0}
        verdict = bench.compare_bench(cur, [base])
        assert verdict["compare_ok"] is False

    def test_train_memory_peak_is_gated(self):
        base = {**BASE, "train_peak_bytes_per_device": 1_000_000.0}
        cur = {**base, "train_peak_bytes_per_device": 2_000_000.0}
        verdict = bench.compare_bench(cur, [base])
        assert verdict["compare_ok"] is False
        assert (
            verdict["compare_regressions"][0]["field"]
            == "train_peak_bytes_per_device"
        )

    def test_train_device_frac_not_gated(self):
        # the device-time share is recorded evidence, not a gate: on CPU
        # backends it is tiny and ratio-noisy
        base = {**BASE, "train_device_time_frac": 0.5}
        cur = {**base, "train_device_time_frac": 0.1}
        verdict = bench.compare_bench(cur, [base])
        assert verdict["compare_ok"] is True

    def test_sub_millisecond_noise_does_not_trip(self):
        # a 3x ratio on a 0.1ms phase is scheduler jitter, not a regression
        base = {**BASE, "serving_local_phase_serve_p50_ms": 0.1}
        cur = {**base, "serving_local_phase_serve_p50_ms": 0.3}
        verdict = bench.compare_bench(cur, [base])
        assert verdict["compare_ok"] is True

    def test_best_prior_wins_across_rounds(self):
        # round A was slower, round B faster: the gate compares against B
        round_a = {**BASE, "serving_local_e2e_p50_ms": 100.0}
        round_b = dict(BASE)
        cur = {**BASE, "serving_local_e2e_p50_ms": 55.0}
        verdict = bench.compare_bench(cur, [round_a, round_b])
        assert verdict["compare_ok"] is False  # 55 vs best=40 is +37.5%
        assert verdict["compare_regressions"][0]["best_prior"] == 40.0

    def test_improvements_counted(self):
        cur = {**BASE, "serving_local_e2e_p50_ms": 20.0}
        verdict = bench.compare_bench(cur, [dict(BASE)])
        assert verdict["compare_ok"] is True
        assert verdict["compare_improvements"] == 1

    def test_missing_fields_skipped(self):
        verdict = bench.compare_bench(
            {"serving_local_e2e_p50_ms": 40.0}, [{"value": 10.0}]
        )
        assert verdict["compare_ok"] is True
        assert verdict["compare_fields"] == 0

    def test_elastic_trace_fields_are_gated(self):
        """ISSUE 13 acceptance: the elasticity trace's p95 and its
        over-provisioning bound (peak replicas) ride the compare gate."""
        base = {**BASE, "fleet_trace_p95_ms": 40.0, "fleet_peak_replicas": 2}
        cur = {**base, "fleet_trace_p95_ms": 80.0}
        verdict = bench.compare_bench(cur, [base])
        assert verdict["compare_ok"] is False
        assert verdict["compare_regressions"][0]["field"] == "fleet_trace_p95_ms"
        # a greedier policy (more replicas for the same trace) trips too
        cur = {**base, "fleet_peak_replicas": 3}
        verdict = bench.compare_bench(cur, [base])
        assert verdict["compare_ok"] is False
        assert (
            verdict["compare_regressions"][0]["field"] == "fleet_peak_replicas"
        )

    def test_roofline_fields_are_gated(self):
        """ISSUE 18: cost-per-1k and sampler overhead gate lower-is-
        better; arithmetic intensity gates higher-is-better."""
        base = {
            **BASE,
            "roofline_topk_cost_per_1k_usd": 1.0e-7,
            "roofline_topk_ai": 3.4,
            "sampler_overhead_frac": 0.002,
        }
        cur = {**base, "roofline_topk_cost_per_1k_usd": 2.0e-7}
        verdict = bench.compare_bench(cur, [base])
        assert verdict["compare_ok"] is False
        assert (
            verdict["compare_regressions"][0]["field"]
            == "roofline_topk_cost_per_1k_usd"
        )
        # AI dropping = the kernel got more memory-bound: a regression
        cur = {**base, "roofline_topk_ai": 2.0}
        verdict = bench.compare_bench(cur, [base])
        assert verdict["compare_ok"] is False
        assert verdict["compare_regressions"][0]["field"] == "roofline_topk_ai"
        # the sampler getting more expensive trips the always-on budget
        cur = {**base, "sampler_overhead_frac": 0.009}
        verdict = bench.compare_bench(cur, [base])
        assert verdict["compare_ok"] is False
        # string/untyped roofline metadata never gates
        assert bench._compare_direction("roofline_device") == 0

    def test_elastic_zero_shed_prior_is_degenerate_not_tripping(self):
        # a 0-shed prior cannot form a ratio; the e2e/chaos suite owns
        # the zero-shed assertion, the gate owns regressions from >0
        base = {**BASE, "fleet_shed_total": 0.0}
        cur = {**base, "fleet_shed_total": 3.0}
        verdict = bench.compare_bench(cur, [base])
        assert verdict["compare_ok"] is True


def _write_json(tmp_path, name, data):
    p = tmp_path / name
    p.write_text(json.dumps(data))
    return str(p)


class TestCompareCLI:
    def test_pure_compare_mode_passes_unchanged(self, monkeypatch, capsys, tmp_path):
        base = _write_json(tmp_path, "base.json", BASE)
        monkeypatch.setattr(
            "sys.argv", ["bench.py", "--compare", base, "--current", base]
        )
        rc = bench.main()
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 0
        assert out["metric"] == "bench_compare"
        assert out["compare_ok"] is True

    def test_pure_compare_mode_trips_on_regression(
        self, monkeypatch, capsys, tmp_path
    ):
        base = _write_json(tmp_path, "base.json", BASE)
        cur = _write_json(
            tmp_path, "cur.json", {**BASE, "serving_local_e2e_p50_ms": 90.0}
        )
        monkeypatch.setattr(
            "sys.argv", ["bench.py", "--compare", base, "--current", cur]
        )
        rc = bench.main()
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 1
        assert out["compare_ok"] is False
        assert out["compare_regressions"][0]["field"] == "serving_local_e2e_p50_ms"

    def test_tolerance_flag_respected(self, monkeypatch, capsys, tmp_path):
        base = _write_json(tmp_path, "base.json", BASE)
        cur = _write_json(
            tmp_path, "cur.json", {**BASE, "serving_local_e2e_p50_ms": 55.0}
        )
        monkeypatch.setattr(
            "sys.argv",
            ["bench.py", "--compare", base, "--current", cur,
             "--compare-tolerance", "0.5"],
        )
        assert bench.main() == 0  # +37.5% within the 50% tolerance
        capsys.readouterr()

    def test_compare_after_run_records_verdict_in_evidence(
        self, monkeypatch, capsys, tmp_path
    ):
        """A full bench run with --compare writes the verdict INTO the
        evidence line and fails the run on regression."""
        prior = _write_json(
            tmp_path, "prior.json", {**BASE, "serving_e2e_p50_ms": 5.0}
        )

        def fake_run(name, timeout_s, retries=1):
            if name == "serving":
                return {"serving_e2e_p50_ms": 9.0, "serving_e2e_qps": 100.0}, None
            return {}, None

        monkeypatch.setattr(bench, "_run_phase", fake_run)
        monkeypatch.setattr("sys.argv", ["bench.py", "--compare", prior])
        rc = bench.main()
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 1  # 9ms vs 5ms prior p50 = +80%
        assert out["compare_ok"] is False
        assert out["compare_baselines"] == [prior]
        assert any(
            r["field"] == "serving_e2e_p50_ms" for r in out["compare_regressions"]
        )

    def test_checked_in_baseline_fixture_is_loadable_and_self_consistent(self):
        import os

        fixture = os.path.join(
            os.path.dirname(__file__), "fixtures", "bench_baseline.json"
        )
        base = bench._load_bench_json(fixture)
        # the fixture must exercise the gate's main surfaces: e2e + phases
        assert "serving_local_e2e_p50_ms" in base
        assert any(k.startswith("serving_local_phase_") for k in base)
        verdict = bench.compare_bench(base, [base])
        assert verdict["compare_ok"] is True and verdict["compare_fields"] > 10

    def test_current_without_compare_errors(self, monkeypatch, capsys):
        monkeypatch.setattr("sys.argv", ["bench.py", "--current", "x.json"])
        with pytest.raises(SystemExit):
            bench.main()
        capsys.readouterr()
