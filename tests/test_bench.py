"""bench.py measurement logic, run in-process on the CPU backend with
tiny shapes: the JSON contract must stay parseable and honest
(requested-but-skipped compares recorded, engines map when budget
allows, a Pallas failure raises)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def _run_child(monkeypatch, capsys, **env):
    defaults = {
        "BENCH_T": "4096",
        "BENCH_C": "32",
        "BENCH_ITERS": "2",
        "BENCH_ENGINE": "cascade",
    }
    defaults.update(env)
    for k, v in defaults.items():
        monkeypatch.setenv(k, str(v))
    if defaults.get("BENCH_MODE") == "e2e":
        result = bench.measure_e2e()
    else:
        result = bench.measure_kernel()
    # the printed line is json.dumps of this dict: keep it serializable
    return json.loads(json.dumps(result))


class TestCompareVisibility:
    def test_budget_skipped_compare_is_recorded(self, monkeypatch, capsys):
        """BENCH_COMPARE=1 with no budget left must say so in the JSON,
        not silently omit the engines map (round-2 advisor finding)."""
        result = _run_child(
            monkeypatch, capsys, BENCH_COMPARE="1", BENCH_REMAINING="0"
        )
        assert "engines" not in result
        assert "budget" in result["engines_skipped"]

    def test_h2d_skipped_compare_is_recorded(self, monkeypatch, capsys):
        result = _run_child(
            monkeypatch,
            capsys,
            BENCH_COMPARE="1",
            BENCH_INCLUDE_H2D="1",
            BENCH_REMAINING="100000",
        )
        assert "engines" not in result
        assert "h2d" in result["engines_skipped"]

    def test_compare_runs_all_engines_when_budget_allows(
        self, monkeypatch, capsys
    ):
        result = _run_child(
            monkeypatch, capsys, BENCH_COMPARE="1", BENCH_REMAINING="100000"
        )
        engines = result["engines"]
        assert set(engines) == {"cascade-xla", "cascade-pallas", "fft"}
        for name, value in engines.items():
            assert isinstance(value, (int, float)), (name, value)
        assert "engines_skipped" not in result

    def test_stage_profile_breakdown(self, monkeypatch, capsys):
        result = _run_child(
            monkeypatch, capsys, BENCH_PROFILE="1", BENCH_T="30000",
            BENCH_C="16",
        )
        stages = result["stage_times_ms"]
        assert len(stages) == len(result["stages"])
        for eng, t_in, ms in stages:
            assert eng in ("pallas", "xla")
            assert isinstance(ms, float) and ms > 0, (eng, t_in, ms)
        # input sizes shrink monotonically through the cascade
        sizes = [t_in for _, t_in, _ in stages]
        assert sizes == sorted(sizes, reverse=True)

    def test_no_compare_no_keys(self, monkeypatch, capsys):
        result = _run_child(monkeypatch, capsys, BENCH_COMPARE="0")
        assert "engines" not in result
        assert "engines_skipped" not in result
        assert result["value"] > 0
        assert result["metric"] == "channel_samples_per_sec"

    def test_quantized_kernel_measured(self, monkeypatch, capsys):
        """BENCH_QUANT=1 records the raw-int16-payload kernel rate
        beside the f32 headline (the realistic interrogator payload)."""
        result = _run_child(
            monkeypatch, capsys, BENCH_QUANT="1", BENCH_REMAINING="100000"
        )
        sub = result["int16"]
        assert sub["value"] > 0
        assert sub["realtime_factor"] > 0
        assert "hbm_gbps" in sub

    def test_quantized_kernel_budget_skip_recorded(
        self, monkeypatch, capsys
    ):
        result = _run_child(
            monkeypatch, capsys, BENCH_QUANT="1", BENCH_COMPARE="0",
            BENCH_REMAINING="0",
        )
        assert "int16" not in result
        assert "budget" in result["int16_skipped"]

    def test_clean_pallas_run_reports_impl_v2(self, monkeypatch, capsys):
        """A clean Pallas headline carries the explicit implementation
        verdict (pallas_impl: v2, no pallas_error), readable from the
        artifact alone."""
        import tpudas.ops.fir as fir_mod

        monkeypatch.delenv("TPUDAS_PALLAS_IMPL", raising=False)
        fir_mod._layout_for.cache_clear()
        fir_mod._clear_cascade_caches()
        monkeypatch.setattr(
            fir_mod, "_pallas_stage_ok",
            lambda k, R, n_ch, B: k >= 3000 and B <= 128,
        )
        try:
            result = _run_child(
                monkeypatch, capsys, BENCH_PALLAS="1", BENCH_COMPARE="0",
                BENCH_QUANT="0",
            )
        finally:
            fir_mod._layout_for.cache_clear()
            fir_mod._clear_cascade_caches()
        assert result["value"] > 0
        assert result["pallas_impl"] == "v2"
        assert result["device"]["platform"] == "cpu"

    def test_pallas_failure_raises(self, monkeypatch, capsys):
        """A Pallas kernel failure is not absorbed into an XLA (or v1)
        headline: the measurement raises."""
        import tpudas.ops.fir as fir_mod
        import tpudas.ops.pallas_fir as pf_mod

        def boom(*a, **k):
            raise RuntimeError("v2 body rejected (synthetic)")

        fir_mod._clear_cascade_caches()
        monkeypatch.setattr(
            fir_mod, "_pallas_stage_ok",
            lambda k, R, n_ch, B: k >= 3000 and B <= 128,
        )
        monkeypatch.setattr(pf_mod, "_kernel_body", boom)
        try:
            with pytest.raises(RuntimeError, match="v2 body rejected"):
                _run_child(
                    monkeypatch, capsys, BENCH_PALLAS="1",
                    BENCH_COMPARE="0", BENCH_QUANT="0",
                )
        finally:
            fir_mod._clear_cascade_caches()

    def test_caught_phase_failure_is_listed(self, monkeypatch, capsys):
        """An optional phase whose failure is caught is named under
        failed_phases, which makes ``python bench.py`` exit 1."""
        real = bench._build_cascade_step

        def flaky(*a, quantized=False, **k):
            if quantized:
                raise RuntimeError("int16 phase failure (synthetic)")
            return real(*a, **k)

        monkeypatch.setattr(bench, "_build_cascade_step", flaky)
        result = _run_child(
            monkeypatch, capsys, BENCH_QUANT="1", BENCH_COMPARE="0",
            BENCH_REMAINING="100000",
        )
        assert "int16 phase failure" in result["int16"]["error"]
        assert result["failed_phases"] == ["int16"]


class TestE2EChild:
    def test_int16_payload_e2e(self, monkeypatch, capsys):
        """BENCH_E2E_DTYPE=int16 runs the quantized product path:
        raw native assembly + device decode, recorded in the JSON."""
        result = _run_child(
            monkeypatch,
            capsys,
            BENCH_MODE="e2e",
            BENCH_E2E_DTYPE="int16",
            BENCH_E2E_SEC="30",
            BENCH_C="16",
            BENCH_E2E_FS="200",
        )
        assert result["mode"] == "e2e"
        assert result["payload"] == "int16"
        assert result["native_windows"] >= 1
        assert result["realtime_factor"] > 0


class TestMainFlow:
    def test_kernel_line_carries_e2e_subobject(self):
        """One `python bench.py` run, one process, records BOTH the
        resident-kernel number and the full product-path (e2e)
        real-time factor, with the device it ran on, and exits 0."""
        import subprocess

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.update(
            BENCH_T="16384",
            BENCH_C="32",
            BENCH_ITERS="2",
            BENCH_E2E_SEC="30",
            JAX_ENABLE_COMPILATION_CACHE="false",
        )
        proc = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(bench.__file__),
                                          "bench.py")],
            env=env,
            capture_output=True,
            text=True,
            timeout=280,
        )
        lines = [
            ln for ln in proc.stdout.splitlines() if ln.startswith("{")
        ]
        assert proc.returncode == 0 and lines, proc.stderr[-500:]
        result = json.loads(lines[-1])
        assert result["value"] > 0
        assert result["stages"]  # layout ground truth present
        assert result["device"]["platform"] == "cpu"
        assert "failed_phases" not in result
        e2e = result["e2e"]
        assert e2e["mode"] == "e2e"
        assert e2e["realtime_factor"] > 0
        assert e2e["native_windows"] >= 1
        assert sum(e2e["engine_counts"].values()) >= 1

    def test_unknown_device_kind_is_an_error(self):
        from types import SimpleNamespace

        assert bench.device_peaks(
            SimpleNamespace(platform="cpu", device_kind="cpu")
        ) is None
        assert bench.device_peaks(
            SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        )["hbm"] == 819e9
        with pytest.raises(ValueError, match="no published peaks"):
            bench.device_peaks(
                SimpleNamespace(platform="tpu", device_kind="TPU v9")
            )


class TestMeshBench:
    def test_sharded_kernel_step(self, monkeypatch, capsys):
        """BENCH_MESH runs the cascade over a (time, ch) mesh — the
        sharded product step is benchable."""
        result = _run_child(
            monkeypatch,
            capsys,
            BENCH_MESH="8",
            BENCH_TIME_SHARDS="2",
            BENCH_T="66000",  # n_loc=33 -> halo (~27k rows) < t_local
            BENCH_C="32",
        )
        assert result["mesh"] == {"time": 2, "ch": 4}
        assert result["value"] > 0

    def test_channel_only_mesh(self, monkeypatch, capsys):
        result = _run_child(
            monkeypatch, capsys, BENCH_MESH="8", BENCH_T="8000", BENCH_C="16"
        )
        assert result["mesh"] == {"time": 1, "ch": 8}
        assert result["value"] > 0

    def test_channel_only_mesh_pads_uneven_c(self, monkeypatch, capsys):
        # C=12 on an 8-way ch axis: the pad-and-trim wrapper must fire
        result = _run_child(
            monkeypatch, capsys, BENCH_MESH="8", BENCH_T="8000", BENCH_C="12"
        )
        assert result["mesh"] == {"time": 1, "ch": 8}
        assert result["value"] > 0

    def test_non_cascade_engine_reports_no_mesh(self, monkeypatch, capsys):
        result = _run_child(
            monkeypatch, capsys, BENCH_MESH="8", BENCH_ENGINE="fft",
            BENCH_T="8000", BENCH_C="16",
        )
        assert "mesh" not in result  # it did not run sharded
