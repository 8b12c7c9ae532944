"""Tests for tools/kernel_bench.py, the fused-engine sweep."""

import json


class TestKernelBench:
    def test_quick_bench_reports_fused_contract(self, tmp_path):
        """Tier-1 smoke of tools/kernel_bench.py (ISSUE 10): the
        --quick sweep runs all three engines at the smallest width,
        asserts the ulp/tolerance equivalence block, and
        counts fused rounds through the obs registry."""
        import tools.kernel_bench as kb

        out = str(tmp_path / "BENCH_quick.json")
        report = kb.run(out, quick=True)
        assert report["ok"]
        with open(out) as fh:
            on_disk = json.load(fh)
        assert on_disk["headline_source"] == "tpudas.obs.registry"
        point = report["sweep"][0]
        assert set(point["engines"]) == {
            "cascade", "fused-xla", "fused-pallas"
        }
        fx = point["engines"]["fused-xla"]
        assert fx["fused_rounds"] > 0  # registry witnessed the path
        assert fx["intermediate_bytes_saved_per_round"] > 0
        eq = report["acceptance"]["equivalence"]
        assert eq["fused_xla_max_ulps"] <= eq["fused_xla_ulps_pinned"]
        assert isinstance(eq["fused_xla_output_byte_identical"], bool)
        assert (
            eq["fused_pallas_rel_err"]
            <= eq["fused_pallas_tolerance_pinned"]
        )
