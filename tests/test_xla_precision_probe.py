"""tools/xla_precision_probe.py runs end to end (CPU, small shape)."""


def test_probe_rows_and_restores_precision():
    from tools import xla_precision_probe as xp
    from tpudas.ops import fir

    kept = fir._XLA_STAGE_PRECISION
    rows = xp.probe(T=80000, C=16, C_err=8)
    assert fir._XLA_STAGE_PRECISION == kept
    stages = [r for r in rows if r["stage"] != "chain"]
    assert len(stages) == 4 * len(xp.PRECISIONS)
    assert len(rows) == 5 * len(xp.PRECISIONS)
    # the CPU backend computes f32 at every precision
    assert max(r["chain_rel_err"] for r in stages) < 1e-5
