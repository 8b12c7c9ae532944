"""Sharded execution on the 8-device CPU mesh: channel sharding,
time-shard halo exchange, batched data parallelism — all must agree
with the single-device kernels."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudas.ops.filter import fft_pass_filter
from tpudas.ops.rolling import rolling_reduce
from tpudas.parallel.batch import batched_rolling_mean
from tpudas.parallel.mesh import make_mesh
from tpudas.parallel.pipeline import sharded_lowpass_decimate
from tpudas.parallel.sharding import shard_channels


@pytest.fixture(scope="module", autouse=True)
def require_8_devices():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")


def _assert_ulp_close(got, ref):
    """Time-sharded and window-batched cascades trace a different
    (batched or sharded) shape than the single-device run, and the
    installed XLA CPU backend may then order the stage dot's sums
    differently: agreement is a few f32 ulps of the output scale, not
    bit equality."""
    from tpudas.testing import max_ulps

    assert max_ulps(got, ref) <= 4


def _signal(T, C, fs, seed=0):
    rng = np.random.default_rng(seed)
    t = np.arange(T) / fs
    lf = np.sin(2 * np.pi * 0.05 * t)[:, None] * (1 + np.arange(C))[None, :]
    return (lf + 0.3 * rng.standard_normal((T, C))).astype(np.float32)


class TestMesh:
    def test_make_mesh_shapes(self):
        m = make_mesh(8, time_shards=2)
        assert m.shape["time"] == 2 and m.shape["ch"] == 4
        m1 = make_mesh(8)
        assert m1.shape["time"] == 1 and m1.shape["ch"] == 8

    def test_bad_factorization(self):
        with pytest.raises(ValueError):
            make_mesh(8, time_shards=3)


class TestChannelSharding:
    def test_zero_comm_filter_matches_single_device(self):
        fs = 100.0
        data = _signal(3000, 16, fs)
        ref = np.asarray(fft_pass_filter(data, 1 / fs, high=2.0))
        mesh = make_mesh(8)
        sharded = shard_channels(jnp.asarray(data), mesh)
        out = fft_pass_filter(sharded, 1 / fs, high=2.0)
        assert np.allclose(np.asarray(out), ref, atol=1e-4)


class TestShardedPipeline:
    fs = 100.0

    def _reference(self, data, corner, ratio, halo):
        """Single-device equivalent: zero-pad halo at the stream ends
        (matching the boundary shards' ppermute zeros), filter, trim,
        stride."""
        T = data.shape[0]
        padded = np.concatenate(
            [
                np.zeros((halo,) + data.shape[1:], data.dtype),
                data,
                np.zeros((halo,) + data.shape[1:], data.dtype),
            ]
        )
        filt = np.asarray(fft_pass_filter(padded, 1 / self.fs, high=corner))
        return filt[halo : halo + T : ratio]

    @pytest.mark.parametrize("time_shards", [1, 2, 4])
    @pytest.mark.slow
    def test_matches_interior_of_unsharded(self, time_shards):
        T, C, ratio, halo = 4000, 16, 10, 200
        data = _signal(T, C, self.fs, seed=1)
        corner = 2.0
        mesh = make_mesh(8, time_shards=time_shards)
        out = np.asarray(
            sharded_lowpass_decimate(
                mesh, data, 1 / self.fs, corner, ratio, halo
            )
        )
        assert out.shape == (T // ratio, C)
        ref = np.asarray(fft_pass_filter(data, 1 / self.fs, high=corner))[::ratio]
        # interior: away from every shard seam by > halo output samples
        # the halo is sized so seams are exact within filter leakage
        interior = slice(halo // ratio + 1, -(halo // ratio + 1))
        scale = np.abs(ref).max()
        assert (
            np.abs(out[interior] - ref[interior]).max() < 5e-3 * scale
        )

    def test_shard_seams_are_clean(self):
        """The samples at shard boundaries must not show discontinuities
        larger than the filter's leakage tolerance."""
        T, C, ratio, halo = 4000, 8, 10, 250
        data = _signal(T, C, self.fs, seed=2)
        mesh = make_mesh(8, time_shards=4)
        out = np.asarray(
            sharded_lowpass_decimate(mesh, data, 1 / self.fs, 2.0, ratio, halo)
        )
        ref = self._reference(data, 2.0, ratio, halo)
        # compare *everywhere* against the zero-padded single-device
        # reference, including across seams
        scale = np.abs(ref).max()
        assert np.abs(out - ref).max() < 5e-3 * scale

    def test_alignment_validation(self):
        mesh = make_mesh(8, time_shards=2)
        data = np.zeros((4001, 16), np.float32)
        with pytest.raises(ValueError, match="divisible"):
            sharded_lowpass_decimate(mesh, data, 0.01, 2.0, 10, 100)


class TestBatchedRolling:
    def test_matches_per_patch_kernel(self):
        B, T, C, w, s = 8, 500, 4, 50, 50
        rng = np.random.default_rng(3)
        batch = rng.standard_normal((B, T, C)).astype(np.float32)
        mesh = make_mesh(8)
        out = np.asarray(batched_rolling_mean(mesh, batch, w, s))
        for b in range(B):
            ref = np.asarray(rolling_reduce(batch[b], w, s, "mean"))
            assert np.allclose(out[b], ref, atol=1e-5, equal_nan=True)


class TestShardedCascade:
    """sharded_cascade_decimate must be bit-equal to the single-device
    cascade — the halo exchange and shard grid are layout, not math."""

    def _plan(self, fs=100.0, ratio=20):
        from tpudas.ops.fir import design_cascade

        return design_cascade(fs, ratio, 0.45, 4)

    @pytest.mark.parametrize("time_shards", [1, 2, 4])
    @pytest.mark.slow
    def test_bit_equal_to_single_device(self, time_shards):
        from tpudas.ops.fir import cascade_decimate
        from tpudas.parallel.pipeline import sharded_cascade_decimate

        plan = self._plan()
        mesh = make_mesh(8, time_shards=time_shards)
        T, C = 12000, 12  # C=12 not divisible by ch shards: pad path
        x = _signal(T, C, 100.0, seed=3)
        phase, n_out = 200, 110
        ref = np.asarray(cascade_decimate(x, plan, phase, n_out, "xla"))
        out = sharded_cascade_decimate(mesh, x, plan, phase, n_out)
        assert out is not None
        assert np.array_equal(np.asarray(out), ref)

    def test_unfit_layout_returns_none(self):
        from tpudas.parallel.pipeline import sharded_cascade_decimate

        plan = self._plan()
        mesh = make_mesh(8, time_shards=8)
        # tiny window: local blocks far smaller than the filter halo
        x = _signal(600, 4, 100.0)
        assert sharded_cascade_decimate(mesh, x, plan, 10, 8) is None

    @pytest.mark.slow
    def test_window_dp_matches_per_window(self):
        """batched_cascade_decimate (window DP + channel sharding) ==
        stacked per-window cascade_decimate, bit for bit."""
        from tpudas.ops.fir import cascade_decimate
        from tpudas.parallel.batch import batched_cascade_decimate

        plan = self._plan()
        mesh = make_mesh(8, time_shards=2)  # (time=2 -> DP axis, ch=4)
        rng = np.random.default_rng(9)
        W, T, C = 3, 9000, 6  # W not divisible by dp, C not by ch
        stack = rng.standard_normal((W, T, C)).astype(np.float32)
        phase, n_out = 150, 80
        out = np.asarray(
            batched_cascade_decimate(mesh, stack, plan, phase, n_out)
        )
        assert out.shape == (W, n_out, C)
        for wdx in range(W):
            ref = np.asarray(
                cascade_decimate(stack[wdx], plan, phase, n_out, "xla")
            )
            assert np.array_equal(out[wdx], ref), wdx

    def test_lfproc_window_dp_byte_equal(self, tmp_path):
        """LFProc with window_dp batches steady-state windows over the
        mesh "time" axis and agrees with the single-device serial run
        to a few ulps (the batched shape may sum in another order)."""
        from tpudas import spool
        from tpudas.proc.lfproc import LFProc
        from tpudas.testing import make_synthetic_spool
        from tpudas.utils.logging import set_log_handler

        d = tmp_path / "raw"
        make_synthetic_spool(
            d, n_files=6, file_duration=30.0, fs=100.0, n_ch=6, noise=0.01
        )
        t0 = np.datetime64("2023-03-22T00:00:00")
        t1 = np.datetime64("2023-03-22T00:03:00")
        events = []
        set_log_handler(events.append)
        try:
            results = {}
            for label, mesh, dp in (
                ("serial", None, False),
                ("dp", make_mesh(8, time_shards=2), True),
            ):
                lfp = LFProc(spool(str(d)).sort("time").update(), mesh=mesh)
                lfp.update_processing_parameter(
                    output_sample_interval=1.0,
                    process_patch_size=60,
                    edge_buff_size=10,
                    window_dp=dp,
                )
                out = tmp_path / f"out_{label}"
                lfp.set_output_folder(str(out), delete_existing=True)
                lfp.process_time_range(t0, t1)
                results[label] = (
                    spool(str(out)).update().chunk(time=None)[0].host_data()
                )
                if dp:
                    assert sum(lfp.engine_counts.values()) == 4
        finally:
            set_log_handler(None)
        batches = [e for e in events if e["event"] == "window_dp_batch"]
        assert batches, "no DP batch actually ran"
        assert sum(e["windows"] for e in batches) >= 2
        _assert_ulp_close(results["dp"], results["serial"])

    @staticmethod
    def _run_dp(tmp_path, n_files=6):
        from tpudas import spool
        from tpudas.proc.lfproc import LFProc
        from tpudas.testing import make_synthetic_spool

        d = tmp_path / "raw"
        make_synthetic_spool(
            d, n_files=n_files, file_duration=30.0, fs=100.0, n_ch=6,
            noise=0.01,
        )
        lfp = LFProc(
            spool(str(d)).sort("time").update(),
            mesh=make_mesh(8, time_shards=2),
        )
        lfp.update_processing_parameter(
            output_sample_interval=1.0,
            process_patch_size=60,
            edge_buff_size=10,
            window_dp=True,
        )
        lfp.set_output_folder(str(tmp_path / "out"), delete_existing=True)
        lfp.process_time_range(
            np.datetime64("2023-03-22T00:00:00"),
            np.datetime64("2023-03-22T00:03:00"),
        )
        return lfp

    def test_lfproc_window_dp_failure_raises(self, tmp_path, monkeypatch):
        """A window-DP batch-compute failure raises out of the run: it
        is not absorbed by re-running the batch per window."""
        import tpudas.parallel.batch as batch_mod

        def boom(*a, **k):
            raise RuntimeError("batch compute failure (synthetic)")

        monkeypatch.setattr(batch_mod, "batched_cascade_decimate", boom)
        with pytest.raises(RuntimeError, match="batch compute failure"):
            self._run_dp(tmp_path)

    def test_lfproc_window_dp_crosscheck_failure_raises(
        self, tmp_path, monkeypatch
    ):
        """A batched Pallas lowering that RETURNS wrong numbers fails
        the first-batch cross-check against the XLA formulation, and
        the run raises."""
        import tpudas.ops.fir as fir_mod
        import tpudas.ops.pallas_fir as pf_mod
        from tpudas.proc.lfproc import PallasVerificationError

        real = pf_mod.fir_decimate_pallas

        def corrupt(x, hb, R, n_out, **kw):
            return real(x, hb, R, n_out=n_out, **kw) * 1.7

        fir_mod._clear_cascade_caches()
        monkeypatch.setattr(
            fir_mod, "resolve_cascade_engine",
            lambda e="auto": "pallas" if e == "auto" else e,
        )
        monkeypatch.setattr(fir_mod, "_pallas_stage_ok", lambda *a: True)
        monkeypatch.setattr(pf_mod, "fir_decimate_pallas", corrupt)
        try:
            with pytest.raises(PallasVerificationError,
                               match="pallas-vs-xla rel err"):
                self._run_dp(tmp_path)
        finally:
            fir_mod._clear_cascade_caches()

    def test_window_dp_custom_single_axis_mesh(self):
        """A 1-axis DP mesh (no channel axis) leaves channels
        unsharded instead of crashing on the spec."""
        import jax
        from jax.sharding import Mesh

        from tpudas.ops.fir import cascade_decimate
        from tpudas.parallel.batch import batched_cascade_decimate

        plan = self._plan()
        mesh = Mesh(np.array(jax.devices()[:4]), ("win",))
        rng = np.random.default_rng(11)
        stack = rng.standard_normal((4, 9000, 6)).astype(np.float32)
        out = np.asarray(
            batched_cascade_decimate(
                mesh, stack, plan, 150, 80, batch_axis="win"
            )
        )
        ref = np.asarray(cascade_decimate(stack[2], plan, 150, 80, "xla"))
        assert np.array_equal(out[2], ref)

    @pytest.mark.slow
    def test_window_dp_quantized(self):
        from tpudas.ops.fir import cascade_decimate
        from tpudas.parallel.batch import batched_cascade_decimate

        plan = self._plan()
        mesh = make_mesh(8, time_shards=4)
        rng = np.random.default_rng(10)
        q = rng.integers(-3000, 3000, size=(4, 9000, 8)).astype(np.int16)
        s = 1e-3
        out = np.asarray(
            batched_cascade_decimate(mesh, q, plan, 150, 80, qscale=s)
        )
        for wdx in range(4):
            ref = np.asarray(
                cascade_decimate(q[wdx], plan, 150, 80, "xla", qscale=s)
            )
            assert np.array_equal(out[wdx], ref), wdx

    def test_quantized_bit_equal_to_single_device(self):
        """Raw int16 windows shard undecoded (half the ICI halo bytes);
        the result matches the single-device quantized cascade to a
        few ulps."""
        from tpudas.ops.fir import cascade_decimate
        from tpudas.parallel.pipeline import sharded_cascade_decimate

        plan = self._plan()
        mesh = make_mesh(8, time_shards=2)
        rng = np.random.default_rng(7)
        q = rng.integers(-3000, 3000, size=(12000, 12)).astype(np.int16)
        s = 1e-3
        phase, n_out = 200, 110
        ref = np.asarray(
            cascade_decimate(q, plan, phase, n_out, "xla", qscale=s)
        )
        out = sharded_cascade_decimate(
            mesh, q, plan, phase, n_out, qscale=s
        )
        assert out is not None
        _assert_ulp_close(np.asarray(out), ref)


class TestLFProcMesh:
    """The product engine runs mesh-sharded end to end: output files
    must be byte-identical to the single-device run."""

    def _run(self, src, out_dir, mesh, engine="auto"):
        from tpudas import spool
        from tpudas.proc.lfproc import LFProc

        lfp = LFProc(spool(str(src)).sort("time").update(), mesh=mesh)
        lfp.update_processing_parameter(
            output_sample_interval=1.0,
            process_patch_size=60,
            edge_buff_size=10,
            engine=engine,
        )
        lfp.set_output_folder(str(out_dir), delete_existing=True)
        lfp.process_time_range(
            np.datetime64("2023-03-22T00:00:00"),
            np.datetime64("2023-03-22T00:03:00"),
        )
        return lfp

    @pytest.fixture(scope="class")
    def src(self, tmp_path_factory):
        from tpudas.testing import make_synthetic_spool

        d = tmp_path_factory.mktemp("mesh_raw")
        make_synthetic_spool(
            d, n_files=6, file_duration=30.0, fs=100.0, n_ch=12, noise=0.01
        )
        return d

    @pytest.mark.parametrize(
        "time_shards,engine",
        [(1, "auto"), (2, "auto"), (4, "auto"), (1, "fft"), (2, "fft")],
    )
    @pytest.mark.slow
    def test_sharded_files_byte_identical(
        self, src, tmp_path, time_shards, engine
    ):
        from tpudas import spool

        single = self._run(src, tmp_path / "single", None, engine)
        mesh = make_mesh(8, time_shards=time_shards)
        sharded = self._run(src, tmp_path / "sharded", mesh, engine)
        a = spool(str(tmp_path / "single")).update().chunk(time=None)[0]
        b = spool(str(tmp_path / "sharded")).update().chunk(time=None)[0]
        assert np.array_equal(a.host_data(), b.host_data())
        assert np.array_equal(a.coords["time"], b.coords["time"])
        # same engines fired, just sharded
        assert sharded.engine_counts == single.engine_counts

    def test_streaming_driver_takes_mesh(self, src, tmp_path):
        from tpudas import spool
        from tpudas.proc.streaming import run_lowpass_realtime

        mesh = make_mesh(8, time_shards=2)
        out = tmp_path / "rt_out"
        rounds = run_lowpass_realtime(
            str(src),
            str(out),
            "2023-03-22T00:00:00",
            output_sample_interval=1.0,
            edge_buffer=10.0,
            process_patch_size=60,
            poll_interval=0.0,
            sleep_fn=lambda s: None,
            max_rounds=3,
            mesh=mesh,
        )
        assert rounds >= 1
        merged = spool(str(out)).update().chunk(time=None)
        assert len(merged) == 1  # seam-free under the mesh

    def test_mesh_without_ch_axis_rejected(self):
        from jax.sharding import Mesh

        from tpudas.proc.lfproc import LFProc

        bad = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("a", "b"))
        with pytest.raises(ValueError, match="'ch' axis"):
            LFProc(None, mesh=bad)


class TestRollingRealtimeMesh:
    def test_mesh_batched_equals_per_patch(self, tmp_path):
        """run_rolling_realtime(mesh=...) batches fresh patches over
        the mesh and must write byte-identical outputs to the
        per-patch path (DP over patches in the PRODUCT driver)."""
        from tpudas import spool
        from tpudas.core.units import s as sec
        from tpudas.proc.streaming import run_rolling_realtime
        from tpudas.testing import make_synthetic_spool

        src = tmp_path / "raw"
        make_synthetic_spool(
            src, n_files=5, file_duration=30.0, fs=100.0, n_ch=12,
            noise=0.05,
        )
        results = {}
        for label, mesh in (("plain", None), ("mesh", make_mesh(8))):
            out = tmp_path / f"out_{label}"
            rounds = run_rolling_realtime(
                str(src),
                str(out),
                window=1.0 * sec,
                step=1.0 * sec,
                scale=2.0,
                poll_interval=0.0,
                sleep_fn=lambda s: None,
                max_rounds=2,
                mesh=mesh,
            )
            assert rounds >= 1
            merged = spool(str(out)).sort("time").update().chunk(time=None)
            results[label] = [p.host_data() for p in merged]
        assert len(results["plain"]) == len(results["mesh"])
        for a, b in zip(results["plain"], results["mesh"]):
            assert np.array_equal(a, b, equal_nan=True)

    def test_non_uniform_batch_falls_back(self, tmp_path):
        # mixed channel counts cannot stack: the driver must fall back
        # to the per-patch path, not crash or drop patches
        from tpudas import spool
        from tpudas.core.units import s as sec
        from tpudas.proc.streaming import run_rolling_realtime
        from tpudas.testing import make_synthetic_spool

        src = tmp_path / "raw"
        make_synthetic_spool(
            src, n_files=2, file_duration=30.0, fs=100.0, n_ch=12
        )
        make_synthetic_spool(
            src, n_files=1, file_duration=30.0, fs=100.0, n_ch=8,
            start="2023-03-22T00:01:00", prefix="other",
        )
        out = tmp_path / "out"
        rounds = run_rolling_realtime(
            str(src), str(out), window=1.0 * sec, step=1.0 * sec,
            poll_interval=0.0, sleep_fn=lambda s: None, max_rounds=2,
            mesh=make_mesh(8),
        )
        assert rounds >= 1
        assert len(spool(str(out)).update()) == 3  # every patch written


# ---------------------------------------------------------------------------
# ISSUE 7: mesh-sharded realtime streaming


class TestHaloExchange:
    """Direct unit tests for tpudas.parallel.halo: the ppermute
    exchange against a host-padded reference, and the tap-derived halo
    width math."""

    def test_exchange_matches_padded_reference(self):
        from jax.sharding import PartitionSpec as P

        from tpudas.parallel.halo import exchange_halo_time

        mesh = make_mesh(8, time_shards=4)
        T, C, halo = 64, 4, 5
        x = np.arange(T * C, dtype=np.float32).reshape(T, C)
        fn = jax.shard_map(
            lambda b: exchange_halo_time(b, halo, n_shards=4),
            mesh=mesh,
            in_specs=P("time", "ch"),
            out_specs=P("time", "ch"),
            check_vma=False,
        )
        out = np.asarray(jax.jit(fn)(x))
        # reference: zero-pad the stream ends, then each shard's
        # extended block is a [T_loc + 2*halo] slice of the padded
        # stream — boundary shards see zeros, interior shards see
        # their neighbors' rows
        t_loc = T // 4
        padded = np.concatenate(
            [np.zeros((halo, C), np.float32), x,
             np.zeros((halo, C), np.float32)]
        )
        ref = np.concatenate(
            [padded[i * t_loc : i * t_loc + t_loc + 2 * halo]
             for i in range(4)]
        )
        assert np.array_equal(out, ref)

    def test_one_sided_exchange_halves_the_extension(self):
        from jax.sharding import PartitionSpec as P

        from tpudas.parallel.halo import exchange_halo_time

        mesh = make_mesh(8, time_shards=4)
        T, C, halo = 64, 2, 4
        x = np.arange(T * C, dtype=np.float32).reshape(T, C)
        fn = jax.shard_map(
            lambda b: exchange_halo_time(
                b, halo, n_shards=4, left=False
            ),
            mesh=mesh,
            in_specs=P("time", "ch"),
            out_specs=P("time", "ch"),
            check_vma=False,
        )
        out = np.asarray(jax.jit(fn)(x))
        t_loc = T // 4
        padded = np.concatenate([x, np.zeros((halo, C), np.float32)])
        ref = np.concatenate(
            [padded[i * t_loc : i * t_loc + t_loc + halo]
             for i in range(4)]
        )
        assert np.array_equal(out, ref)

    def test_halo_wider_than_shard_rejected(self):
        from tpudas.parallel.halo import exchange_halo_time

        with pytest.raises(ValueError, match="halo"):
            exchange_halo_time(jnp.zeros((8, 2)), 9, n_shards=2)

    def test_fir_halo_rows_from_taps(self):
        """fir_halo_rows == the cascade's exact look-ahead need: the
        telescoped (k + B - 1) * R input consumption minus the shard's
        own rows — and it matches the layout the sharded executor
        computes."""
        from tpudas.ops.fir import chain_layout, design_cascade
        from tpudas.parallel.halo import fir_halo_rows
        from tpudas.parallel.pipeline import sharded_cascade_layout

        plan = design_cascade(100.0, 20, 0.45, 4)
        for n_loc in (8, 55, 110):
            halo = fir_halo_rows(plan, n_loc)
            _, rows = chain_layout(plan, n_loc, 1, "auto")
            assert halo == rows - n_loc * plan.ratio
            assert halo > 0  # a causal FIR cascade always looks ahead
        mesh = make_mesh(8, time_shards=2)
        layout = sharded_cascade_layout(mesh, plan, 200, 110, 12000)
        assert layout is not None
        n_loc, t_local, halo = layout
        assert halo == fir_halo_rows(plan, n_loc)
        assert t_local == n_loc * plan.ratio


class TestPadMaskLayout:
    """sharding.py spec construction at non-divisible channel counts:
    the pad-and-mask layout (zero columns up to the shard multiple,
    trimmed back on gather)."""

    def test_channel_pad_values(self):
        from tpudas.parallel.sharding import channel_pad

        mesh = make_mesh(4)
        assert channel_pad(16, mesh) == 0
        assert channel_pad(10, mesh) == 2
        assert channel_pad(3, mesh) == 1
        assert channel_pad(1, mesh) == 3

    def test_pad_channels_host_and_device(self):
        from tpudas.parallel.sharding import pad_channels

        mesh = make_mesh(4)
        x = np.ones((5, 10), np.float32)
        p = pad_channels(x, mesh)
        assert isinstance(p, np.ndarray) and p.shape == (5, 12)
        assert np.array_equal(p[:, 10:], np.zeros((5, 2)))
        pj = pad_channels(jnp.asarray(x), mesh)
        assert pj.shape == (5, 12)
        assert np.array_equal(np.asarray(pj), p)
        # already divisible: returned untouched
        y = np.ones((5, 8), np.float32)
        assert pad_channels(y, mesh) is y

    def test_place_block_spec_and_gather_roundtrip(self):
        from jax.sharding import PartitionSpec as P

        from tpudas.parallel.sharding import (
            gather_leaves,
            is_device_resident,
            place_block,
        )

        mesh = make_mesh(4)
        x = np.random.default_rng(0).standard_normal(
            (32, 10)
        ).astype(np.float32)
        placed = place_block(x, mesh)
        assert is_device_resident(placed)
        assert placed.shape == (32, 12)  # padded to the shard multiple
        assert placed.sharding.spec == P(None, "ch")
        (back,) = gather_leaves((placed,), 10)
        assert isinstance(back, np.ndarray)
        assert np.array_equal(back, x)  # pad trimmed, bytes identical

    def test_transfer_accounting(self):
        """place/gather traffic lands in
        tpudas_parallel_transfer_bytes_total — what the bench reads to
        prove steady rounds stop round-tripping the carry."""
        from tpudas.obs.registry import MetricsRegistry, use_registry
        from tpudas.parallel.sharding import gather_leaves, place_block

        mesh = make_mesh(4)
        reg = MetricsRegistry()
        x = np.zeros((16, 8), np.float32)
        with use_registry(reg):
            placed = place_block(x, mesh)
            gather_leaves((placed,), 8)
            gather_leaves((np.zeros((4, 8), np.float32),), 8)  # host: free
        snap = reg.snapshot()["tpudas_parallel_transfer_bytes_total"]
        series = {
            tuple(sorted(labels.items())): value
            for labels, value in snap["series"]
        }
        assert series[(("direction", "place"),)] == x.nbytes
        assert series[(("direction", "gather"),)] == x.nbytes


class TestShardedStreamOps:
    """The sharded stream steps (cascade + fft) are byte-identical to
    the single-device steps, keep their carry resident on the mesh
    between calls, and trim the pad-and-mask columns on output."""

    @pytest.mark.parametrize("n_ch", [16, 10, 3])
    @pytest.mark.slow
    def test_cascade_stream_bit_equal_and_resident(self, n_ch):
        from jax.sharding import PartitionSpec as P

        from tpudas.ops.fir import (
            cascade_decimate_stream,
            cascade_stream_init,
            design_cascade,
        )
        from tpudas.parallel.sharding import is_device_resident

        mesh = make_mesh(4)
        plan = design_cascade(100.0, 20, 0.45, 4)
        rng = np.random.default_rng(5)
        blocks = [
            rng.standard_normal((t, n_ch)).astype(np.float32)
            for t in (400, 800, 400)
        ]
        ref_carry = cascade_stream_init(plan, n_ch)
        sh_carry = cascade_stream_init(plan, n_ch)
        for blk in blocks:
            y_ref, ref_carry = cascade_decimate_stream(
                blk, ref_carry, plan, "xla"
            )
            y_sh, sh_carry = cascade_decimate_stream(
                blk, sh_carry, plan, "xla", mesh=mesh
            )
            assert np.array_equal(np.asarray(y_ref), np.asarray(y_sh))
            for leaf in sh_carry:
                assert is_device_resident(leaf)
                assert leaf.sharding.spec == P(None, "ch")
                # padded to the shard multiple while resident
                assert leaf.shape[1] == n_ch + (-n_ch % 4)

    @pytest.mark.parametrize("n_ch", [16, 10])
    @pytest.mark.slow
    def test_fft_stream_bit_equal_and_resident(self, n_ch):
        from tpudas.ops.filter import (
            fft_pass_filter_stream,
            fft_stream_init,
        )
        from tpudas.parallel.sharding import is_device_resident

        mesh = make_mesh(4)
        rng = np.random.default_rng(6)
        blocks = [
            rng.standard_normal((t, n_ch)).astype(np.float32)
            for t in (256, 128)
        ]
        ref_carry = fft_stream_init(32, n_ch)
        sh_carry = fft_stream_init(32, n_ch)
        for blk in blocks:
            y_ref, ref_carry = fft_pass_filter_stream(
                blk, ref_carry, 0.01, high=2.0
            )
            y_sh, sh_carry = fft_pass_filter_stream(
                blk, sh_carry, 0.01, high=2.0, mesh=mesh
            )
            assert np.array_equal(np.asarray(y_ref), np.asarray(y_sh))
            assert is_device_resident(sh_carry)
        assert np.array_equal(
            np.asarray(ref_carry), np.asarray(sh_carry)[:, :n_ch]
        )

    def test_mismatched_carry_width_rejected(self):
        from tpudas.ops.fir import (
            cascade_decimate_stream,
            cascade_stream_init,
            design_cascade,
        )

        mesh = make_mesh(4)
        plan = design_cascade(100.0, 20, 0.45, 4)
        carry = cascade_stream_init(plan, 6)
        x = np.zeros((400, 16), np.float32)
        with pytest.raises(ValueError):
            cascade_decimate_stream(x, carry, plan, "xla", mesh=mesh)


class TestResolveMesh:
    def test_int_env_and_passthrough(self, monkeypatch):
        from tpudas.parallel.mesh import resolve_mesh

        monkeypatch.delenv("TPUDAS_MESH", raising=False)
        assert resolve_mesh(None) is None
        assert resolve_mesh(0) is None
        assert resolve_mesh(1) is None
        m = resolve_mesh(4)
        assert dict(m.shape) == {"time": 1, "ch": 4}
        assert resolve_mesh(m) is m
        monkeypatch.setenv("TPUDAS_MESH", "2")
        m2 = resolve_mesh(None)
        assert dict(m2.shape) == {"time": 1, "ch": 2}
        # explicit argument wins over the environment
        assert resolve_mesh(0, env="TPUDAS_MESH") is None

    def test_bad_counts_rejected(self, monkeypatch):
        from tpudas.parallel.mesh import resolve_mesh

        with pytest.raises(ValueError, match=">= 0"):
            resolve_mesh(-1)
        with pytest.raises(ValueError, match="exceeds"):
            resolve_mesh(len(jax.devices()) + 1)

    def test_shard_gauge_follows_resolution(self):
        from tpudas.obs.registry import MetricsRegistry, use_registry
        from tpudas.parallel.mesh import resolve_mesh

        reg = MetricsRegistry()
        with use_registry(reg):
            resolve_mesh(4)
        assert reg.get("tpudas_parallel_shards").value() == 4
        with use_registry(reg):
            resolve_mesh(None)
        assert reg.get("tpudas_parallel_shards").value() == 1


class TestShardedRealtimeEquivalence:
    """ISSUE 7 acceptance: a sharded realtime run on the CPU mesh
    produces outputs, saved carry, and pyramid/detect artifacts
    byte-identical to the single-device run over the same spool — and
    the serialized carry is layout-independent in both directions."""

    FS = 100.0
    N_CH = 10  # NOT divisible by 4: exercises the pad-and-mask layout
    FILE_SEC = 30.0
    T0 = np.datetime64("2023-03-22T00:00:00")
    # thresholds that actually fire events on the noisy synthetic spool
    # (an empty ledger would compare equal vacuously)
    DETECT_OPS = (
        ("stalta", {"sta": 2.0, "lta": 10.0, "on": 2.0, "off": 1.2}),
        ("rms", {"window": 5.0, "step": 2.0, "thresh": 1.5,
                 "baseline": 20.0}),
    )

    def _feed(self, src, first, n=1):
        from tpudas.testing import make_synthetic_spool

        make_synthetic_spool(
            src, n_files=n, file_duration=self.FILE_SEC, fs=self.FS,
            n_ch=self.N_CH, noise=0.05,
            start=self.T0
            + np.timedelta64(int(first * self.FILE_SEC * 1e9), "ns"),
            prefix=f"raw{first:03d}",
        )

    def _drive(self, src, out, mesh, engine="auto", feed_rounds=2,
               max_rounds=6, hooks=True, **kw):
        """One realtime run: 3 initial files, one more fed before each
        of ``feed_rounds`` subsequent polls, terminates on no-growth."""
        from tpudas.proc.streaming import run_lowpass_realtime

        if not os.path.isdir(src):
            self._feed(src, 0, 3)
        state = {"fed": 0}

        def sleep(_):
            if state["fed"] < feed_rounds:
                state["fed"] += 1
                self._feed(src, 2 + state["fed"])

        return run_lowpass_realtime(
            source=src, output_folder=out, start_time=str(self.T0),
            output_sample_interval=1.0, edge_buffer=10.0,
            process_patch_size=60, poll_interval=0.0, sleep_fn=sleep,
            max_rounds=max_rounds, mesh=mesh, engine=engine,
            pyramid=hooks, detect=hooks,
            detect_operators=self.DETECT_OPS if hooks else None,
            health=True, **kw,
        )

    # --- artifact comparisons ------------------------------------------

    def _merged(self, out):
        from tpudas import spool

        p = spool(str(out)).update().chunk(time=None)[0]
        return np.asarray(p.host_data()), np.asarray(p.coords["time"])

    def _carry_state(self, out):
        from tpudas.proc.stream import load_carry

        c = load_carry(str(out))
        assert c is not None
        return c

    def _assert_carries_equal(self, a, b):
        assert a._meta() == b._meta()
        assert len(a.bufs) == len(b.bufs)
        for x, y in zip(a.bufs, b.bufs):
            assert np.array_equal(np.asarray(x), np.asarray(y))
        if a.residual is None:
            assert b.residual is None
        else:
            assert np.array_equal(a.residual, b.residual)

    def _tree(self, out, sub):
        import hashlib

        root = os.path.join(str(out), sub)
        tree = {}
        for dirpath, _dirs, files in os.walk(root):
            for name in sorted(files):
                if ".prev" in name or ".tmp" in name:
                    continue
                path = os.path.join(dirpath, name)
                with open(path, "rb") as fh:
                    digest = hashlib.sha256(fh.read()).hexdigest()
                tree[os.path.relpath(path, root)] = digest
        return tree

    def _assert_all_artifacts_equal(self, ref_out, out):
        d_ref, t_ref = self._merged(ref_out)
        d, t = self._merged(out)
        assert np.array_equal(d_ref, d)
        assert np.array_equal(t_ref, t)
        self._assert_carries_equal(
            self._carry_state(ref_out), self._carry_state(out)
        )
        from tpudas.serve.tiles import TILE_DIRNAME

        ref_tiles = self._tree(ref_out, TILE_DIRNAME)
        assert ref_tiles and ref_tiles == self._tree(out, TILE_DIRNAME)
        from tpudas.detect.ledger import DETECT_DIRNAME, load_events

        ref_det = self._tree(ref_out, DETECT_DIRNAME)
        det = self._tree(out, DETECT_DIRNAME)
        # the detect carry .npz embeds zip timestamps: compare parsed
        from tpudas.detect.runner import load_detect_carry

        for key in list(ref_det):
            if key.endswith(".npz"):
                ref_det.pop(key), det.pop(key, None)
        assert ref_det == det
        ca, cb = load_detect_carry(str(ref_out)), load_detect_carry(str(out))
        assert (ca is None) == (cb is None)
        if ca is not None:
            assert ca["meta"] == cb["meta"]
            for sa, sb in zip(ca["states"], cb["states"]):
                assert sorted(sa) == sorted(sb)
                for k in sa:
                    assert np.array_equal(
                        np.asarray(sa[k]), np.asarray(sb[k])
                    )
        assert len(load_events(str(ref_out))) > 0  # not vacuous

    # --- the acceptance tests ------------------------------------------

    @pytest.mark.slow
    def test_sharded_run_byte_identical(self, tmp_path, cpu_mesh4,
                                        monkeypatch):
        """mesh=Mesh and TPUDAS_MESH=4 runs == the single-device run:
        outputs, carry .npz content, pyramid tiles, events ledger,
        score tiles, detect carry."""
        from tpudas.obs.health import read_health

        monkeypatch.delenv("TPUDAS_MESH", raising=False)
        legs = {"single": dict(mesh=None), "mesh": dict(mesh=cpu_mesh4)}
        for name, kw in legs.items():
            rounds = self._drive(
                tmp_path / f"src_{name}", tmp_path / f"out_{name}", **kw
            )
            assert rounds == 3
            health = read_health(str(tmp_path / f"out_{name}"))
            assert health["mode"] == "stateful"  # mesh kept the carry
        monkeypatch.setenv("TPUDAS_MESH", "4")
        assert self._drive(
            tmp_path / "src_env", tmp_path / "out_env", mesh=None
        ) == 3
        monkeypatch.delenv("TPUDAS_MESH")
        self._assert_all_artifacts_equal(
            tmp_path / "out_single", tmp_path / "out_mesh"
        )
        self._assert_all_artifacts_equal(
            tmp_path / "out_single", tmp_path / "out_env"
        )

    @pytest.mark.slow
    def test_sharded_fft_engine_byte_identical(self, tmp_path, cpu_mesh4):
        outs = {}
        for name, mesh in (("single", None), ("mesh", cpu_mesh4)):
            out = tmp_path / f"out_{name}"
            self._drive(
                tmp_path / f"src_{name}", out, mesh, engine="fft",
                hooks=False,
            )
            outs[name] = out
        d_ref, t_ref = self._merged(outs["single"])
        d, t = self._merged(outs["mesh"])
        assert np.array_equal(d_ref, d) and np.array_equal(t_ref, t)
        self._assert_carries_equal(
            self._carry_state(outs["single"]),
            self._carry_state(outs["mesh"]),
        )

    @pytest.mark.slow
    def test_carry_save_cadence(self, tmp_path, cpu_mesh4):
        """TPUDAS_CARRY_SAVE_EVERY > 1 skips the per-round gather+save
        (the steady round keeps the pytree on-device) and the clean
        shutdown flushes — end state byte-identical, fewer saves."""
        from tpudas.obs.registry import MetricsRegistry, use_registry

        saves = {}
        for name, every in (("each", 1), ("cadence", 4)):
            reg = MetricsRegistry()
            with use_registry(reg):
                self._drive(
                    tmp_path / f"src_{name}", tmp_path / f"out_{name}",
                    cpu_mesh4, hooks=False, carry_save_every=every,
                )
            saves[name] = reg.value("tpudas_stream_carry_saves_total")
        # each: open + one per processing round; cadence 4: open + the
        # final clean-termination flush only
        assert saves["cadence"] == 2
        assert saves["each"] == 4
        d_ref, t_ref = self._merged(tmp_path / "out_each")
        d, t = self._merged(tmp_path / "out_cadence")
        assert np.array_equal(d_ref, d) and np.array_equal(t_ref, t)
        self._assert_carries_equal(
            self._carry_state(tmp_path / "out_each"),
            self._carry_state(tmp_path / "out_cadence"),
        )

    @pytest.mark.slow
    def test_carry_is_layout_independent_across_restarts(
        self, tmp_path, cpu_mesh4
    ):
        """A run can stop sharded and resume single-device (or the
        reverse) from the same serialized carry, byte-identical to a
        control that never changed layout."""
        scenarios = {
            "ctrl": (None, None),
            "shard_then_single": (cpu_mesh4, None),
            "single_then_shard": (None, cpu_mesh4),
        }
        for name, (mesh1, mesh2) in scenarios.items():
            src = tmp_path / f"src_{name}"
            out = tmp_path / f"out_{name}"
            # leg 1: 3 initial files + 1 fed, stops after 2 rounds
            self._drive(src, out, mesh1, feed_rounds=1, max_rounds=2,
                        hooks=False)
            # leg 2: resumes the persisted carry, feeds 1 more file
            self._feed(src, 4)
            self._drive(src, out, mesh2, feed_rounds=0, hooks=False)
        d_ref, t_ref = self._merged(tmp_path / "out_ctrl")
        for name in ("shard_then_single", "single_then_shard"):
            d, t = self._merged(tmp_path / ("out_" + name))
            assert np.array_equal(d_ref, d), name
            assert np.array_equal(t_ref, t), name
            self._assert_carries_equal(
                self._carry_state(tmp_path / "out_ctrl"),
                self._carry_state(tmp_path / ("out_" + name)),
            )
