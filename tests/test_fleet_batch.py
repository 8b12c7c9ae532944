"""Ragged-batched fleet execution (ISSUE 16).

Stacked-step byte-identity at the ops layer (mixed channel widths,
both STACKED_ENGINES, quantized int16, FFT overlap-save), carry
slice-out/slice-in roundtrips across solo<->stacked transitions, the
BatchGroupFormer's memoized signatures, the BatchStepExecutor
rendezvous (wave partition, leave-shrink), and the batched FleetEngine
end-to-end: byte-identity against single-stream controls, park/fault
mid-round batch shrink, and KI-kill resume under ``batched=True``.
"""

import hashlib
import os
import subprocess
import sys
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from tpudas.core.timeutils import to_datetime64
from tpudas.fleet import FleetEngine, StreamConfig, StreamSpec
from tpudas.fleet.batch import BatchGroupFormer, BatchStepExecutor
from tpudas.io.registry import write_patch
from tpudas.obs.registry import MetricsRegistry, use_registry
from tpudas.testing import (
    FaultPlan,
    FaultSpec,
    install_fault_plan,
    synthetic_patch,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FS = 100.0
FILE_SEC = 30.0
T0 = "2023-03-22T00:00:00"
WIDTHS = {"s0": 6, "s1": 10, "s2": 6}
NOISES = {"s0": 0.005, "s1": 0.01, "s2": 0.02}


def _feed(directory, start_index, count, noise=0.01, n_ch=6):
    os.makedirs(directory, exist_ok=True)
    t0 = to_datetime64(T0).astype("datetime64[ns]")
    step = np.timedelta64(int(round(1e9 / FS)), "ns")
    n = int(FILE_SEC * FS)
    for i in range(start_index, start_index + count):
        p = synthetic_patch(
            t0=t0 + i * n * step, duration=FILE_SEC, fs=FS, n_ch=n_ch,
            seed=i, phase_origin=t0, noise=noise,
        )
        write_patch(p, os.path.join(directory, f"raw_{i:04d}.h5"))


def _lowpass_config(**overrides):
    base = dict(
        kind="lowpass",
        start_time=T0,
        output_sample_interval=1.0,
        edge_buffer=8.0,
        process_patch_size=40,
        poll_interval=0.0,
        poll_jitter=0.0,
    )
    base.update(overrides)
    return StreamConfig(**base)


def _run_control(source, out, feed_fn=None, **overrides):
    from tpudas.proc.streaming import run_lowpass_realtime

    state = {"called": False}

    def sleep(_):
        if not state["called"]:
            state["called"] = True
            if feed_fn is not None:
                feed_fn()

    kwargs = dict(
        source=source,
        output_folder=out,
        start_time=T0,
        output_sample_interval=1.0,
        edge_buffer=8.0,
        process_patch_size=40,
        poll_interval=0.0,
        sleep_fn=sleep,
    )
    kwargs.update(overrides)
    return run_lowpass_realtime(**kwargs)


def _output_shas(folder) -> dict:
    out = {}
    for name in sorted(os.listdir(folder)):
        if name.startswith("LFDAS_") and name.endswith(".h5"):
            with open(os.path.join(folder, name), "rb") as fh:
                out[name] = hashlib.sha256(fh.read()).hexdigest()
    return out


def _pyramid_shas(folder) -> dict:
    from tpudas.serve.tiles import TILE_DIRNAME
    from tpudas.utils.atomicio import is_tmp_name

    tiles = os.path.join(folder, TILE_DIRNAME)
    out = {}
    for dirpath, _d, filenames in os.walk(tiles):
        for name in sorted(filenames):
            if ".prev" in name or is_tmp_name(name):
                continue
            path = os.path.join(dirpath, name)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, tiles)] = hashlib.sha256(
                    fh.read()
                ).hexdigest()
    return out


# ---------------------------------------------------------------------------
# ops layer: stacked steps vs solo, byte for byte


class TestStackedCascadeOps:
    # both resolved stacked engines must stay in the matrix — the
    # tools/check_engines.py lint walks this file for the literals
    @pytest.mark.slow
    @pytest.mark.parametrize("engine", ["xla", "fused-xla"])
    def test_mixed_width_multi_round_byte_identity(self, engine):
        """Ragged packing (widths 5/8/3) over 3 carry-fed rounds: every
        stream's output and carry leaves byte-equal the solo path."""
        from tpudas.ops.fir import (
            cascade_decimate_stream,
            cascade_decimate_stream_stacked,
            cascade_stream_init,
            design_cascade,
        )

        plan = design_cascade(100.0, 10, 0.45, 4)
        widths = (5, 8, 3)
        rng = np.random.default_rng(7)
        stacked_c = [cascade_stream_init(plan, w) for w in widths]
        solo_c = [cascade_stream_init(plan, w) for w in widths]
        for _round in range(3):
            blocks = [
                rng.standard_normal((200, w)).astype(np.float32)
                for w in widths
            ]
            res = cascade_decimate_stream_stacked(
                blocks, stacked_c, plan, engine
            )
            stacked_c = [c for _y, c in res]
            for i, b in enumerate(blocks):
                y_solo, solo_c[i] = cascade_decimate_stream(
                    b, solo_c[i], plan, engine
                )
                assert np.array_equal(
                    np.asarray(res[i][0]), np.asarray(y_solo)
                ), f"member {i} output diverged ({engine})"
                for a, bb in zip(stacked_c[i], solo_c[i]):
                    assert np.array_equal(np.asarray(a), np.asarray(bb))

    @pytest.mark.slow
    def test_quantized_int16_stacked(self):
        """A stacked int16 wave with a shared qscale dequantizes
        in-kernel, byte-identical to the solo quantized path."""
        from tpudas.ops.fir import (
            cascade_decimate_stream,
            cascade_decimate_stream_stacked,
            cascade_stream_init,
            design_cascade,
        )

        plan = design_cascade(100.0, 10, 0.45, 4)
        scale = 2.5e-4
        rng = np.random.default_rng(11)
        widths = (4, 7)
        blocks = [
            rng.integers(-3000, 3000, (200, w)).astype(np.int16)
            for w in widths
        ]
        res = cascade_decimate_stream_stacked(
            blocks,
            [cascade_stream_init(plan, w) for w in widths],
            plan, "xla", qscale=scale,
        )
        for b, w, (y, _c) in zip(blocks, widths, res):
            y_solo, _ = cascade_decimate_stream(
                b, cascade_stream_init(plan, w), plan, "xla",
                qscale=scale,
            )
            assert np.array_equal(np.asarray(y), np.asarray(y_solo))

    def test_carry_slice_roundtrip_solo_stacked_solo(self):
        """A stream moves solo -> stacked -> solo; the carries sliced
        out of the stacked step feed the solo step with no drift."""
        from tpudas.ops.fir import (
            cascade_decimate_stream,
            cascade_decimate_stream_stacked,
            cascade_stream_init,
            design_cascade,
        )

        plan = design_cascade(100.0, 10, 0.45, 4)
        widths = (5, 8)
        rng = np.random.default_rng(3)
        rounds = [
            [
                rng.standard_normal((200, w)).astype(np.float32)
                for w in widths
            ]
            for _ in range(3)
        ]
        # reference: all-solo
        ref_c = [cascade_stream_init(plan, w) for w in widths]
        ref_y = [[], []]
        for blocks in rounds:
            for i, b in enumerate(blocks):
                y, ref_c[i] = cascade_decimate_stream(b, ref_c[i], plan)
                ref_y[i].append(np.asarray(y))
        # candidate: solo round, stacked round, solo round
        c = [cascade_stream_init(plan, w) for w in widths]
        got_y = [[], []]
        for i, b in enumerate(rounds[0]):
            y, c[i] = cascade_decimate_stream(b, c[i], plan)
            got_y[i].append(np.asarray(y))
        res = cascade_decimate_stream_stacked(rounds[1], c, plan, "xla")
        c = [cc for _y, cc in res]
        for i, (y, _cc) in enumerate(res):
            got_y[i].append(np.asarray(y))
        for i, b in enumerate(rounds[2]):
            y, c[i] = cascade_decimate_stream(b, c[i], plan)
            got_y[i].append(np.asarray(y))
        for i in range(len(widths)):
            for a, b in zip(got_y[i], ref_y[i]):
                assert np.array_equal(a, b)
            for a, b in zip(c[i], ref_c[i]):
                assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_stacked_validation(self):
        from tpudas.ops.fir import (
            cascade_decimate_stream_stacked,
            cascade_stream_init,
            design_cascade,
        )

        plan = design_cascade(100.0, 10, 0.45, 4)
        c4 = cascade_stream_init(plan, 4)
        b4 = np.zeros((200, 4), np.float32)
        with pytest.raises(ValueError, match="stacked engine"):
            cascade_decimate_stream_stacked(
                [b4], [c4], plan, "pallas-stream"
            )
        with pytest.raises(ValueError, match="shared T"):
            cascade_decimate_stream_stacked(
                [b4, np.zeros((100, 4), np.float32)], [c4, c4],
                plan, "xla",
            )
        with pytest.raises(ValueError, match="carry width"):
            cascade_decimate_stream_stacked(
                [np.zeros((200, 5), np.float32)], [c4], plan, "xla"
            )


class TestStackedFFTOps:
    def test_mixed_width_multi_round_byte_identity(self):
        from tpudas.ops.filter import (
            fft_pass_filter_stream,
            fft_pass_filter_stream_stacked,
            fft_stream_init,
        )

        widths = (5, 8, 3)
        rng = np.random.default_rng(5)
        stacked_c = [fft_stream_init(64, w) for w in widths]
        solo_c = [fft_stream_init(64, w) for w in widths]
        for _round in range(3):
            blocks = [
                rng.standard_normal((512, w)).astype(np.float32)
                for w in widths
            ]
            res = fft_pass_filter_stream_stacked(
                blocks, stacked_c, 0.01, high=0.45
            )
            stacked_c = [c for _y, c in res]
            for i, b in enumerate(blocks):
                y_solo, solo_c[i] = fft_pass_filter_stream(
                    b, solo_c[i], 0.01, high=0.45
                )
                assert np.array_equal(
                    np.asarray(res[i][0]), np.asarray(y_solo)
                ), f"member {i} FFT output diverged"
                assert np.array_equal(
                    np.asarray(stacked_c[i]), np.asarray(solo_c[i])
                )

    def test_stacked_validation(self):
        from tpudas.ops.filter import (
            fft_pass_filter_stream_stacked,
            fft_stream_init,
        )

        c = fft_stream_init(64, 4)
        with pytest.raises(ValueError, match="length mismatch"):
            fft_pass_filter_stream_stacked(
                [np.zeros((512, 4), np.float32)], [c, c], 0.01,
                high=0.45,
            )
        with pytest.raises(ValueError, match="does not match"):
            fft_pass_filter_stream_stacked(
                [np.zeros((512, 5), np.float32)], [c], 0.01, high=0.45
            )


# ---------------------------------------------------------------------------
# the group former


def _fake_runner(**over):
    cfg = SimpleNamespace(
        engine=over.pop("engine", None),
        filter_order=over.pop("filter_order", 4),
        on_gap=over.pop("on_gap", "interpolate"),
    )
    r = SimpleNamespace(
        kind="lowpass",
        stateful=True,
        mesh=None,
        spec=SimpleNamespace(config=cfg),
        d_t=1.0,
        buff_out=8,
        process_patch_size=40,
        carry=None,
    )
    for k, v in over.items():
        setattr(r, k, v)
    return r


class TestBatchGroupFormer:
    def test_group_key_determinism(self):
        """Same-config streams get equal signatures; any grouping-
        relevant difference (engine request, filter order, cadence)
        splits them."""
        f = BatchGroupFormer()
        a = f.signature("a", _fake_runner())
        b = f.signature("b", _fake_runner())
        assert a is not None and a == b
        assert f.signature("c", _fake_runner(engine="fused-xla")) != a
        assert f.signature("d", _fake_runner(filter_order=6)) != a
        assert f.signature("e", _fake_runner(d_t=2.0)) != a
        # recomputing from an identical runner state is stable
        assert f.signature("a", _fake_runner()) == a

    def test_solo_only_streams_get_none(self):
        f = BatchGroupFormer()
        assert f.signature("a", None) is None
        assert f.signature("b", _fake_runner(kind="rolling")) is None
        assert f.signature("c", _fake_runner(stateful=False)) is None
        assert f.signature("d", _fake_runner(mesh=object())) is None

    def test_memo_hit_miss_and_invalidate(self):
        reg = MetricsRegistry()
        f = BatchGroupFormer()
        r = _fake_runner()
        with use_registry(reg):
            f.signature("a", r)
            f.signature("a", r)  # same runner, same token -> hit
            f.invalidate("a")
            f.signature("a", r)  # invalidated -> recompute
        assert reg.value(
            "tpudas_fleet_batch_sig_memo_total", result="hit"
        ) == 1
        assert reg.value(
            "tpudas_fleet_batch_sig_memo_total", result="miss"
        ) == 2

    def test_carry_change_invalidates_token(self):
        """An engine crossover mutates the carry's engine fields; the
        memo token sees it and recomputes (no stale plan keys)."""
        reg = MetricsRegistry()
        f = BatchGroupFormer()
        carry = SimpleNamespace(
            kind="cascade", engine_req="auto",
            d_ns=10_000_000_000, ratio=100, edge_in=800, order=4,
        )
        r = _fake_runner(carry=carry)
        with use_registry(reg):
            s1 = f.signature("a", r)
            carry.engine_req = "fused-xla"
            s2 = f.signature("a", r)
        assert s1 != s2
        assert reg.value(
            "tpudas_fleet_batch_sig_memo_total", result="miss"
        ) == 2


# ---------------------------------------------------------------------------
# the rendezvous executor


class TestBatchStepExecutor:
    def _run_members(self, ex, fns):
        """Run one callable per member on its own thread (bind/leave
        contract included); returns {member: result-or-exception}."""
        out = {}

        def runner(m, fn):
            ex.bind(m)
            try:
                out[m] = fn()
            except BaseException as exc:  # noqa: BLE001
                out[m] = exc
            finally:
                ex.leave(m)

        threads = [
            threading.Thread(target=runner, args=(m, fn))
            for m, fn in fns.items()
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        return out

    def test_same_key_wave_stacks_and_matches_solo(self):
        from tpudas.ops.fir import (
            cascade_decimate_stream,
            cascade_stream_init,
            design_cascade,
        )

        plan = design_cascade(100.0, 10, 0.45, 4)
        rng = np.random.default_rng(2)
        widths = {"a": 5, "b": 8, "c": 3}
        blocks = {
            m: rng.standard_normal((200, w)).astype(np.float32)
            for m, w in widths.items()
        }
        reg = MetricsRegistry()
        ex = BatchStepExecutor(widths)
        with use_registry(reg):
            res = self._run_members(ex, {
                m: (lambda m=m: ex.cascade_step(
                    blocks[m], cascade_stream_init(plan, widths[m]),
                    plan, "xla",
                ))
                for m in widths
            })
        assert reg.value(
            "tpudas_fleet_batch_stacked_launches_total"
        ) == 1
        assert reg.value(
            "tpudas_fleet_batch_stacked_members_total"
        ) == 3
        for m, w in widths.items():
            y, _carry = res[m]
            y_solo, _ = cascade_decimate_stream(
                blocks[m], cascade_stream_init(plan, w), plan, "xla"
            )
            assert np.array_equal(np.asarray(y), np.asarray(y_solo))

    def test_mixed_keys_partition_into_waves(self):
        """Members whose exact stack key differs (here: block length)
        split into a stacked pair plus a solo dispatch."""
        from tpudas.ops.fir import cascade_stream_init, design_cascade

        plan = design_cascade(100.0, 10, 0.45, 4)
        rng = np.random.default_rng(4)
        reg = MetricsRegistry()
        ex = BatchStepExecutor(["a", "b", "c"])
        mk = lambda t, w: rng.standard_normal((t, w)).astype(np.float32)
        with use_registry(reg):
            res = self._run_members(ex, {
                "a": lambda: ex.cascade_step(
                    mk(200, 5), cascade_stream_init(plan, 5), plan, "xla"
                ),
                "b": lambda: ex.cascade_step(
                    mk(200, 8), cascade_stream_init(plan, 8), plan, "xla"
                ),
                "c": lambda: ex.cascade_step(
                    mk(400, 5), cascade_stream_init(plan, 5), plan, "xla"
                ),
            })
        assert reg.value(
            "tpudas_fleet_batch_stacked_launches_total"
        ) == 1
        assert reg.value("tpudas_fleet_batch_solo_launches_total") == 1
        assert np.shape(np.asarray(res["c"][0]))[0] == 40

    def test_leave_shrinks_rendezvous(self):
        """A member that leaves without submitting (fault before its
        device dispatch) must not deadlock the others."""
        from tpudas.ops.fir import cascade_stream_init, design_cascade

        plan = design_cascade(100.0, 10, 0.45, 4)
        rng = np.random.default_rng(6)
        ex = BatchStepExecutor(["a", "b", "c"])

        def faulty():
            raise ValueError("pre-dispatch fault")

        res = self._run_members(ex, {
            "a": lambda: ex.cascade_step(
                rng.standard_normal((200, 5)).astype(np.float32),
                cascade_stream_init(plan, 5), plan, "xla",
            ),
            "b": lambda: ex.cascade_step(
                rng.standard_normal((200, 5)).astype(np.float32),
                cascade_stream_init(plan, 5), plan, "xla",
            ),
            "c": faulty,
        })
        assert isinstance(res["c"], ValueError)
        for m in ("a", "b"):
            y, carry = res[m]
            assert np.shape(np.asarray(y)) == (20, 5)
            assert len(carry) > 0


# ---------------------------------------------------------------------------
# the batched fleet, end to end


def _batched_specs(tmp_path, **cfg_overrides):
    specs = []
    for sid, w in WIDTHS.items():
        src = str(tmp_path / f"src_{sid}")
        _feed(src, 0, 2, noise=NOISES[sid], n_ch=w)
        specs.append(
            StreamSpec(
                stream_id=sid, source=src,
                config=_lowpass_config(**cfg_overrides),
            )
        )
    return specs


def _assert_streams_match_controls(tmp_path, root, pyramid=True,
                                   sids=None, feed_more=True):
    for sid in (sids or WIDTHS):
        ctrl_src = str(tmp_path / f"ctrl_src_{sid}")
        _feed(ctrl_src, 0, 2, noise=NOISES[sid], n_ch=WIDTHS[sid])
        ctrl_out = str(tmp_path / f"ctrl_out_{sid}")
        feed_fn = None
        if feed_more:
            feed_fn = lambda s=ctrl_src, sid=sid: _feed(
                s, 2, 1, noise=NOISES[sid], n_ch=WIDTHS[sid]
            )
        _run_control(ctrl_src, ctrl_out, feed_fn=feed_fn,
                     pyramid=pyramid)
        assert _output_shas(os.path.join(root, sid)) == (
            _output_shas(ctrl_out)
        ), f"stream {sid} outputs differ from solo control"
        if pyramid:
            assert _pyramid_shas(os.path.join(root, sid)) == (
                _pyramid_shas(ctrl_out)
            ), f"stream {sid} pyramid differs from solo control"


class TestFleetBatched:
    @pytest.mark.slow
    def test_mixed_width_byte_identity_and_metrics(self, tmp_path):
        """3 mixed-width streams (6/10/6 ch) through the batched
        scheduler: every dispatch stacks (ragged packing), outputs and
        pyramids byte-identical to per-stream controls, and the
        batch metrics account for every round."""
        root = str(tmp_path / "root")
        specs = _batched_specs(tmp_path, pyramid=True)
        fed = {"done": False}

        def fleet_sleep(_):
            if not fed["done"]:
                fed["done"] = True
                for sid, w in WIDTHS.items():
                    _feed(
                        str(tmp_path / f"src_{sid}"), 2, 1,
                        noise=NOISES[sid], n_ch=w,
                    )

        reg = MetricsRegistry()
        with use_registry(reg):
            summary = FleetEngine(
                root, specs, sleep_fn=fleet_sleep, batched=True
            ).run()
        assert summary["rounds_total"] == 6
        assert summary["parked"] == []
        # zero jitter -> every poll (2 processing rounds + the final
        # termination poll) services as one 3-member group
        assert reg.value("tpudas_fleet_batch_groups_total") == 3
        assert reg.value("tpudas_fleet_batch_members_total") == 9
        assert reg.value(
            "tpudas_fleet_batch_stacked_launches_total"
        ) > 0
        assert reg.value(
            "tpudas_fleet_batch_solo_launches_total"
        ) == 0
        stacked = reg.value(
            "tpudas_fleet_batch_stacked_members_total"
        )
        launches = reg.value(
            "tpudas_fleet_batch_stacked_launches_total"
        )
        assert stacked == 3 * launches  # every wave carried all 3
        _assert_streams_match_controls(tmp_path, root)

    def test_env_var_enables_batching(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPUDAS_FLEET_BATCHED", "1")
        root = str(tmp_path / "root")
        specs = _batched_specs(tmp_path)
        eng = FleetEngine(root, specs, sleep_fn=lambda _s: None)
        assert eng.batched is True
        monkeypatch.setenv("TPUDAS_FLEET_BATCHED", "0")
        eng2 = FleetEngine(root, specs, sleep_fn=lambda _s: None)
        assert eng2.batched is False

    @pytest.mark.slow
    def test_fault_mid_round_shrinks_batch_not_fleet(self, tmp_path):
        """A stream faulting mid-round drops out of its batch group
        and parks; the surviving members' outputs stay byte-identical
        to their solo controls (the stacked carries slice back out
        intact)."""
        root = str(tmp_path / "root")
        specs = _batched_specs(tmp_path, pyramid=True)
        # carry.save's ctx is the stream's output folder (root/s1);
        # hit counting is global across streams, so the window must
        # span the whole run and `match` does the targeting
        plan = FaultPlan(
            FaultSpec(
                "carry.save", exc=ValueError, at=1, times=50,
                match=os.sep + "s1",
            )
        )
        reg = MetricsRegistry()
        with use_registry(reg), install_fault_plan(plan):
            summary = FleetEngine(
                root, specs, sleep_fn=lambda _s: None, batched=True
            ).run()
        assert summary["streams"]["s1"]["status"] == "parked"
        for sid in ("s0", "s2"):
            assert summary["streams"][sid]["status"] == "terminated"
        assert reg.value("tpudas_fleet_batch_groups_total") >= 1
        _assert_streams_match_controls(
            tmp_path, root, sids=("s0", "s2"), feed_more=False
        )
        # the parked stream's carry survived: a fresh engine (no
        # fault plan) finishes it byte-identical to its own control
        summary2 = FleetEngine(
            root, specs, sleep_fn=lambda _s: None, batched=True
        ).run()
        assert summary2["streams"]["s1"]["status"] == "terminated"
        _assert_streams_match_controls(
            tmp_path, root, sids=("s1",), feed_more=False
        )

    @pytest.mark.slow
    def test_ki_mid_batched_fleet_resumes_byte_identical(self, tmp_path):
        """KeyboardInterrupt mid-round under batched execution (the
        in-process stand-in for SIGKILL; tools/crash_drill.py
        --batched drills the real signal) kills the engine; a fresh
        batched engine resumes every stream byte-identical to its
        uninterrupted solo control."""
        root = str(tmp_path / "root")
        specs = _batched_specs(tmp_path, pyramid=True)
        plan = FaultPlan(
            FaultSpec("round.body", exc=KeyboardInterrupt, at=2)
        )
        with install_fault_plan(plan):
            with pytest.raises(KeyboardInterrupt):
                FleetEngine(
                    root, specs, sleep_fn=lambda _s: None, batched=True
                ).run()
        summary = FleetEngine(
            root, specs, sleep_fn=lambda _s: None, batched=True
        ).run()
        assert summary["parked"] == []
        _assert_streams_match_controls(tmp_path, root, feed_more=False)


@pytest.mark.slow
class TestCrashDrillBatched:
    def test_drill_batched_leg(self, tmp_path):
        """The SIGKILL crash drill's batched leg: kill -9 mid-fleet
        with TPUDAS_FLEET_BATCHED=1, resume, byte-identity."""
        proc = subprocess.run(
            [
                sys.executable,
                os.path.join(REPO, "tools", "crash_drill.py"),
                "--streams", "3", "--batched", "--cycles", "2",
                "--engines", "cascade",
                "--workdir", str(tmp_path),
            ],
            capture_output=True,
            text=True,
            timeout=900,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "OK" in proc.stdout
