"""Multi-host (DCN) init hooks: env parsing, arg precedence,
idempotence, and the process-0 coordinator case — with
``jax.distributed.initialize`` mocked (no cluster needed, SURVEY.md
§2.4 DCN row)."""

import jax
import pytest

import tpudas.parallel.distributed as dist


@pytest.fixture(autouse=True)
def reset_state(monkeypatch):
    monkeypatch.setattr(dist, "_initialized", False)
    for var in ("COORDINATOR_ADDRESS", "NUM_PROCESSES", "PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    calls = []
    monkeypatch.setattr(
        jax.distributed, "initialize", lambda **kw: calls.append(kw)
    )
    yield calls


class TestInitializeMultihost:
    def test_noop_without_config(self, reset_state):
        assert dist.initialize_multihost() is False
        assert reset_state == []

    def test_env_parsing(self, reset_state, monkeypatch):
        monkeypatch.setenv("COORDINATOR_ADDRESS", "10.0.0.1:8476")
        monkeypatch.setenv("NUM_PROCESSES", "8")
        monkeypatch.setenv("PROCESS_ID", "3")
        assert dist.initialize_multihost() is True
        assert reset_state == [
            {
                "coordinator_address": "10.0.0.1:8476",
                "num_processes": 8,
                "process_id": 3,
            }
        ]

    def test_explicit_args_beat_env(self, reset_state, monkeypatch):
        monkeypatch.setenv("COORDINATOR_ADDRESS", "env:1")
        monkeypatch.setenv("NUM_PROCESSES", "2")
        monkeypatch.setenv("PROCESS_ID", "1")
        assert dist.initialize_multihost("arg:2", 4, 2) is True
        (call,) = reset_state
        assert call["coordinator_address"] == "arg:2"
        assert call["num_processes"] == 4
        assert call["process_id"] == 2

    def test_process_zero_is_not_dropped(self, reset_state):
        # `process_id or env` would lose the coordinator (id 0)
        assert (
            dist.initialize_multihost("10.0.0.1:8476", 2, 0) is True
        )
        assert reset_state[0]["process_id"] == 0

    def test_idempotent(self, reset_state):
        assert dist.initialize_multihost("10.0.0.1:8476", 2, 0) is True
        assert dist.initialize_multihost("10.0.0.1:8476", 2, 0) is False
        assert len(reset_state) == 1

    def test_partial_config_is_noop(self, reset_state, monkeypatch):
        monkeypatch.setenv("COORDINATOR_ADDRESS", "10.0.0.1:8476")
        # NUM_PROCESSES / PROCESS_ID missing
        assert dist.initialize_multihost() is False
        assert reset_state == []


class TestQueries:
    def test_single_process(self):
        assert dist.is_distributed() is False
        assert len(dist.global_mesh_devices()) == len(jax.devices())


@pytest.mark.slow
class TestRealTwoProcessDCN:
    def test_two_process_mesh_collectives(self):
        """The real thing, no mocks: two spawned processes call
        jax.distributed.initialize (via initialize_multihost env
        config), build one global (2, 4) mesh whose time axis spans the
        process boundary, and run psum + ppermute-halo collectives
        across it (BASELINE config 5's DCN direction)."""
        import os
        import socket
        import subprocess
        import sys

        import __graft_entry__ as g

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        worker = os.path.join(os.path.dirname(__file__), "dcn_worker.py")
        procs = []
        for pid in range(2):
            env = g._cpu_env(4)  # 4 virtual devices per process
            env.update(
                COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
                NUM_PROCESSES="2",
                PROCESS_ID=str(pid),
            )
            procs.append(
                subprocess.Popen(
                    [sys.executable, worker],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                )
            )
        outs = []
        for p in procs:
            try:
                out, err = p.communicate(timeout=180)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                pytest.fail("DCN worker timed out (coordinator hang?)")
            outs.append((p.returncode, out, err))
        for rc, out, err in outs:
            assert rc == 0, err[-1500:]
            assert "DCN_WORKER_OK" in out, (out, err[-500:])
