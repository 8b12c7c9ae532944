"""The generator: seeded, exact, continuous, and a valid tdas stream."""

import os

import numpy as np

import traffic as tr

CFG = {"channels": 24, "sample_rate_hz": 1000.0, "payload_scale": 1e-3,
       "channel_spacing_m": 1.0}
MIX = {"file_seconds": 2, "pool": 3, "lead": 2, "files_per_unit": 1}


def _file_bytes(tmp_path, seed, i, name):
    rows = tr.file_rows(CFG, MIX)
    k = tr.pool_index(seed, i, MIX["pool"])
    pay = tr.make_payload(seed, k, rows, CFG["channels"], 1000.0)
    path = os.path.join(tmp_path, name)
    tr.write_file(path, tr.header(CFG, i, rows), pay)
    with open(path, "rb") as fh:
        return fh.read()


def test_same_seed_same_bytes(tmp_path):
    for i in range(4):
        a = _file_bytes(tmp_path, 2**31 + 17, i, "a.tdas")
        b = _file_bytes(tmp_path, 2**31 + 17, i, "b.tdas")
        assert a == b
    assert _file_bytes(tmp_path, 5, 0, "c") != _file_bytes(tmp_path, 6, 0, "d")


def test_header_is_tdas_v1_with_its_own_time(tmp_path):
    rows = tr.file_rows(CFG, MIX)
    for i in (0, 7):
        raw = _file_bytes(tmp_path, 3, i, "h.tdas")
        magic, ver, t0, dt, n_t, n_c, code, scale, d0, dx, _ = \
            tr.TDAS_HEADER.unpack(raw[:64])
        assert (magic, ver, code, n_t, n_c) == (b"TDAS", 1, 1, rows, 24)
        assert t0 == tr.T0_NS + i * rows * dt and dt == 1_000_000
        assert np.float32(scale) == np.float32(1e-3)
        assert len(raw) == 64 + rows * 24 * 2


def test_columns_equal_the_file_exactly():
    rows = tr.file_rows(CFG, MIX)
    pay = tr.make_payload(9, 1, rows, 24, 1000.0)
    cols = [0, 5, 23]
    got = tr.payload_columns(9, 1, rows, 24, 1000.0, cols)
    assert np.array_equal(got, pay[:, cols].astype(np.float64))
    assert np.abs(pay).max() < 2**15


def test_pool_order_is_a_seeded_permutation():
    for seed in (1, 2**40):
        block = [tr.pool_index(seed, i, 5) for i in range(5)]
        assert sorted(block) == list(range(5))
    assert [tr.pool_index(1, i, 5) for i in range(20)] != \
        [tr.pool_index(2, i, 5) for i in range(20)]


def test_stream_has_no_step_at_file_seams():
    """The sines close whole cycles per file: across a seam the stream
    moves no more than the noise and one sample of the fastest tone."""
    cols = list(range(24))
    s = tr.stream_columns(CFG, MIX, 4, 6, cols)
    rows = tr.file_rows(CFG, MIX)
    d = np.abs(np.diff(s, axis=0))
    seams = d[rows - 1::rows]
    assert seams.max() <= d.max()


def test_stager_delivers_in_order_and_stops(tmp_path):
    st = tr.Stager(CFG, MIX, 11, str(tmp_path))
    try:
        names = st.deliver(2) + st.deliver(1)
        assert names == [tr.file_name(i) for i in range(3)]
        assert sorted(os.listdir(st.live_dir)) == names
        rows = tr.file_rows(CFG, MIX)
        with open(os.path.join(st.live_dir, names[2]), "rb") as fh:
            raw = fh.read()
        k = tr.pool_index(11, 2, MIX["pool"])
        want = tr.make_payload(11, k, rows, 24, 1000.0)
        assert raw[64:] == want.tobytes()
    finally:
        st.close()
    assert not st.proc.is_alive()
