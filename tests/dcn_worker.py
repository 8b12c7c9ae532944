"""Worker process for the real 2-process DCN test (test_distributed).

Run with COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID in the
environment on the CPU backend (4 virtual devices per process). Builds
the global (2, 4) mesh across both processes and runs collectives in
both mesh directions — psum reductions and the ppermute halo exchange —
over the distributed runtime that jax.distributed.initialize set up.
"""

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpudas.parallel.distributed import (  # noqa: E402
    global_mesh_devices,
    initialize_multihost,
    is_distributed,
)


def main():
    assert initialize_multihost() is True, "env config missing"

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import multihost_utils
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpudas.parallel.halo import exchange_halo_time

    assert jax.process_count() == 2, jax.process_count()
    assert is_distributed()
    devs = np.array(global_mesh_devices())
    assert devs.size == 8, devs
    # time axis spans the two processes: rows 0-3 on process 0, 4-7 on
    # process 1 — every "time" collective crosses the DCN boundary
    mesh = Mesh(devs.reshape(2, 4), ("time", "ch"))

    T, C = 16, 8
    global_data = np.arange(T * C, dtype=np.float32).reshape(T, C)
    sharding = NamedSharding(mesh, P("time", "ch"))
    arr = jax.make_array_from_callback(
        global_data.shape, sharding, lambda idx: global_data[idx]
    )

    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("time", "ch"),),
        out_specs=P(),
        check_vma=False,
    )
    def total(block):
        return jax.lax.psum(jax.lax.psum(jnp.sum(block), "time"), "ch")

    val = float(total(arr))
    expected = float(global_data.sum())
    assert abs(val - expected) < 1e-3, (val, expected)

    halo = 2

    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("time", "ch"),),
        out_specs=P("time", "ch"),
        check_vma=False,
    )
    def left_shift(block):
        padded = exchange_halo_time(block, halo, axis_name="time", n_shards=2)
        return padded[: block.shape[0]]

    out = multihost_utils.process_allgather(left_shift(arr), tiled=True)
    want = np.zeros_like(global_data)
    want[halo:] = global_data[:-halo]  # stream start receives zeros
    assert np.array_equal(out, want), (out[:4], want[:4])

    # the PRODUCT engine's sharded cascade across the DCN boundary:
    # the compiled shard_map step (time sharding spans the two
    # processes, so its halo ppermute crosses DCN) must be bit-equal
    # to the single-process cascade (BASELINE config 5)
    from tpudas.ops.fir import cascade_decimate, design_cascade
    from tpudas.parallel.pipeline import (
        _build_sharded_cascade_fn,
        sharded_cascade_layout,
    )

    plan = design_cascade(100.0, 20, 0.45, 4)
    n_out = 800  # each shard's halo (filter support) must fit its block
    Cc = 8
    layout = sharded_cascade_layout(
        mesh, plan, plan.delay, n_out,
        n_out * plan.ratio, n_ch_local=Cc // 4, engine="xla",
    )
    assert layout is not None, "2-shard layout must fit this window"
    n_loc, t_local, halo_c = layout
    T_target = 2 * t_local
    rng = np.random.default_rng(0)
    x_np = rng.standard_normal((T_target, Cc)).astype(np.float32)
    x_glob = jax.make_array_from_callback(
        x_np.shape, sharding, lambda idx: x_np[idx]
    )
    step = _build_sharded_cascade_fn(
        plan, n_loc, halo_c, "xla", mesh, "time", "ch"
    )
    got = multihost_utils.process_allgather(step(x_glob), tiled=True)
    ref = np.asarray(
        cascade_decimate(x_np, plan, plan.delay, 2 * n_loc, "xla")
    )
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert np.array_equal(got, ref), np.abs(got - ref).max()

    print(f"DCN_WORKER_OK pid={jax.process_index()}", flush=True)


if __name__ == "__main__":
    main()
