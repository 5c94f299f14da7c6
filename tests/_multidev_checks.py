"""Multi-device checks, run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (never in the main
pytest process — smoke tests must see one device)."""
import os
import sys

assert "--xla_force_host_platform_device_count=8" in \
    os.environ.get("XLA_FLAGS", ""), "run via test_dist_multidev.py"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.comm import lgr_allreduce, mpr_host  # noqa: E402


def check_lgr_equivalence():
    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("gpu", "inst"))
    key = jax.random.key(0)
    grads = {"w": jax.random.normal(key, (2, 4, 33, 7)),   # odd sizes: pad path
             "b": jax.random.normal(key, (2, 4, 11))}
    expect = jax.tree.map(lambda g: np.broadcast_to(
        np.asarray(g).mean(axis=(0, 1)), g.shape), grads)
    for strat in ("mrr", "har", "mpr"):
        out = lgr_allreduce(grads, mesh, strat)
        for k in grads:
            np.testing.assert_allclose(np.asarray(out[k]), expect[k],
                                       rtol=1e-5, atol=1e-5)
    print("lgr equivalence ok")


def check_har_equals_mrr_2x2():
    """Regression (ISSUE 1): HAR and MRR must agree numerically on a 2x2
    mesh — the smallest layout where the hierarchical schedule's
    scatter/psum/gather path differs from the flat ring."""
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("gpu", "inst"))
    key = jax.random.key(7)
    grads = {"w": jax.random.normal(key, (2, 2, 17, 5)),   # pad path (17)
             "b": jax.random.normal(key, (2, 2, 8))}       # exact path
    har = lgr_allreduce(grads, mesh, "har")
    mrr = lgr_allreduce(grads, mesh, "mrr")
    for k in grads:
        np.testing.assert_allclose(np.asarray(har[k]), np.asarray(mrr[k]),
                                   rtol=1e-6, atol=1e-6)
    print("har == mrr on 2x2 ok")


def check_comm_schedule_parity_vs_host_oracle():
    """Every schedule (2-level and 3-level) must match the mpr_host host
    oracle on 2x2 and 2x2x2 device grids, for both average and raw-sum
    semantics (ISSUE 3 satellite: single average switch)."""
    key = jax.random.key(11)
    grids = [((2, 2), ("gpu", "inst"), ("mrr", "har", "mpr")),
             ((2, 2, 2), ("gpu", "inst", "dev"),
              ("mrr", "har", "har3", "mpr"))]
    for shape, axes, strategies in grids:
        n = int(np.prod(shape))
        devs = np.array(jax.devices()[:n]).reshape(shape)
        mesh = Mesh(devs, axes)
        grads = {"w": jax.random.normal(key, shape + (33, 7)),  # pad path
                 "b": jax.random.normal(key, shape + (8,))}     # exact path
        idx = list(np.ndindex(*shape))
        per_inst = [jax.tree.map(lambda x, i=i: x[i], grads) for i in idx]
        want_mean = mpr_host(per_inst)
        want_sum = mpr_host(per_inst, average=False)
        for strat in strategies:
            out = lgr_allreduce(grads, mesh, strat)
            out_sum = lgr_allreduce(grads, mesh, strat, average=False)
            for k in grads:
                got = np.asarray(out[k])[(0,) * len(shape)]
                np.testing.assert_allclose(got, want_mean[k],
                                           rtol=1e-5, atol=1e-5)
                # every replica must agree
                np.testing.assert_allclose(
                    np.asarray(out[k]),
                    np.broadcast_to(want_mean[k], out[k].shape),
                    rtol=1e-5, atol=1e-5)
                got_sum = np.asarray(out_sum[k])[(0,) * len(shape)]
                np.testing.assert_allclose(got_sum, want_sum[k],
                                           rtol=1e-5, atol=1e-5)
        print(f"comm parity ok on {shape}")


def check_multi_device_gmi_end_to_end():
    """Acceptance (ISSUE 3): the (gpu, inst, dev) mesh that
    GMIManager.instance_mesh builds for multi-device GMIs reduces
    correctly through the layout's Communicator — no ValueError, parity
    with the mpr_host oracle to <=1e-5."""
    from repro.comm import Communicator, ReduceCostModel
    from repro.core.gmi import GMIManager
    from repro.core.placement import Layout

    mgr = GMIManager(devices=jax.devices(), devices_per_gpu=4)
    for gid, gpu in [(0, 0), (1, 0), (2, 1), (3, 1)]:
        mgr.add_gmi(gid, "trainer", 0.5)     # 2 chips per GMI
        mgr.set_gpu(gid, gpu)
    layout = Layout("multidev", mgr, [], [0, 1, 2, 3])
    comm = Communicator.from_layout(layout, cost_model=ReduceCostModel(),
                                    with_mesh=True)
    assert comm.strategy == "har3", comm     # cost model picks 3-level
    assert comm.mesh.axis_names == ("gpu", "inst", "dev")
    key = jax.random.key(5)
    grads = {"w": jax.random.normal(key, (2, 2, 2, 17, 3)),
             "b": jax.random.normal(key, (2, 2, 2, 5))}
    out = comm.allreduce(grads)
    per_inst = [jax.tree.map(lambda x, i=i: x[i], grads)
                for i in np.ndindex(2, 2, 2)]
    want = comm.reduce_host(per_inst)
    for k in grads:
        np.testing.assert_allclose(
            np.asarray(out[k]),
            np.broadcast_to(want[k], out[k].shape), rtol=1e-5, atol=1e-5)
    # online strategy switch keeps reducing correctly on the same mesh
    for strat in ("mpr", "har"):
        out2 = comm.switch(strat).allreduce(grads)
        np.testing.assert_allclose(np.asarray(out2["w"]),
                                   np.broadcast_to(want["w"],
                                                   out2["w"].shape),
                                   rtol=1e-5, atol=1e-5)
    print("multi-device GMI communicator ok")


def check_mpr_host():
    key = jax.random.key(1)
    gs = [{"w": jax.random.normal(jax.random.fold_in(key, i), (5, 3))}
          for i in range(6)]
    red = mpr_host(gs)
    want = np.mean([np.asarray(g["w"]) for g in gs], axis=0)
    np.testing.assert_allclose(red["w"], want, rtol=1e-6)
    print("mpr host ok")


def check_checkpoint_restore_with_shardings():
    """Crash-resume on a real mesh (ISSUE 6): a checkpoint written from
    sharded arrays must restore bit-identically AND land on the given
    NamedShardings (device_put shard-by-shard on the 8-device mesh)."""
    import tempfile

    from repro.checkpoint import load, save

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    sh_w = NamedSharding(mesh, P("data", "model"))
    sh_b = NamedSharding(mesh, P())
    key = jax.random.key(3)
    tree = {"w": jax.device_put(jax.random.normal(key, (8, 6)), sh_w),
            "b": jax.device_put(jnp.arange(5, dtype=jnp.int32), sh_b)}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt_1")
        save(path, tree, step=1)
        like = {"w": jnp.zeros((8, 6)), "b": jnp.zeros(5, jnp.int32)}
        back = load(path, like, shardings={"w": sh_w, "b": sh_b})
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))
        assert back[k].sharding == tree[k].sharding, \
            (k, back[k].sharding, tree[k].sharding)
    assert len(back["w"].sharding.device_set) == 8
    print("sharded checkpoint restore ok")


def check_gmi_instance_mesh():
    from repro.core.gmi import GMIManager
    mgr = GMIManager(devices=jax.devices(), devices_per_gpu=4)
    for gid, gpu in [(0, 0), (1, 0), (2, 1), (3, 1)]:
        mgr.add_gmi(gid, "trainer", 0.5)
        mgr.set_gpu(gid, gpu)
    mesh = mgr.instance_mesh("trainer")
    # 2-device GMIs contribute BOTH chips along the trailing "dev" axis
    # (the old mesh silently kept only device_ids[0] of each instance)
    assert mesh.axis_names == ("gpu", "inst", "dev")
    assert mesh.devices.shape == (2, 2, 2)
    assert len({d.id for d in mesh.devices.reshape(-1)}) == 8
    sub = mgr.submesh(0)
    assert sub.devices.size == 2
    print("gmi meshes ok")


if __name__ == "__main__":
    check_lgr_equivalence()
    check_har_equals_mrr_2x2()
    check_comm_schedule_parity_vs_host_oracle()
    check_multi_device_gmi_end_to_end()
    check_mpr_host()
    check_checkpoint_restore_with_shardings()
    check_gmi_instance_mesh()
    print("MULTIDEV ALL OK")
