"""Table 7: LGR vs the MPR baseline — now per-strategy, including the
3-axis (gpu, inst, dev) mesh of multi-device GMIs.

Layouts (8 fake host devices): 2G2T, 2G3T, 4G2T and the multi-device
2G2T2D grid; policy sizes AT ~1.1e5, HM ~2.9e5, SH ~1.5e6 parameters (the
Table-7/8 gradient sizes).  Every feasible in-SPMD schedule is timed per
layout (one row per strategy) against the host-staged mpr baseline, with
the Table-2 model's predicted speedup alongside.

Runs in a subprocess on the CPU backend with 8 host devices
(``JAX_PLATFORMS=cpu`` set explicitly), so the rows time EMULATED
collectives, never a chip, and the child never competes with the parent
for an accelerator.  A failed child raises.  Under
``benchmarks/run.py --quick`` these rows land in BENCH_lgr.json and sit
behind the standard >2x regression gate.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit

_CHILD = textwrap.dedent("""
    import json, sys, time
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh
    sys.path.insert(0, "src")
    from repro.comm import (ReduceCostModel, lgr_allreduce, mpr_host,
                            select_reduction_strategy)

    SIZES = {"AT": 110_000, "HM": 290_000, "SH": 1_500_000}
    LAYOUTS = {"2G2T": (2, 2), "2G3T": (2, 3), "4G2T": (4, 2),
               "2G2T2D": (2, 2, 2)}
    AXES = ("gpu", "inst", "dev")
    CM = ReduceCostModel()
    out = {}
    for lname, shape in LAYOUTS.items():
        n = int(np.prod(shape))
        devs = np.array(jax.devices()[:n]).reshape(shape)
        mesh = Mesh(devs, AXES[:len(shape)])
        g, t = shape[0], shape[1]
        mpl = [[gi*t + i for i in range(t)] for gi in range(g)]
        alg1 = select_reduction_strategy(mpl)
        strategies = [s for s in CM.candidates(shape) if s != "mpr"]
        for bench, nparam in SIZES.items():
            grads = {"w": jax.random.normal(jax.random.key(0),
                                            shape + (nparam,))}
            per_inst = [jax.tree.map(lambda x, i=i: x[i], grads)
                        for i in np.ndindex(*shape)]
            def best_of(fn, reps):
                best = float("inf")
                for _ in range(reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn())
                    best = min(best, time.perf_counter() - t0)
                return best * 1e6
            us_mpr = best_of(lambda: mpr_host(per_inst), 3)
            for strat in strategies:
                def run_lgr():
                    return lgr_allreduce(grads, mesh, strat)
                jax.block_until_ready(run_lgr())     # compile
                # best-of-N: scheduler noise on emulated host collectives
                # dwarfs the mean; the min is the honest trajectory row
                us_lgr = best_of(run_lgr, 7)
                out[f"{lname}_{bench}_{strat}"] = {
                    "strategy": strat, "us_lgr": us_lgr, "us_mpr": us_mpr,
                    "alg1": alg1, "shape": list(shape)}
    print(json.dumps(out))
""")


def run():
    from repro.comm import ReduceCostModel

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    if proc.returncode != 0:
        raise RuntimeError(f"bench_lgr child exited {proc.returncode}:\n"
                           f"{proc.stderr[-2000:]}")
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    sizes = {"AT": 110_000, "HM": 290_000, "SH": 1_500_000}
    cm = ReduceCostModel()
    for key, rec in data.items():
        lname, bench, strat = key.split("_")
        shape = tuple(rec["shape"])
        nbytes = sizes[bench] * 4
        # ReduceCostModel.time reads the dev axis straight off the grid
        pred_mpr = cm.time("mpr", shape, nbytes) * 1e6
        pred = cm.time(strat, shape, nbytes) * 1e6
        mark = "alg1" if strat == rec["alg1"] else "alt"
        emit(f"lgr_{key}", rec["us_lgr"],
             f"{mark}_mpr_us={rec['us_mpr']:.0f}_speedup="
             f"{rec['us_mpr'] / rec['us_lgr']:.2f}x_model_speedup="
             f"{pred_mpr / pred:.2f}x")
