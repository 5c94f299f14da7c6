"""Algorithm 2 in action: the profiling-based (GMIperGPU, num_env) search
with the real PPO profiler (reduced sweep for CPU budget)."""
from __future__ import annotations

import time

from benchmarks.common import emit
from repro.core.selection import explore, make_ppo_profiler


def run(bench: str = "Ant"):
    profile = make_ppo_profiler(iters=1, mem_budget_bytes=32e9)
    t0 = time.perf_counter()
    trace = explore(profile, bench, num_gpu=4,
                    gmi_per_gpu_range=(4, 2, 1),
                    num_env_sweep=(128, 256, 512, 1024))
    dt = time.perf_counter() - t0
    ne, gpg = trace.best_config
    emit(f"selection_{bench}", dt * 1e6,
         f"best_num_env={ne}_best_GMIperGPU={gpg}"
         f"_proj_steps_per_s={trace.best_throughput:.0f}"
         f"_points={len(trace.points)}")
