"""Smoke run of the system's main paths on a TPU, through the entry points
a user calls, at published widths.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the LGR all-reduce only

One chip, in one process:

1. kernel parity: each compiled Pallas kernel of the main path
   (``env_mega_step``, ``pack_channels``, ``gae_norm``, ``nstep_returns``,
   ``paged_attention``) against its jnp reference, on the chip;
2. sync PPO: ``make_drl_train_step`` with the fused kernels, ShadowHand
   (Table-6 policy 211:512:512:512:256:20), 16384 envs, 16 steps/iter;
3. async A3C: ``make_async_runner(megakernel=True, use_fused_kernels=True)``
   on a blocking ring, Ant with 16384 envs on each of 2 serving GMIs, so
   ``collect_ring`` runs the Pallas env megakernel into ring slots;
4. serving: ``ServeEngine(decode_kernel=True)`` answers 4 requests on
   internlm2-1.8b at its published config (24 layers, d_model 2048, bf16),
   random weights from ``--seed``.

``--chips 4`` runs only the paper's multi-device path: ``lgr_allreduce``
through ``Communicator.allreduce`` for every feasible strategy on (4, 1)
and (2, 2) (gpu, inst) meshes of the four chips, at the Table-7 gradient
sizes, against the host-staged ``mpr_host`` oracle.

Every number printed is a smoke measurement on the named device, not a
benchmark result.  Any failed check raises, so the process exits non-zero;
without a TPU it exits non-zero before any phase.  The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

HI = "highest"


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileMeter:
    """Sums JAX's backend compile time (persistent-cache reads included)
    and counts persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def close(name, got, want, tol):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{name}: non-finite values")
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol, err_msg=name)
    return err


def require_kernel(text: str, what: str) -> None:
    """A compiled Pallas kernel lowers to a ``tpu_custom_call``; interpret
    mode (or an XLA twin) leaves none in the program."""
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{what}: no compiled Pallas kernel in program")


# ------------------------------------------------------------------ parity --
def phase_parity(num_envs=16384, T=16, slots=2, gae_envs=65536, seed=0):
    from repro.envs import make_env
    from repro.kernels import ops, ref
    from repro.kernels.channel_pack import pack_channels_xla
    from repro.rl.rollout import gae

    key = jax.random.key(seed)
    ks = jax.random.split(key, 8)
    res = {}

    # env megakernel: one step into ring slot 1 at row t_row of sentinel
    # rings (every untouched row and slot must keep the sentinel)
    t_row = min(5, T - 1)
    env = make_env("Ant", megakernel=True)
    mc, spec = env.mega, env.spec
    J, O, N = spec.act_dim, spec.obs_dim, num_envs
    state, obs = env.reset(ks[0], N)
    action = jax.random.normal(ks[1], (N, J))

    def rings():
        return {"obs": jnp.full((T, slots * N, O), -7.0),
                "actions": jnp.full((T, slots * N, J), -7.0),
                "rewards": jnp.full((T, slots * N), -7.0),
                "dones": jnp.full((T, slots * N), -7.0)}

    statics = dict(chain=mc.chain, task=mc.task, substeps=spec.substeps,
                   dt=spec.dt, max_episode_len=spec.max_episode_len)
    args = (mc.sensor, mc.tgt, mc.masses, mc.lengths)
    require_kernel(ops.env_mega_step.lower(
        *state, action, obs, rings(), t_row, 1, *args,
        **statics).as_text(), "env_mega_step")
    got = ops.env_mega_step(*state, action, obs, rings(), t_row, 1, *args,
                            **statics)
    with jax.default_matmul_precision(HI):
        want = ref.env_mega_step_ref(*state, action, obs, rings(), t_row,
                                     1, *args, **statics)
    err = max(close(f"env_mega_step[{i}]", g, w, 1e-4)
              for i, (g, w) in enumerate(zip(got[:10], want[:10])))
    for c in want[10]:
        err = max(err, close(f"env_mega_step ring {c}", got[10][c],
                             want[10][c], 1e-4))
    res["env_mega_step"] = err

    # ring pack: one push into slot 1 == the XLA twin, bit for bit
    pay = {"obs": jax.random.normal(ks[2], (T, N, O)),
           "actions": jax.random.normal(ks[3], (T, N, J)),
           "rewards": jax.random.normal(ks[4], (T, N)),
           "dones": (jax.random.uniform(ks[5], (T, N)) < 0.1
                     ).astype(jnp.float32),
           "bootstrap": jax.random.normal(ks[6], (N,)),
           "actor_version": jnp.int32(3)}

    def full_rings():
        r = rings()
        r["bootstrap"] = jnp.full((slots, N), -7.0)
        r["actor_version"] = jnp.full((slots, 1), -7, jnp.int32)
        return r

    require_kernel(ops.pack_channels.lower(full_rings(), pay, 1).as_text(),
                   "pack_channels")
    got = ops.pack_channels(full_rings(), pay, jnp.int32(1))
    want = pack_channels_xla(full_rings(), pay, jnp.int32(1))
    for c in want:
        np.testing.assert_array_equal(np.asarray(got[c]),
                                      np.asarray(want[c]), err_msg=c)
    res["pack_channels"] = 0.0

    # fused GAE (+ global normalization) and the n-step scan
    G = gae_envs
    r = jax.random.normal(ks[2], (T, G))
    v = jax.random.normal(ks[3], (T, G))
    d = (jax.random.uniform(ks[4], (T, G)) < 0.1).astype(jnp.float32)
    last = jax.random.normal(ks[5], (G,))
    require_kernel(ops.gae_norm.lower(r, v, d, last).as_text(), "gae_norm")
    advs, rets = ops.gae_norm(r, v, d, last)
    a_u, r_u = jax.jit(gae)(r, v, d, last)
    want_a = (a_u - a_u.mean()) / (a_u.std() + 1e-8)
    res["gae_norm"] = max(close("gae_norm advs", advs, want_a, 1e-4),
                          close("gae_norm returns", rets, r_u, 1e-4))
    require_kernel(ops.nstep_returns.lower(r, d, last).as_text(),
                   "nstep_returns")
    res["nstep_returns"] = close(
        "nstep_returns", ops.nstep_returns(r, d, last),
        jax.jit(ref.nstep_returns_ref)(r, d, last), 1e-4)

    # paged decode at internlm2-1.8b attention widths, bf16
    res["paged_attention"] = _paged_parity(seed)
    for k, e in res.items():
        log(f"parity {k}: max_abs_err={e!r}")
    return res


def _paged_parity(seed, B=4, H=16, KH=8, hd=128, page=16, M=64):
    from repro.kernels import ops, ref
    rng = np.random.default_rng(seed)
    P = B * M + 1                       # physical pages, page 0 = trash
    ks = jax.random.split(jax.random.key(seed + 1), 3)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, H, hd), dt)
    kp = jax.random.normal(ks[1], (P, page, KH, hd), dt)
    vp = jax.random.normal(ks[2], (P, page, KH, hd), dt)
    slot_pos = np.full((P, page), -1, np.int32)
    table = np.full((B, M), -1, np.int32)
    pos = np.zeros((B,), np.int32)
    perm = iter(rng.permutation(np.arange(1, P)))
    for b in range(B):
        pos[b] = int(rng.integers(1, M * page))
        for vpg in range(pos[b] // page + 1):
            pid = int(next(perm))
            table[b, vpg] = pid
            hi = min(page, pos[b] + 1 - vpg * page)
            slot_pos[pid, :hi] = vpg * page + np.arange(hi)
    sp, tb, ps = jnp.asarray(slot_pos), jnp.asarray(table), jnp.asarray(pos)
    require_kernel(ops.paged_attention.lower(q, kp, vp, sp, tb, ps).as_text(),
                   "paged_attention")
    got = ops.paged_attention(q, kp, vp, sp, tb, ps)
    with jax.default_matmul_precision(HI):
        want = jax.jit(ref.paged_attention_ref)(q, kp, vp, sp, tb, ps)
    return close("paged_attention", got, want, 2e-2)


# -------------------------------------------------------------- sync PPO ---
def phase_ppo(num_envs=16384, num_steps=16, iters=3, seed=0):
    from repro.envs import make_env
    from repro.launch.steps import make_drl_train_step
    from repro.rl.ppo import PPOConfig, init_train

    env = make_env("ShadowHand")
    step, cfg = make_drl_train_step(
        env, PPOConfig(num_steps=num_steps, use_fused_kernels=True))
    params, opt, es, obs = init_train(jax.random.key(seed), env,
                                      env.spec.policy_dims, num_envs)
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    compiled = step.lower(params, opt, es, obs, key).compile()
    compile_s = time.perf_counter() - t0
    require_kernel(compiled.as_text(), "PPO train step (gae_scan)")
    params, opt, es, obs, key, m = compiled(params, opt, es, obs, key)
    jax.block_until_ready(m)                              # first execution
    t0 = time.perf_counter()
    losses = []
    for _ in range(iters):
        params, opt, es, obs, key, m = compiled(params, opt, es, obs, key)
        losses.append(m["loss"])
    jax.block_until_ready(losses)
    dt = time.perf_counter() - t0
    losses = [float(x) for x in losses]
    if not all(np.isfinite(losses)):
        raise AssertionError(f"PPO loss not finite: {losses}")
    sps = iters * num_steps * num_envs / dt
    log(f"ppo ShadowHand num_envs={num_envs} num_steps={num_steps} "
        f"iters={iters}: compile_s={compile_s!r} steps_per_s={sps!r} "
        f"losses={losses}")
    return sps


# ------------------------------------------------------------- async A3C ---
def phase_a3c(num_envs=16384, num_steps=16, rounds=3, seed=0):
    from repro.core.placement import plan_async
    from repro.envs import make_env
    from repro.launch.steps import make_async_runner
    from repro.rl import rollout

    # 2 serving + 2 trainer GMIs; on one chip the GMIs are logical ids
    layout = plan_async(num_gpus=2, serving_gpus=1, gmis_per_gpu=2,
                        devices=list(range(4)), devices_per_gpu=2)
    runner = make_async_runner(make_env("Ant"), layout, megakernel=True,
                               use_fused_kernels=True, num_envs=num_envs,
                               num_steps=num_steps, seed=seed)
    if runner.overlap or not runner.env.megakernel:
        raise AssertionError("A3C smoke must run the blocking-ring "
                             "megakernel producer")
    _require_collect_ring_kernel(runner, rollout)
    times, losses = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        ls, _ = runner.round()
        times.append(time.perf_counter() - t0)
        losses += ls
    ls, _ = runner.finish()
    losses += ls
    if runner.trained_samples != runner.predictions:
        raise AssertionError(f"trained {runner.trained_samples} != "
                             f"predicted {runner.predictions}")
    if not losses or not all(np.isfinite(losses)):
        raise AssertionError(f"A3C losses not finite: {losses}")
    per_round = num_steps * num_envs * len(runner.serving_gmis)
    steady = times[1:] or times
    log(f"a3c Ant megakernel serving_gmis={len(runner.serving_gmis)} "
        f"num_envs={num_envs} rounds={rounds}: first_round_s={times[0]!r} "
        f"steady_round_s={min(steady)!r} "
        f"env_steps_per_s={per_round / min(steady)!r} "
        f"predictions={runner.predictions} "
        f"trained_samples={runner.trained_samples} batches={len(losses)}")
    return runner.trained_samples


def _require_collect_ring_kernel(runner, rollout):
    """Lower the producer program ``collect_ring`` dispatches and check
    the env step inside it is the compiled Pallas kernel."""
    from repro.kernels.ops import _interpret_default
    if _interpret_default():
        raise AssertionError("Pallas kernels would run in interpret mode")
    env, T, N = runner.env, runner.num_steps, runner.num_envs
    es, obs, k = runner.actors[runner.serving_gmis[0]]
    S = len(runner.serving_gmis)
    f32 = jnp.float32
    bufs = {"obs": jax.ShapeDtypeStruct((T, S * N, env.spec.obs_dim), f32),
            "actions": jax.ShapeDtypeStruct((T, S * N, env.spec.act_dim),
                                            f32),
            "rewards": jax.ShapeDtypeStruct((T, S * N), f32),
            "dones": jax.ShapeDtypeStruct((T, S * N), f32)}
    mc = env.mega
    text = rollout._collect_ring.lower(
        runner.actor_params, es, obs, k, bufs, jnp.int32(0), mc.sensor,
        mc.tgt, mc.masses, mc.lengths, chain=mc.chain, task=mc.task,
        substeps=env.spec.substeps, dt=env.spec.dt,
        max_episode_len=env.spec.max_episode_len, num_steps=T,
        use_pallas=True, interpret=False,
        policy_fn=rollout.policy_apply).as_text()
    require_kernel(text, "collect_ring (env megakernel)")


# --------------------------------------------------------------- serving ---
def phase_serve(seed=0, prompt_len=64, gen=16, page_size=16,
                num_pages=257, cfg=None):
    from repro.configs.internlm2_1_8b import FULL
    from repro.models import transformer as T
    from repro.serve import Request, ServeEngine

    cfg = cfg or FULL
    dt = jnp.dtype(cfg.dtype)

    @jax.jit
    def init(k):
        return jax.tree.map(
            lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
            else x, T.init_model(k, cfg))

    params = init(jax.random.key(seed))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    engine = ServeEngine(cfg, params, max_slots=4,
                         max_seq=prompt_len + gen + page_size,
                         page_size=page_size, num_pages=num_pages,
                         decode_kernel=True)
    rng = np.random.default_rng(seed)
    reqs = [Request(tokens=rng.integers(0, cfg.vocab_size, prompt_len),
                    max_new_tokens=gen, seed=seed + i) for i in range(4)]
    t0 = time.perf_counter()
    done = engine.serve(reqs)
    wall = time.perf_counter() - t0
    if len(done) != 4 or any(c.status != "ok" for c in done):
        raise AssertionError(f"serving: {[c.status for c in done]}")
    for c in done:
        if len(c.tokens) != gen or not all(0 <= t < cfg.vocab_size
                                           for t in c.tokens):
            raise AssertionError(f"serving: bad tokens {c.tokens}")
    tel = engine.telemetry
    log(f"serve {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"dtype={cfg.dtype} param_bytes={nbytes} decode_kernel=True "
        f"page_size={page_size} num_pages={num_pages}: requests=4 "
        f"prompt={prompt_len} gen={gen} wall_s_incl_compile={wall!r} "
        f"decode_s={tel.total_decode_s!r} prefill_s={tel.total_prefill_s!r} "
        f"first_tokens={[c.tokens[:4] for c in done]}")
    return done


# ------------------------------------------------------- four chips: LGR ---
SIZES = {"AT": 110_000, "HM": 290_000, "SH": 1_500_000}


def phase_lgr(devices=None, sizes=SIZES, seed=0):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.comm import Communicator, ReduceCostModel, mpr_host

    devices = list(devices if devices is not None else jax.devices())
    if len(devices) != 4:
        raise AssertionError(f"LGR phase needs 4 devices, got {devices}")
    rows = 0
    for shape in ((4, 1), (2, 2)):
        mesh = Mesh(np.array(devices).reshape(shape), ("gpu", "inst"))
        strategies = [s for s in ReduceCostModel().candidates(shape)
                      if s in ("mpr", "mrr", "har")]
        if not strategies:
            raise AssertionError(f"no LGR strategy for mesh {shape}")
        for bench, n in sizes.items():
            g = jax.random.normal(jax.random.key(seed), shape + (n,))
            grads = {"w": jax.device_put(
                g, NamedSharding(mesh, P("gpu", "inst")))}
            per_inst = [{"w": g[i]} for i in np.ndindex(*shape)]
            want = mpr_host(per_inst)["w"]
            for strat in strategies:
                comm = Communicator(strat, mesh=mesh)
                out = comm.allreduce(grads)["w"]
                held = {s.device for s in out.addressable_shards}
                if held != set(devices):
                    raise AssertionError(
                        f"{strat}{shape}: result shards on {held}, "
                        f"expected all 4 devices")
                err = 0.0
                for i in np.ndindex(*shape):
                    err = max(err, close(f"lgr {strat}{shape} {bench}",
                                         out[i], want, 1e-5))
                log(f"lgr mesh={shape} strategy={strat} {bench} "
                    f"params={n}: max_abs_err={err!r} "
                    f"shard_devices={len(held)}")
                rows += 1
    return rows


# ------------------------------------------------------------------ main ---
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 2
    devs = jax.devices()
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    meter = CompileMeter()
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phases = [("lgr", lambda: phase_lgr(devs[:4], seed=args.seed))]
    else:
        phases = [("parity", lambda: phase_parity(seed=args.seed)),
                  ("ppo", lambda: phase_ppo(seed=args.seed)),
                  ("a3c", lambda: phase_a3c(seed=args.seed)),
                  ("serve", lambda: phase_serve(seed=args.seed))]
    for name, run in phases:
        t = time.perf_counter()
        run()
        log(f"phase {name}: ok in {time.perf_counter() - t!r} s")
    log(f"compile: backend_compile_s={meter.seconds!r} "
        f"persistent_cache_hits={meter.hits} "
        f"total_wall_s={time.perf_counter() - t0!r}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
