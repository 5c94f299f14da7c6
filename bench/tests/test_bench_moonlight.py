"""Moonlight-16B-A3B as a GRPO-trained policy against its plain reference
(``reference/moonlight.py``), at a tiny size on the CPU: d_model 64,
8 routed experts of which 4 are held here, top-3, vocabulary 256, the
dense layer and one MoE layer, rows of 32 positions.

The weights, the MLA block, the stack's log-probabilities and the share
of the experts against the reference; the GRPO step's first three steps
through the harness (``run.run_cell``); the bfloat16 control and three
planted faults (a capacity-1.0 dispatch that drops overflow, routing
without the choice bias, the shared experts left out), each of which the
cell's limits must refuse."""
import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402

import run                                               # noqa: E402
from benchlib import training_check as tc                # noqa: E402
from drivers import lm_grpo                              # noqa: E402
from reference import moonlight as RM                    # noqa: E402

CELL = "moonlight_grpo_4x8k"
SEED = 2 ** 31 + 99
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 12, "kv_lora_rank": 32,
        "moe_intermediate_size": 32, "intermediate_size": 96,
        "router_experts": 8, "n_routed_experts": 4,
        "num_experts_per_tok": 3, "num_hidden_layers": 2,
        "vocab_size": 256}


def tiny(**over):
    cell = run.load_cell(CELL)
    cell.config = dict(cell.config, **dict(TINY, **over))
    cell.traffic = dict(cell.traffic, seq_len=32, prompt_len=4,
                        response_lens=[8, 12, 20, 28])
    return cell


def tiny_model(**over):
    cell = tiny(**over)
    return lm_grpo.model_config(cell.config), RM.widths(cell.config)


def init(key, cfg):
    from repro.models.transformer import init_latent_moe
    return jax.jit(lambda k: init_latent_moe(k, cfg))(key)


def test_config_file_is_the_programs_one_chip_cut():
    from repro.configs.moonlight_16b_a3b import EP8, FULL
    cfg = lm_grpo.model_config(run.load_cell(CELL).config)
    assert cfg.replace(name=EP8.name, source=EP8.source) == EP8
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "moonlight_16b_a3b_ep8")
    conf = json.loads((ROOT / entry["file"]).read_text())
    assert sorted(entry["reduced"]) == sorted(conf["reduced"])
    assert conf["published"] == {"num_hidden_layers": FULL.num_layers,
                                 "n_routed_experts": FULL.num_experts,
                                 "vocab_size": FULL.vocab_size}


def test_weights_are_the_references():
    cfg, w = tiny_model()
    key = jax.random.PRNGKey(3)
    prog = tc.leaves(init(key, cfg))
    ref = tc.leaves(jax.jit(lambda k: RM.init(k, w))(key))
    assert set(prog) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(prog[k], ref[k], err_msg=k)


@pytest.mark.parametrize("chunked", [False, True], ids=["direct", "chunked"])
def test_mla_block_matches_reference(chunked):
    from repro.models.attention import mla
    cfg, w = tiny_model()
    lp = jax.tree.map(lambda a: a[0],
                      init(jax.random.PRNGKey(4), cfg)["layers"])
    S = 48
    x = jax.random.normal(jax.random.PRNGKey(5), (2, S, cfg.d_model))
    got = jax.jit(lambda p, x: mla(
        p, x, jnp.arange(S), num_heads=cfg.num_heads,
        qk_nope_dim=cfg.qk_nope_head_dim, qk_rope_dim=cfg.qk_rope_head_dim,
        v_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, chunked_threshold=16 if chunked else 4096))(
            lp["mla"], x)
    want = jax.jit(jax.vmap(lambda x: RM.mla(lp["mla"], x, w)))(x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_stack_logprobs_match_reference():
    """The grouped kernel takes bf16 operands (the TPU's default precision
    for a float32 product), so the routed part agrees to bf16 rounding."""
    from repro.models.transformer import token_logprobs
    cfg, w = tiny_model()
    params = init(jax.random.PRNGKey(6), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 24), 0,
                                cfg.vocab_size)
    valid = jnp.ones(tokens.shape, bool)
    got, counters = jax.jit(lambda p: token_logprobs(
        p, cfg, tokens, valid, chunk=8))(params)
    want = jax.jit(jax.vmap(lambda t: RM.logprobs(params, t, w)))(tokens)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-2)
    assert int(counters["dropped"].sum()) == 0
    total = counters["assignments"].sum() + counters["offchip"].sum()
    assert int(total) == tokens.size * cfg.experts_per_token * (
        cfg.num_layers - cfg.first_dense_layers)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Two chips' shares of 4 experts each, with the shared experts
    (computed on every chip alike) counted once, give the layer the
    reference computes with all 8 experts held."""
    from repro.models import moe
    from repro.models.layers import mlp
    cfg, w = tiny_model(n_routed_experts=8)
    lp = jax.tree.map(lambda a: a[0], init(
        jax.random.PRNGKey(8), cfg)["layers"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 40, cfg.d_model))
    valid = jnp.ones((1, 40), bool)
    outs = []
    for r in range(2):
        share = dict(lp, experts=jax.tree.map(
            lambda a: a[4 * r:4 * r + 4], lp["experts"]))
        out, c = jax.jit(lambda p: moe.routed_moe_apply(
            p, x, valid, top_k=3, routed_scale=cfg.routed_scale,
            first_expert=4 * r))(share)
        outs.append(out[0])
        assert int(c["assignments"].sum() + c["offchip"]) == 40 * 3
    shared = mlp(lp["shared"], x[0])
    want = jax.jit(lambda p: RM.moe(p, x[0], w))(lp)
    np.testing.assert_allclose(outs[0] + outs[1] - shared, want,
                               rtol=0, atol=3e-2 * float(jnp.abs(want).max()))


# -------------------------------------------- the step against the reference
@pytest.fixture(scope="module")
def reference():
    cell = tiny()
    return lm_grpo.reference_first_steps(cell.config, cell.traffic, SEED)


def first_steps(cell):
    d = lm_grpo.Driver(cell.config, cell.traffic, SEED)
    d.first_steps(cell.traffic["first_steps"])
    return d


def judged(prog, reference, cell, extra=None):
    numbers = tc.numbers(prog, reference)
    numbers.update(extra or {})
    return run.judge(numbers, cell.limits["numbers"])


def test_tiny_cell_runs_through_the_harness():
    """The step's first three steps agree with the reference well inside
    the cell's limits, and nothing is dropped."""
    cell = tiny()
    res = run.run_cell(cell, SEED, 0.5, False, jax.devices()[:1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    checks = res["checks"]
    assert set(checks) == set(cell.limits["numbers"])
    assert checks["dropped_assignments"]["value"] == 0
    for k in ("grad_gap", "delta_gap"):
        assert checks[k]["value"] < checks[k]["limit"] / 10
    assert {k: v["unit"] for k, v in res["metrics"].items()} == \
        {"train_samples_per_s": "samples/s", "setup_s": "s"}


def capacity_dispatch(mp, router_experts=TINY["router_experts"]):
    """The capacity-bucketed dispatch at capacity factor 1.0: each held
    expert takes at most tokens x k / router_experts assignments, in token
    order; the rest drop (and are counted)."""
    from repro.models import moe
    orig = moe._held_experts

    def capped(experts_p, x, local, gates, sizes):
        E = experts_p["wi"].shape[0]
        T, k = local.shape
        cap = int(T * k / router_experts)
        onehot = jax.nn.one_hot(local.reshape(-1), E, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, 0) - 1) * onehot
        keep = (onehot.sum(-1) == 1) & (pos.sum(-1) < cap)
        local2 = jnp.where(keep.reshape(T, k), local, E)
        sizes2 = jnp.minimum(sizes, cap)
        out, _ = orig(experts_p, x, local2,
                      jnp.where(keep.reshape(T, k), gates, 0.0), sizes2)
        return out, jnp.sum(sizes - sizes2).astype(jnp.int32)
    mp.setattr(moe, "_held_experts", capped)


def unbiased_routing(mp):
    from repro.models import moe
    orig = moe._route
    mp.setattr(moe, "_route", lambda p, *a: orig(
        dict(p, bias=jnp.zeros_like(p["bias"])), *a))


def no_shared_experts(mp):
    from repro.models import moe
    mp.setattr(moe, "mlp", lambda p, x: jnp.zeros_like(x))


FAULTS = [capacity_dispatch, unbiased_routing, no_shared_experts]


@pytest.mark.parametrize("plant", FAULTS, ids=[f.__name__ for f in FAULTS])
def test_fault_is_not_correct(plant, reference, monkeypatch):
    plant(monkeypatch)
    cell = tiny()
    d = first_steps(cell)
    ok, checks = judged(d.first, reference, cell, d.end_window())
    assert not ok
    assert any(c["value"] > c["limit"] for c in checks.values())


def test_control_is_not_correct(reference):
    cell = tiny()
    ctrl = lm_grpo.reference_first_steps(cell.config, cell.traffic, SEED,
                                         dtype="bfloat16")
    extra = {k: 0.0 for k in cell.limits["numbers"]
             if k not in tc.NUMBERS}
    assert not judged(ctrl, reference, cell, extra)[0]


# ------------------------------------------ operations, bytes and readers --
def dot_flops(fn, *args):
    from repro.launch.hlo_analysis import analyze
    return analyze(jax.jit(fn).lower(*args).compile().as_text())["dot_flops"]


def test_forward_flops_match_compiled_dot_flops(monkeypatch):
    """``lm_flops``' trunk and head against the dot FLOPs XLA compiles for
    the stack's forward pass, with every position real and the routed
    experts replaced by a stand-in without products (their term is pinned
    below).  The compiled pass attends every (query, key) pair of the
    square, not the causal half the algorithm needs, and applies the head
    at every position but the last."""
    from benchlib import lm_flops
    from repro.models import moe
    from repro.models.transformer import token_logprobs
    monkeypatch.setattr(moe, "_held_experts", lambda e, x, local, g, n: (
        x * g.sum(-1, keepdims=True), jnp.int32(0)))
    cfg, w = tiny_model()
    G, S = 2, 24
    params = init(jax.random.PRNGKey(10), cfg)
    tokens = jnp.zeros((G, S), jnp.int32)
    got = dot_flops(lambda p: token_logprobs(
        p, cfg, tokens, jnp.ones((G, S), bool))[0], params)
    assert got == lm_flops.trunk_forward(w, G * S, G * S * S) \
        + lm_flops.head_forward(w, G * (S - 1))


def test_routed_flops_match_the_grouped_oracle():
    from benchlib import lm_flops
    from repro.kernels import ref
    _, w = tiny_model()
    A = 40
    xs = jnp.ones((A, w["D"]))
    wi = jnp.ones((w["Eh"], w["D"], w["F"]))
    wg = 2 * wi
    wo = jnp.ones((w["Eh"], w["F"], w["D"]))
    sizes = jnp.array([10, 10, 10, 10], jnp.int32)

    def experts(xs, wi, wg, wo):
        h = ref.gmm_ref(xs, wi, sizes) * ref.gmm_ref(xs, wg, sizes)
        return ref.gmm_ref(h, wo, sizes)
    # the oracle multiplies each row by its group's matrix: 2 A K N a call
    assert dot_flops(experts, xs, wi, wg, wo) == \
        lm_flops.routed_forward(w, A)


def test_step_flops_count_the_causal_half_of_real_tokens():
    from benchlib import lm_flops
    _, w = tiny_model()
    fwd = (lm_flops.trunk_forward(w, 10 + 6, 55 + 21)
           + lm_flops.routed_forward(w, 30) + lm_flops.head_forward(w, 9))
    assert lm_flops.step_flops(w, [10, 6], [5, 4], 30) == 4 * fwd


class _Op:
    def __init__(self, name, seconds, pallas=True):
        self.name, self.seconds, self.pallas = name, seconds, pallas


class _Reduction:
    def __init__(self, ops):
        self.ops = ops

    def kernel_seconds(self, pred):
        picked = [o for o in self.ops if pred(o)]
        return sum(o.seconds for o in picked), len(picked)


def test_readers():
    from types import SimpleNamespace

    from benchlib import lm_flops
    from benchlib.peaks import peaks_for
    from metrics import expert_gmm_roofline, lm_step_mfu
    peaks = peaks_for("TPU v5 lite")
    w = RM.widths(run.load_cell(CELL).config)
    rows = 2000.0
    mix = lm_flops.gmm_calls(w)
    n_gmm = sum(k == "gmm" for k, _, _ in mix)
    least = {kind: sum(max(f / peaks["bf16_flops"],
                           b / peaks["hbm_bytes_per_s"])
                       for f, b in (lm_flops.gmm_call_cost(k, rows, K, N,
                                                           w["Eh"])
                                    for k, K, N in mix if k == kind))
             for kind in ("gmm", "tgmm")}
    # one layer-minibatch's calls, each taking twice its least time
    ops = [_Op(f"gmm.{i}", 2 * least["gmm"] / n_gmm) for i in range(n_gmm)]
    ops += [_Op(f"tgmm.{i}", 2 * least["tgmm"] / (len(mix) - n_gmm))
            for i in range(len(mix) - n_gmm)]
    ops += [_Op("fusion.1", 1.0, pallas=False), _Op("gmm.9", 1.0, False)]
    ctx = SimpleNamespace(
        reduction=_Reduction(ops), peaks=peaks, chips=1,
        kernel_shapes={"expert_gmm": {"widths": w, "rows_per_call": rows}},
        traced={"seconds": 2.0, "samples": 1000, "units": 1},
        flops_per_sample=1e9)
    assert expert_gmm_roofline.read(ctx) == pytest.approx(50.0)
    assert lm_step_mfu.read(ctx) == pytest.approx(
        100 * 1000 * 1e9 / (2.0 * peaks["bf16_flops"]))
    ctx.reduction = _Reduction([])
    assert expert_gmm_roofline.read(ctx) is None
