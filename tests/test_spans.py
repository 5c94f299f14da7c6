"""Host spans (``repro.spans``) and where the program records them: the
async A3C round and the MCC flush, plus the PPO step's named scopes."""
import re

import jax
import pytest

from repro import spans
from repro.envs import make_env


@pytest.fixture(autouse=True)
def empty_ring():
    spans.clear()
    yield
    spans.clear()


def test_nesting_gives_parent_indices():
    with spans.span("outer", k=1) as outer:
        with spans.span("a"):
            with spans.span("a.inner"):
                pass
        with spans.span("b"):
            pass
    with spans.span("next"):
        pass
    recs = spans.records()
    assert [r[0] for r in recs] == ["outer", "a", "a.inner", "b", "next"]
    assert [r[3] for r in recs] == [-1, 0, 1, 0, -1]
    for name, start, end, _ in recs:
        assert end >= start
    o = recs[0]
    assert o[1] <= recs[1][1] and recs[3][2] <= o[2]
    assert outer.seconds == pytest.approx((o[2] - o[1]) * 1e-9)


def test_open_span_has_no_end_until_it_closes():
    with spans.span("open"):
        (rec,) = spans.records()
        assert rec[0] == "open" and rec[2] is None
    assert spans.records()[0][2] is not None


def test_span_closes_on_an_exception():
    with pytest.raises(ValueError):
        with spans.span("outer"):
            with spans.span("raises"):
                raise ValueError
    with spans.span("after"):
        pass
    recs = spans.records()
    assert all(r[2] is not None for r in recs)
    assert [r[3] for r in recs] == [-1, 0, -1]      # the stack unwound


def test_ring_drops_the_oldest_records():
    n = spans.CAPACITY + 5
    for i in range(n):
        with spans.span(f"s{i}"):
            pass
    recs = spans.records()
    assert len(recs) == spans.CAPACITY
    assert recs[0][0] == "s5" and recs[-1][0] == f"s{n - 1}"
    # parents are indices into what the ring still holds
    with spans.span("root"):
        for _ in range(3):
            with spans.span("child"):
                pass
    recs = spans.records()
    assert recs[-4][0] == "root"
    assert [r[3] for r in recs[-3:]] == [spans.CAPACITY - 4] * 3
    spans.clear()
    assert spans.records() == []


def test_dropped_parent_reads_minus_one():
    with spans.span("gone"):
        for i in range(spans.CAPACITY):
            with spans.span("child"):
                pass
    recs = spans.records()
    assert recs[0][0] == "child" and recs[0][3] == -1


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData
    with spans.span("untraced"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    with spans.span("traced.outer", round=3):
        with spans.span("traced.inner"):
            jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    with spans.span("after.trace"):
        pass
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {ev.name for p in ProfileData.from_file(str(path)).planes
             if p.name.startswith("/host:") for ln in p.lines
             for ev in ln.events}
    assert {"traced.outer", "traced.inner"} <= names
    assert "untraced" not in names
    assert [r[0] for r in spans.records()] == [
        "untraced", "traced.outer", "traced.inner", "after.trace"]


# ------------------------------------------------------ async A3C round --
def _mega_runner(**kw):
    from repro.core.placement import plan_async
    from repro.launch.steps import make_async_runner
    # the benchmark's layout: 2 serving GMIs sharing one ring, 1 trainer
    layout = plan_async(2, 1, 2, devices=list(range(4)), devices_per_gpu=2)
    runner = make_async_runner(make_env("Ant"), layout, megakernel=True,
                               num_envs=8, num_steps=2, **kw)
    assert runner.serving_gmis == [0, 1] and not runner.overlap
    return runner


def _under(recs, i):
    """Names of the records below record ``i``."""
    def inside(j):
        while j >= 0:
            if j == i:
                return True
            j = recs[j][3]
        return False
    return sorted(r[0] for j, r in enumerate(recs)
                  if j != i and inside(recs[j][3]))


def test_megakernel_round_records_its_spans():
    runner = _mega_runner()
    runner.round()                          # compiles
    spans.clear()
    losses, _ = runner.round()
    assert len(losses) == 1
    recs = spans.records()
    rounds = [i for i, r in enumerate(recs) if r[0] == "a3c.round"]
    assert len(rounds) == 1 and recs[rounds[0]][3] == -1
    assert _under(recs, rounds[0]) == sorted(
        ["a3c.serve", "a3c.serve", "mcc.flush", "a3c.train", "a3c.update",
         "host_read", "host_read"])
    train = next(i for i, r in enumerate(recs) if r[0] == "a3c.train")
    assert _under(recs, train) == ["a3c.update", "host_read", "host_read"]


def test_nonfinite_guard_reads_the_loss():
    runner = _mega_runner()
    runner.nonfinite_guard = True
    runner.round()
    spans.clear()
    runner.round()
    assert [r[0] for r in spans.records()].count("host_read") == 3


class _Recorder:
    """Stands in for the controller: keeps what each round reports."""
    communicator = None

    def __init__(self):
        self.dts = []

    def observe_pipeline(self, pipeline, samples, dt):
        self.dts.append(dt)
        return None


def test_controller_dt_and_flush_seconds_are_the_spans():
    runner = _mega_runner()
    runner.controller = _Recorder()
    runner.round()
    spans.clear()
    runner.pipe.take_transfer_samples()
    runner.round()
    recs = spans.records()
    (rnd,) = [r for r in recs if r[0] == "a3c.round"]
    (flush,) = [r for r in recs if r[0] == "mcc.flush"]
    assert runner.controller.dts[-1] == pytest.approx(
        (rnd[2] - rnd[1]) * 1e-9, rel=0, abs=1e-12)
    ((seconds, nbytes),) = runner.pipe.take_transfer_samples()
    assert seconds == pytest.approx((flush[2] - flush[1]) * 1e-9, rel=0,
                                    abs=1e-12)
    assert nbytes > 0


# ------------------------------------------------------ PPO named scopes --
def test_ppo_step_names_its_phases_in_the_compiled_program():
    from repro.rl.ppo import PPOConfig, init_train, make_train_step
    env = make_env("Ant")
    cfg = PPOConfig(num_steps=2, num_epochs=1, num_minibatches=2,
                    use_fused_kernels=True)
    step = make_train_step(env, cfg)
    p, o, es, obs = init_train(jax.random.PRNGKey(0), env,
                               env.spec.policy_dims, 8)
    text = step.lower(p, o, es, obs, jax.random.PRNGKey(1)) \
        .compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("ppo/collect", "ppo/gae", "ppo/shuffle", "ppo/loss_grad",
                  "ppo/adam"):
        assert any(f"/{scope}/" in n for n in names), scope
    assert any(re.search(r"/ppo/collect/.*/policy/", n) for n in names)
    assert any(re.search(r"/ppo/collect/.*/env_step/", n) for n in names)
