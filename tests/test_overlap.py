"""Collective-matmul overlap (ops/overlap.py), tunable remat, and the
hybrid step's schedule/donation contracts.

Tier-1 gates certified here (all on the 8-device virtual CPU mesh):

- the three ring primitives match the dense matmul, forward AND grads;
- hybrid training with FLAGS_mp_overlap on reproduces the non-overlap
  loss trajectory to rtol 1e-6 on >= 2 mesh factorizations (and both
  stay within the established 1e-3 of the single-device baseline);
- the ring actually engages: the overlap step's lowering contains
  collective_permute ops the GSPMD step does not have;
- steady-state overlap training is ONE compile (no_retrace);
- FLAGS_remat_policy leaves the ERNIE recompute() loss trajectory
  bitwise identical while the MEASURED per-step peak orders
  none >= dots_saveable >= full (strict at the ends), and the hybrid
  engine's per-block remat shows the same peak ordering;
- every hybrid engine-state leaf is donated: the compiled step aliases
  all params/buffers/opt-state outputs back onto their arguments;
- HybridParallelEngine.schedule() is pure metadata, stable across
  rebuilds of the same configuration.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
import __graft_entry__ as graft  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402,F401

import paddle_tpu as paddle  # noqa: E402 — installs the shard_map shim
from paddle_tpu import observe  # noqa: E402
from paddle_tpu.ops import overlap as ovl  # noqa: E402

@pytest.fixture(scope="module")
def baseline():
    losses, master = graft.baseline_losses()
    return losses, master


def _mesh(dp, mp):
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:dp * mp]).reshape(dp, mp)
    return Mesh(devs, (ovl.DP_AXIS, ovl.MP_AXIS))


def _need(n):
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices")


# ---------------------------------------------------------------------------
# ring primitives vs dense
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dp,mp", [(1, 4), (2, 4), (4, 2)])
def test_ring_primitives_match_dense(dp, mp):
    _need(dp * mp)
    mesh = _mesh(dp, mp)
    rs = np.random.RandomState(0)
    b, s, h, m = 4, 8, 16, 24
    x = rs.randn(b, s, h).astype(np.float32)
    w = rs.randn(h, m).astype(np.float32)
    dense = x @ w

    for prim in (ovl.matmul_allreduce, ovl.allgather_matmul,
                 ovl.matmul_reducescatter):
        got = jax.jit(lambda x, w, p=prim: p(x, w, mesh))(x, w)
        np.testing.assert_allclose(np.asarray(got), dense,
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=prim.__name__)

        def loss(x, w, p=prim):
            return (p(x, w, mesh) ** 2).sum()

        gx, gw = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, w)
        rx, rw = jax.grad(
            lambda x, w: ((x @ w) ** 2).sum(), argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{prim.__name__} dx")
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{prim.__name__} dw")


def test_ring_primitives_reject_indivisible_shapes():
    _need(4)
    mesh = _mesh(1, 4)
    x = np.zeros((2, 6, 16), np.float32)   # seq 6 % 4 != 0
    w = np.zeros((16, 24), np.float32)
    assert ovl.allgather_matmul(x, w, mesh) is None
    assert ovl.matmul_reducescatter(x, w, mesh) is None
    x2 = np.zeros((2, 8, 18), np.float32)  # h 18 % 4 != 0
    w2 = np.zeros((18, 24), np.float32)
    assert ovl.matmul_allreduce(x2, w2, mesh) is None


def test_supported_mesh_predicate():
    _need(8)
    assert ovl.supported(_mesh(2, 4))
    assert ovl.supported(_mesh(1, 8))
    assert not ovl.supported(_mesh(8, 1))      # mp == 1: nothing to hide
    assert not ovl.supported(None)
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
    assert not ovl.supported(
        Mesh(devs, (ovl.DP_AXIS, "pp", ovl.MP_AXIS)))  # pp > 1


# ---------------------------------------------------------------------------
# hybrid engine: overlap A/B parity + ring engagement + compile-once
# ---------------------------------------------------------------------------


def _hybrid_engine(dp, mp, master, sp):
    """fleet.init + a tiny GPT hybrid engine on the sweep state; caller
    must run inside _fleet_ctx (teardown)."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.hybrid import make_gpt_hybrid_engine

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": mp,
                               "pp_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()
    model, crit, cfg = graft._sweep_model(use_parallel=True,
                                          sequence_parallel=sp)
    graft._set_state(model, master)
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=model.parameters())
    eng = make_gpt_hybrid_engine(model, crit, opt, hcg)
    x, y = graft._sweep_batch(cfg)
    return eng, x, y


class _fleet_ctx:
    def __init__(self, overlap=None, remat=None):
        self.flags = {}
        if overlap is not None:
            self.flags["FLAGS_mp_overlap"] = overlap
        if remat is not None:
            self.flags["FLAGS_remat_policy"] = remat

    def __enter__(self):
        paddle.set_flags(self.flags)
        return self

    def __exit__(self, *exc):
        from paddle_tpu.distributed.topology import (
            set_hybrid_communicate_group,
        )

        set_hybrid_communicate_group(None)
        paddle.set_flags({"FLAGS_mp_overlap": False,
                          "FLAGS_remat_policy": "auto"})


def _hybrid_losses(dp, mp, master, sp, overlap):
    with _fleet_ctx(overlap=overlap):
        eng, x, y = _hybrid_engine(dp, mp, master, sp)
        return [float(eng.train_batch(x, y).item())
                for _ in range(graft._STEPS)]


@pytest.mark.parametrize("dp,mp,sp", [(1, 2, False), (2, 4, True)],
                         ids=["dp1.mp2", "dp2.mp4.seqpar"])
def test_overlap_loss_parity(dp, mp, sp, baseline):
    """The PR gate: overlap on/off trajectories agree to rtol 1e-6
    (measured: bitwise without sequence parallelism, ~1e-7 with — the
    reduce rings reassociate partial sums), and both stay within the
    established 1e-3 of the single-device baseline."""
    _need(dp * mp)
    ref, master = baseline
    base = _hybrid_losses(dp, mp, master, sp, overlap=False)
    over = _hybrid_losses(dp, mp, master, sp, overlap=True)
    np.testing.assert_allclose(over, base, rtol=1e-6)
    np.testing.assert_allclose(over, ref, rtol=1e-3)
    np.testing.assert_allclose(base, ref, rtol=1e-3)


def test_overlap_engages_ring_and_compiles_once(baseline):
    """Parity alone would pass if every routing guard silently fell back
    to GSPMD; the lowered overlap step must actually contain the ring's
    collective_permute ops (the GSPMD step has none — its collectives
    are inserted later by the SPMD partitioner). And steady-state
    overlap training stays ONE compile under no_retrace()."""
    _need(2)
    _, master = baseline
    with _fleet_ctx(overlap=False):
        eng, x, y = _hybrid_engine(1, 2, master, sp=True)
        eng.train_batch(x, y)
        with observe.suppress():
            base_ir = eng._step_fn.lower(*eng._step_protos).as_text()
    assert "collective_permute" not in base_ir

    observe.reset()
    with _fleet_ctx(overlap=True):
        eng, x, y = _hybrid_engine(1, 2, master, sp=True)
        with observe.no_retrace(allow=("hybrid_step",)):
            eng.train_batch(x, y)
        with observe.no_retrace():          # steady state: no recompiles
            for _ in range(2):
                eng.train_batch(x, y)
        with observe.suppress():
            over_ir = eng._step_fn.lower(*eng._step_protos).as_text()
    assert "collective_permute" in over_ir
    evs = observe.compile_events("hybrid_step")
    assert len(evs) == 1, [e["signature"] for e in evs]


def test_overlap_force_env_overrides_flag(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_MP_OVERLAP_FORCE", "off")
    paddle.set_flags({"FLAGS_mp_overlap": True})
    try:
        assert not ovl.enabled()
        monkeypatch.setenv("PADDLE_TPU_MP_OVERLAP_FORCE", "on")
        paddle.set_flags({"FLAGS_mp_overlap": False})
        assert ovl.enabled()
        monkeypatch.delenv("PADDLE_TPU_MP_OVERLAP_FORCE")
        assert not ovl.enabled()
    finally:
        paddle.set_flags({"FLAGS_mp_overlap": False})


# ---------------------------------------------------------------------------
# donation + schedule
# ---------------------------------------------------------------------------


def test_step_donation_complete(baseline):
    """Every engine-state leaf must be aliased arg<->output in the
    compiled hybrid step: the only unaliased output bytes are the
    scalar loss and the optimizer's step counters (measured: 140 B vs
    ~113 KB of state)."""
    _need(4)
    _, master = baseline
    with _fleet_ctx():
        eng, x, y = _hybrid_engine(2, 2, master, sp=False)
        eng.train_batch(x, y)
        ma = eng.memory_analysis()
    assert ma["alias"] > 0
    unaliased = ma["outputs"] - ma["alias"]
    assert 0 <= unaliased <= 1024, (
        f"{unaliased} unaliased output bytes — a state leaf lost its "
        f"donation (outputs={ma['outputs']}, alias={ma['alias']})")


def test_schedule_stable_across_rebuilds(baseline):
    _need(4)
    _, master = baseline

    def build_schedule():
        with _fleet_ctx():
            eng, x, y = _hybrid_engine(2, 2, master, sp=False)
            return eng.schedule(), eng.num_layers

    s1, num_layers = build_schedule()
    s2, _ = build_schedule()
    assert s1 == s2                      # stable across rebuilds
    names = [p["name"] for p in s1]
    assert names == (["embed"] + [f"block{i}" for i in range(num_layers)]
                     + ["head", "grad-reduce", "opt"])
    kinds = [p["kind"] for p in s1]
    assert kinds == (["embed"] + ["block"] * num_layers
                     + ["head", "collective", "opt"])
    blocks = [p for p in s1 if p["kind"] == "block"]
    assert [b["stage"] for b in blocks] == [0] * num_layers  # pp == 1
    # mp sharding is visible in the per-phase specs: some block param
    # carries the mp axis, and the embed phase holds the embeddings
    flat = [ax for spec in blocks[0]["params"].values()
            for entry in spec for ax in (
                entry if isinstance(entry, tuple) else (entry,))]
    assert ovl.MP_AXIS in flat
    assert any("embedding" in k for k in s1[0]["params"])
    reduce_phase = next(p for p in s1 if p["kind"] == "collective")
    assert reduce_phase["axes"] == (ovl.DP_AXIS,)
    assert s1[-1]["params"]                  # opt specs present


# ---------------------------------------------------------------------------
# tunable remat
# ---------------------------------------------------------------------------


def _ernie_remat_run(policy):
    from paddle_tpu.engine import Engine
    from paddle_tpu.nlp.transformers import (
        ErnieConfig, ErnieForPretraining, ErniePretrainingCriterion,
    )

    paddle.set_flags({"FLAGS_remat_policy": policy})
    try:
        paddle.seed(11)
        cfg = ErnieConfig(vocab_size=256, hidden_size=32, num_layers=2,
                          num_heads=4, ffn_hidden_size=64, max_seq_len=32,
                          dropout=0.0, use_parallel=False, recompute=True)
        model = ErnieForPretraining(cfg)
        crit = ErniePretrainingCriterion(cfg)
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=model.parameters())

        def loss_fn(outputs, mlm_labels):
            logits, nsp = outputs
            return crit(logits, nsp, mlm_labels)

        eng = Engine(model, opt, loss_fn)
        rs = np.random.RandomState(0)
        ids = rs.randint(0, cfg.vocab_size, (4, 32)).astype(np.int32)
        labels = ids.copy()
        labels[rs.rand(4, 32) > 0.3] = -100
        losses = [float(eng.train_batch(ids, labels).item())
                  for _ in range(3)]
        return losses, eng.memory_analysis()
    finally:
        paddle.set_flags({"FLAGS_remat_policy": "auto"})


def test_remat_policy_parity_and_peak_ordering_ernie():
    """FLAGS_remat_policy through recompute(): the loss trajectory is
    BITWISE identical across policies (remat replays the same math),
    while the MEASURED compiled peak orders none >= dots_saveable >=
    full — saving fewer residuals costs memory, saving more saves it."""
    runs = {p: _ernie_remat_run(p)
            for p in ("none", "dots_saveable", "full")}
    l_full = runs["full"][0]
    assert runs["none"][0] == l_full
    assert runs["dots_saveable"][0] == l_full
    peaks = {p: runs[p][1]["peak"] for p in runs}
    assert peaks["none"] >= peaks["dots_saveable"] >= peaks["full"]
    assert peaks["none"] > peaks["full"], peaks   # remat must really cut


def test_remat_policy_peak_ordering_hybrid(baseline):
    """The same knob threads through the hybrid engine's per-block
    remat. dots_saveable and full (both checkpoint wrappers) match
    bitwise; `none` compiles WITHOUT the remat barrier, so XLA re-fuses
    the forward and the trajectory drifts by reassociation only
    (measured ~2e-4 rel on CPU) — still far inside the 1e-3 the whole
    mp sweep tolerates."""
    _need(2)
    _, master = baseline

    def run(policy):
        with _fleet_ctx(remat=policy):
            eng, x, y = _hybrid_engine(1, 2, master, sp=False)
            losses = [float(eng.train_batch(x, y).item())
                      for _ in range(graft._STEPS)]
            return losses, eng.memory_analysis()

    runs = {p: run(p) for p in ("none", "dots_saveable", "full")}
    assert runs["dots_saveable"][0] == runs["full"][0]
    np.testing.assert_allclose(runs["none"][0], runs["full"][0],
                               rtol=1e-3)
    peaks = {p: runs[p][1]["peak"] for p in runs}
    assert peaks["none"] >= peaks["dots_saveable"] >= peaks["full"]
    assert peaks["none"] > peaks["full"], peaks


def test_remat_wrapper_rejects_unknown_policy():
    from paddle_tpu.distributed.fleet.utils.recompute import remat_wrapper

    paddle.set_flags({"FLAGS_remat_policy": "bogus"})
    try:
        with pytest.raises(ValueError, match="bogus"):
            remat_wrapper()
    finally:
        paddle.set_flags({"FLAGS_remat_policy": "auto"})
