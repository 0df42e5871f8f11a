"""Distribution substrate: sharding rules, checkpointing, straggler,
elastic re-mesh, roofline HLO analyzer."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed import (CheckpointManager, StragglerMonitor, elastic,
                               rules_for, tree_paths)
from repro.distributed.sharding import batch_sharding, cache_sharding
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.roofline import hlo_cost

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   vocab_size=256, n_heads=4, n_kv_heads=2, d_ff=128,
                   dtype="float32", remat="none")


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_rules_match_expected_paths():
    rules = rules_for(("data",))
    assert rules.spec_for("decoder/super/0/mixer/wq/w", 3) == \
        P(None, ("data",), "model")
    assert rules.spec_for("decoder/super/0/mixer/wo/w", 3) == \
        P(None, "model", ("data",))
    assert rules.spec_for("embed/table", 2) == P("model", ("data",))
    assert rules.spec_for("decoder/super/0/ffn/wi_gate", 4) == \
        P(None, "model", ("data",), None)
    assert rules.spec_for("decoder/super/0/norm1/scale", 1) == P()
    # QWeight leaves share the float weight's layout
    assert rules.spec_for("decoder/super/0/mixer/wq/w/packed", 3) == \
        P(None, ("data",), "model")


def test_rules_multipod_dp():
    rules = rules_for(("pod", "data"))
    assert rules.spec_for("lm_head/w", 2) == P(("pod", "data"), "model")


def test_uneven_dims_fall_back_to_replicated():
    """mamba2 in_proj N=3352 doesn't divide 16 -> that dim replicates."""
    from repro.distributed.sharding import _evenly

    class StubMesh:                     # only .shape is consulted
        shape = {"data": 16, "model": 16}

    spec = _evenly(P("data", "model"), (768, 3352), StubMesh())
    assert spec == P("data", None)
    spec2 = _evenly(P("data", "model"), (768, 3200), StubMesh())
    assert spec2 == P("data", "model")


def test_all_params_get_shardings():
    params = transformer.init_params(TINY, jax.random.key(0))
    mesh = _mesh11()
    rules = rules_for(("data",))
    shardings = rules.shardings(params, mesh)
    assert len(jax.tree.leaves(shardings)) == len(jax.tree.leaves(params))


def test_cache_sharding_roles():
    mesh = _mesh11()
    cache = transformer.init_cache(TINY, 4, 16)
    sh = cache_sharding(cache, mesh, ("data",), batch_size=4)
    flat = {"/".join(map(str, jax.tree_util.keystr(kp).split("'")[1::2])): v
            for kp, v in jax.tree_util.tree_flatten_with_path(sh)[0]}
    # stacked KV leaf: (S, B, S_kv, KV, D) -> (None, dp, model-on-seq, ...)
    kv = [v for k, v in flat.items() if k.endswith("k")][0]
    assert kv.spec[1] in ("data", ("data",))


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------

def _state():
    return {"w": jnp.arange(12.0).reshape(3, 4),
            "opt": {"mu": jnp.ones((3, 4)), "count": jnp.asarray(3)}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = _state()
    mgr.save(10, state)
    restored = mgr.restore(10, state)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state, restored)


def test_checkpoint_latest_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = _state()
    for step in (10, 20, 30):
        mgr.save(step, jax.tree.map(lambda x: x + step, state))
    assert mgr.committed_steps() == [20, 30]
    step, tree = mgr.restore_latest(state)
    assert step == 30
    np.testing.assert_allclose(np.asarray(tree["w"]),
                               np.asarray(state["w"]) + 30)


def test_checkpoint_corruption_detected(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = _state()
    mgr.save(10, state)
    mgr.save(20, state)
    # corrupt the newest checkpoint's first leaf
    d = os.path.join(str(tmp_path), "step_00000020")
    fn = os.path.join(d, "leaf_00000.npy")
    arr = np.load(fn)
    arr = arr + 999
    np.save(fn, arr)
    step, _ = mgr.restore_latest(state, verbose=False)
    assert step == 10                                  # fell back


def test_checkpoint_partial_write_ignored(tmp_path):
    """A .tmp dir (preemption mid-write) is invisible to restore."""
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = _state()
    mgr.save(10, state)
    os.makedirs(os.path.join(str(tmp_path), "step_00000020.tmp"))
    step, _ = mgr.restore_latest(state, verbose=False)
    assert step == 10


def test_trainer_auto_resume(tmp_path):
    """Kill-and-restart: the second Trainer resumes from the checkpoint."""
    from repro.data import DataConfig, SyntheticLM
    from repro.train import TrainHParams, Trainer, TrainerConfig
    data = SyntheticLM(DataConfig(vocab_size=256, seq_len=16,
                                  global_batch=4))
    mk = lambda steps: Trainer(
        TINY, TrainHParams(lr=1e-3), data,
        TrainerConfig(total_steps=steps, ckpt_every=5, log_every=100,
                      ckpt_dir=str(tmp_path)))
    t1 = mk(10)
    t1.run()                               # writes step 5, 10
    t2 = mk(14)                            # "restarted job"
    t2.run()
    steps_run = [h["step"] for h in t2.history]
    assert steps_run[0] == 10              # resumed, not from scratch
    assert steps_run[-1] == 13


# ---------------------------------------------------------------------------
# straggler monitor
# ---------------------------------------------------------------------------

def test_straggler_flags_slow_worker():
    events = []
    mon = StragglerMonitor(threshold=3.0, patience=2, warmup=3,
                           on_straggler=lambda *a: events.append(a))
    rng = np.random.default_rng(1)
    for _ in range(20):
        mon.observe("w0", 0.10 + rng.normal() * 1e-4)
    for _ in range(2):
        mon.observe("w0", 0.50)            # 5x slower, twice
    assert events, "straggler not flagged"


def test_straggler_tolerates_noise():
    mon = StragglerMonitor(threshold=3.0, patience=3, warmup=5)
    rng = np.random.default_rng(0)
    flags = [mon.observe("w", 0.1 + abs(rng.normal()) * 0.002)
             for _ in range(100)]
    assert not any(flags)


# ---------------------------------------------------------------------------
# elastic re-mesh
# ---------------------------------------------------------------------------

def test_plan_remesh_shrinks_data_axis():
    plan = elastic.plan_remesh(192, model_extent=16, global_batch=256,
                               prev_data_extent=16)
    assert plan.mesh_shape == (8, 16)      # 192 // 16 = 12 -> largest div 8
    assert plan.microsteps == 2            # keeps global batch


def test_plan_remesh_rejects_too_few():
    with pytest.raises(ValueError):
        elastic.plan_remesh(8, model_extent=16, global_batch=256,
                            prev_data_extent=16)


def test_elastic_reshard_roundtrip():
    plan = elastic.plan_remesh(1, model_extent=1, global_batch=4,
                               prev_data_extent=1)
    mesh = elastic.build_mesh(plan)
    rules = rules_for(("data",))
    params = transformer.init_params(TINY, jax.random.key(0))
    host = jax.tree.map(lambda x: np.asarray(x), params)
    resharded = elastic.reshard(host, mesh, rules)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), params, resharded)


# ---------------------------------------------------------------------------
# roofline HLO analyzer
# ---------------------------------------------------------------------------

def test_hlo_cost_counts_loops():
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def scanned(x):
        def body(c, _):
            return c @ c, None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    compiled = jax.jit(scanned).lower(w).compile()
    c = hlo_cost.analyze(compiled.as_text())
    np.testing.assert_allclose(c.flops, 7 * 2 * 128 ** 3, rtol=0.01)


def test_hlo_cost_dot_flops_exact():
    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 48), jnp.float32)
    compiled = jax.jit(lambda x, y: x @ y).lower(a, b).compile()
    c = hlo_cost.analyze(compiled.as_text())
    np.testing.assert_allclose(c.flops, 2 * 64 * 32 * 48, rtol=1e-6)


def test_hlo_top_ops_profile():
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    compiled = jax.jit(lambda x: (x @ x) @ x).lower(a).compile()
    rows = hlo_cost.top_ops(compiled.as_text(), 5, key="flops")
    assert rows and rows[0][2] == "dot"
