"""RAP core behaviour: memory model, GSI, masks/compaction, DQN, controller."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import (baselines, controller as ctl, dqn, env as env_lib,
                        gsi, masks, memory, workload)
from repro.models import decoder, registry


# ------------------------------------------------------------ memory model
def test_memory_model_matches_pytree(tiny_model):
    model, params, _ = tiny_model
    cfg = model.cfg
    mm = memory.build_memory_model(cfg, param_bytes_per=4)  # f32 smoke
    L = cfg.n_layers
    full = masks.full_mask(L)
    analytic = mm.param_bytes(full)
    real = sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree.leaves(params))
    assert abs(analytic - real) / real < 0.05


def test_memory_model_kv_scaling(tiny_model):
    model, _, _ = tiny_model
    mm = memory.build_memory_model(model.cfg)
    full = masks.full_mask(model.cfg.n_layers)
    s1 = mm.state_bytes(full, 2, 128)
    s2 = mm.state_bytes(full, 4, 128)
    s3 = mm.state_bytes(full, 2, 256)
    assert abs(s2 - 2 * s1) < 1e-6 and abs(s3 - 2 * s1) < 1e-6  # Eq. (1)
    # removing an MHA block reduces KV; removing FFN does not
    m = masks.remove_block(full, 0)
    assert mm.state_bytes(m, 2, 128) < s1
    m = masks.remove_block(full, model.cfg.n_layers)
    assert mm.state_bytes(m, 2, 128) == s1


def test_memory_model_matches_real_cache(tiny_model):
    """Analytical Eq.(4) state bytes == the actual allocated cache bytes."""
    model, params, batch = tiny_model
    cfg = model.cfg
    mm = memory.build_memory_model(cfg)
    B, S = 2, 64
    cache = model.init_cache(B, S)
    real = sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree.leaves(cache))
    analytic = mm.state_bytes(masks.full_mask(cfg.n_layers), B, S)
    # cfg dtype is f32 in smoke; kv bytes default = dtype bytes
    assert abs(real - analytic) / real < 0.05


# -------------------------------------------------------------------- GSI
def test_gsi_removal_order_and_trace(tiny_model):
    model, params, batch = tiny_model
    res = gsi.gsi_rank(model, params, batch, max_removals=3)
    assert len(res.order) == 3
    assert len(set(res.order)) == 3
    # scores snapshots: removed blocks become inf-masked in later snapshots
    s0, s1 = res.score_snapshots[0], res.score_snapshots[1]
    assert np.isfinite(s0[res.order[0]])
    assert not np.isfinite(s1[res.order[0]])


def test_gsi_vs_oneshot_divergence(tiny_model):
    """After removals, re-evaluated scores differ from one-shot scores —
    the paper's inter-layer dependence claim (Fig. 6)."""
    model, params, batch = tiny_model
    oneshot = gsi.oneshot_rank(model, params, batch)
    res = gsi.gsi_rank(model, params, batch, max_removals=2)
    later = res.score_snapshots[1]
    live = np.isfinite(later) & np.isfinite(oneshot)
    assert not np.allclose(later[live], oneshot[live], rtol=1e-3)


def test_gsi_scorer_masks_inactive(tiny_model):
    model, params, batch = tiny_model
    L = model.cfg.n_layers
    scorer = gsi.make_candidate_scorer(model, batch)
    m = np.ones(2 * L, np.float32)
    m[1] = 0.0
    scores = np.asarray(scorer(params, jnp.asarray(m)))
    assert not np.isfinite(scores[1])
    assert np.isfinite(np.delete(scores, 1)).all()


# ----------------------------------------------------- masks / compaction
def test_masked_equals_structural(tiny_model):
    model, params, batch = tiny_model
    cfg = model.cfg
    L = cfg.n_layers
    mask = masks.full_mask(L)
    mask[1] = False          # drop one mixer
    mask[L + 2] = False      # drop one ffn
    gates = masks.mask_to_gates(mask)
    full_logits = model.logits(params, batch, gates=gates)
    small, layout = masks.compact_params(params, cfg, mask)
    small_logits, _ = decoder.forward(small, cfg, batch["tokens"],
                                      layout=layout)
    np.testing.assert_allclose(np.asarray(full_logits),
                               np.asarray(small_logits), atol=1e-4,
                               rtol=1e-4)


def test_compaction_shrinks_params(tiny_model):
    model, params, _ = tiny_model
    cfg = model.cfg
    L = cfg.n_layers
    mask = masks.full_mask(L)
    mask[0] = mask[L] = False    # drop layer 0 entirely
    small, layout = masks.compact_params(params, cfg, mask)
    n_full = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    n_small = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(small))
    assert n_small < n_full
    assert len(layout) == L - 1


def test_full_mask_compaction_returns_dense_arrays(tiny_model):
    """The full mask gathers every row in order: compaction hands back the
    dense stacks themselves (no second copy of the weights), with the
    default layout."""
    model, params, _ = tiny_model
    cfg = model.cfg
    small, layout = masks.compact_params(params, cfg,
                                         masks.full_mask(cfg.n_layers))
    assert layout == decoder.default_layout(cfg)
    for kind, stack in params["stacks"].items():
        assert small["stacks"][kind] is stack
    for a, b in zip(jax.tree.leaves(small), jax.tree.leaves(params)):
        assert a is b


def test_bucket_key_collapses_uniform(tiny_model):
    model, _, _ = tiny_model
    cfg = model.cfg
    L = cfg.n_layers
    # whole-layer drops collapse by count (the vLLM-bucket-like case)
    m1 = masks.full_mask(L); m1[1] = m1[L + 1] = False
    m2 = masks.full_mask(L); m2[2] = m2[L + 2] = False
    assert masks.bucket_key(cfg, m1) == masks.bucket_key(cfg, m2)
    # half-layer drops keep their position in the signature
    m3 = masks.full_mask(L); m3[L] = False   # ffn-only drop
    assert masks.bucket_key(cfg, m1) != masks.bucket_key(cfg, m3)


# ------------------------------------------------------------ env + DQN
def make_env(tiny):
    model, params, batch = tiny
    mm = memory.build_memory_model(model.cfg)
    return env_lib.PruneEnv(model, params, batch, mm), mm


def test_env_episode_semantics(tiny_model):
    env, mm = make_env(tiny_model)
    budget = 0.7 * mm.dense_peak(4, 256)
    s = env.reset(4, 256, budget)
    assert s.shape == (env.state_dim,)
    valid = env.valid_actions()
    assert valid[1:].all()
    # STOP masked while over budget (memory-aware action mask)
    assert valid[0] == env.fits()
    s2, r, done, info = env.step(1)   # remove block 0
    assert not env.mask[0]
    assert np.isfinite(r)


def test_env_reward_decreases_with_removal(tiny_model):
    """Removing a block lowers Σ kept·(α·imp − β·mem) memory penalty."""
    env, mm = make_env(tiny_model)
    env.reset(4, 256, 0.5 * mm.dense_peak(4, 256))
    r_full = env._reward()
    env.step(1)
    # reward changes and stays finite
    assert np.isfinite(env._reward())


def test_dqn_training_runs_and_fits(tiny_model):
    env, mm = make_env(tiny_model)

    def sampler(rng):
        bs = int(rng.integers(1, 8))
        sql = int(rng.integers(64, 512))
        return bs, sql, 0.75 * mm.dense_peak(bs, sql)

    tr = dqn.train(lambda: env, episodes=4,
                   cfg=dqn.DQNConfig(eps_decay_episodes=2, batch_size=16),
                   request_sampler=sampler, seed=0)
    assert len(tr.episode_rewards) == 4
    assert all(tr.episode_fits)    # mask_stop_until_fit guarantees this
    assert dqn.n_params(tr.q_params) < 50_000   # paper: ~18K controller


def test_controller_meets_budget(tiny_model):
    model, params, batch = tiny_model
    mm = memory.build_memory_model(model.cfg)
    qp = dqn.init_qnet(jax.random.key(0), 2 * model.cfg.n_layers + 4,
                       2 * model.cfg.n_layers + 1, 32)
    c = ctl.RAPController(model, params, batch, mm, qp)
    budget = 0.6 * mm.dense_peak(4, 256)
    d = c.decide(4, 256, budget)
    assert d.fits and d.peak_bytes <= budget
    # abundant memory → keep everything (paper: "leaves model intact")
    d2 = c.decide(1, 32, 1.1 * mm.dense_peak(1, 32))
    assert d2.mask.all() and d2.steps == 0


# ------------------------------------------------------------- baselines
def test_baseline_masks_fit_budget(tiny_model):
    model, params, batch = tiny_model
    mm = memory.build_memory_model(model.cfg)
    bs, sql = 4, 256
    budget = 0.75 * mm.dense_peak(bs, sql)
    for name, fn in [
        ("shortgpt", lambda: baselines.shortgpt_mask(model, params, batch,
                                                     mm, bs, sql, budget)),
        ("random", lambda: baselines.random_drop_mask(model, mm, bs, sql,
                                                      budget)),
        ("oneshot", lambda: baselines.oneshot_ppl_mask(model, params, batch,
                                                       mm, bs, sql, budget)),
        ("llmpruner", lambda: baselines.llmpruner_mask(model, params, batch,
                                                       mm, bs, sql, budget)),
    ]:
        m = fn()
        assert mm.peak_bytes(m, bs, sql) <= budget, name


def test_mha_ffn_only_baselines_target_right_blocks(tiny_model):
    model, params, batch = tiny_model
    mm = memory.build_memory_model(model.cfg)
    L = model.cfg.n_layers
    budget = 0.8 * mm.dense_peak(4, 256)
    m_mha = baselines.mha_drop_mask(model, params, batch, mm, 4, 256, budget)
    assert m_mha[L:].all()          # FFN untouched
    m_ffn = baselines.ffn_skip_mask(model, params, batch, mm, 4, 256, budget)
    assert m_ffn[:L].all()          # MHA untouched


def test_slicegpt_slices_and_runs(tiny_model):
    model, params, batch = tiny_model
    mm = memory.build_memory_model(model.cfg)
    ratio = baselines.slicegpt_fit_ratio(model.cfg, mm, 4, 256,
                                         0.8 * mm.dense_peak(4, 256))
    assert 0.0 < ratio < 1.0
    p2, cfg2 = baselines.slicegpt_slice(model, params, ratio)
    assert cfg2.d_ff < model.cfg.d_ff
    m2 = registry.build(cfg2)
    loss, _ = m2.loss(p2, batch)
    assert np.isfinite(float(loss))


# -------------------------------------------------------------- workload
def test_workload_deterministic():
    cfg = workload.WorkloadConfig(seed=3, horizon_s=120)
    a, b = workload.generate(cfg), workload.generate(cfg)
    assert [(r.t, r.batch, r.seq_len) for r in a] == \
        [(r.t, r.batch, r.seq_len) for r in b]
    assert all(cfg.mem_floor <= r.budget_frac <= 1.0 for r in a)
