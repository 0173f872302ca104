"""Runtime behaviour: fault-tolerant trainer, checkpoint manager, server,
gradient compression, sharding rules, int8 KV."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, latest_step, save_pytree
from repro.configs import get_smoke_config
from repro.core import controller as ctl, dqn, memory
from repro.core.policy import RLPolicy
from repro.data import SyntheticCorpus, batch_iterator
from repro.models import registry
from repro.optim import adamw
from repro.parallel import compression, param_pspecs
from repro.runtime import RAPServer, Trainer, TrainerConfig


# --------------------------------------------------------------- checkpoint
def test_checkpoint_atomic_and_keep_n(tmp_path, tiny_model):
    _, params, _ = tiny_model
    cm = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3):
        cm.save(params, s)
    steps = sorted(n for n in os.listdir(tmp_path) if n.startswith("step_"))
    assert len(steps) == 2 and cm.latest_step() == 3


def test_checkpoint_roundtrip_async(tmp_path, tiny_model):
    _, params, _ = tiny_model
    cm = CheckpointManager(str(tmp_path))
    cm.save(params, 7, blocking=False)
    cm.wait()
    restored, manifest = cm.restore(jax.eval_shape(lambda: params))
    assert manifest["step"] == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_ignores_partial_writes(tmp_path, tiny_model):
    """A .tmp directory (simulated crash mid-save) is never visible."""
    _, params, _ = tiny_model
    save_pytree(params, str(tmp_path), 5)
    os.makedirs(tmp_path / "step_0000000009.tmp")
    assert latest_step(str(tmp_path)) == 5


# ------------------------------------------------------------------ trainer
def _small_trainer(tmp_path, steps=12, ckpt_every=4):
    cfg = get_smoke_config("llama2-7b").replace(n_layers=2)
    model = registry.build(cfg)
    return model, Trainer(
        model, adamw.AdamWConfig(lr=1e-3, total_steps=steps),
        TrainerConfig(total_steps=steps, ckpt_dir=str(tmp_path),
                      ckpt_every=ckpt_every, log_every=4, ckpt_async=False,
                      remat=False))


def test_trainer_checkpoint_restart_resumes_exactly(tmp_path):
    model, tr = _small_trainer(tmp_path)
    corpus = SyntheticCorpus(model.cfg.vocab_size, seed=1)
    tr.run(batch_iterator(corpus, 2, 32), steps=8)
    assert tr.ckpt.latest_step() == 8
    # fresh trainer = simulated restart after node failure
    model2, tr2 = _small_trainer(tmp_path)
    assert tr2.maybe_restore()
    assert tr2.step == 8
    batches = batch_iterator(corpus, 2, 32, start=tr2.step)
    out = tr2.run(batches)
    assert out["final_step"] == 12


def test_trainer_emergency_checkpoint_on_crash(tmp_path):
    model, tr = _small_trainer(tmp_path, steps=100, ckpt_every=1000)
    corpus = SyntheticCorpus(model.cfg.vocab_size, seed=1)
    base = batch_iterator(corpus, 2, 32)

    def crashing():
        for i, b in enumerate(base):
            if i == 5:
                raise RuntimeError("simulated node failure")
            yield b

    with pytest.raises(RuntimeError):
        tr.run(crashing())
    assert tr.ckpt.latest_step() == 5   # emergency save happened


def test_trainer_straggler_detection(tmp_path):
    import time
    model, tr = _small_trainer(tmp_path, steps=10, ckpt_every=1000)
    corpus = SyntheticCorpus(model.cfg.vocab_size, seed=1)
    events = []
    tr.on_straggler = lambda s, dt: events.append(s)
    base = batch_iterator(corpus, 2, 32)

    def slow():
        for i, b in enumerate(base):
            if i == 6:
                time.sleep(1.2)   # inject a straggler step
            yield b

    tr.run(slow())
    assert len(tr.straggler_events) >= 1
    assert events == [s for s, _, _ in tr.straggler_events]


def test_trainer_elastic_remesh(tmp_path):
    """Shrink/grow the device mesh mid-run; training continues."""
    from repro.launch.mesh import make_host_mesh
    model, tr = _small_trainer(tmp_path, steps=8, ckpt_every=100)
    corpus = SyntheticCorpus(model.cfg.vocab_size, seed=1)
    tr.run(batch_iterator(corpus, 2, 32), steps=3)
    tr.remesh(make_host_mesh((1, 1), ("data", "model")))
    out = tr.run(batch_iterator(corpus, 2, 32, start=tr.step), steps=3)
    assert out["final_step"] == 6
    assert np.isfinite(out["history"][-1]["loss"])


# ------------------------------------------------------------------- server
def test_server_structural_vs_masked_equivalent(tiny_model):
    model, params, batch = tiny_model
    mm = memory.build_memory_model(model.cfg)
    qp = dqn.init_qnet(jax.random.key(0), 2 * model.cfg.n_layers + 4,
                       2 * model.cfg.n_layers + 1, 32)
    c = ctl.RAPController(model, params, batch, mm, qp)
    prompt = np.asarray(batch["tokens"])[:, :16]
    budget = 0.8 * mm.dense_peak(prompt.shape[0], 24)
    s1 = RAPServer(model, params, RLPolicy(c), mode="structural",
                   max_new_tokens=4)
    s2 = RAPServer(model, params, RLPolicy(c), mode="masked",
                   max_new_tokens=4)
    r1 = s1.serve(prompt, budget)
    r2 = s2.serve(prompt, budget)
    assert np.array_equal(r1.mask, r2.mask)
    np.testing.assert_array_equal(r1.tokens, r2.tokens)
    assert r1.fits and r1.peak_bytes <= budget


def test_server_bucket_cache_reuse(tiny_model):
    model, params, batch = tiny_model
    mm = memory.build_memory_model(model.cfg)
    qp = dqn.init_qnet(jax.random.key(1), 2 * model.cfg.n_layers + 4,
                       2 * model.cfg.n_layers + 1, 32)
    c = ctl.RAPController(model, params, batch, mm, qp)
    srv = RAPServer(model, params, RLPolicy(c), mode="structural",
                    max_new_tokens=2)
    prompt = np.asarray(batch["tokens"])[:, :16]
    budget = 0.85 * mm.dense_peak(2, 18)
    r1 = srv.serve(prompt, budget)
    r2 = srv.serve(prompt, budget)
    assert r1.compiled_new and not r2.compiled_new


# ------------------------------------------------------------- compression
def test_int8_error_feedback_allreduce():
    """Inside shard_map on a 1-device mesh: quantized mean ≈ true mean and
    the residual carries the quantization error."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh((1,), ("data",))
    g = {"w": jnp.asarray(np.random.default_rng(0)
                          .standard_normal((64,)).astype(np.float32))}
    r = compression.init_residuals(g)

    def f(g, r):
        return compression.compress_allreduce(g, r, ("data",))

    mean, new_r = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                out_specs=(P(), P()), check_vma=False)(g, r)
    err = np.abs(np.asarray(mean["w"]) - np.asarray(g["w"]))
    scale = np.abs(np.asarray(g["w"])).max() / 127.0
    assert err.max() <= scale * 0.51 + 1e-6
    np.testing.assert_allclose(np.asarray(new_r["w"]),
                               np.asarray(g["w"] - mean["w"]), atol=1e-6)
    # second round with residual: cumulative error shrinks (error feedback)
    mean2, _ = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                             out_specs=(P(), P()), check_vma=False)(g, new_r)
    total = np.asarray(mean["w"] + mean2["w"])
    np.testing.assert_allclose(total, 2 * np.asarray(g["w"]),
                               atol=2 * scale)


# ---------------------------------------------------------------- sharding
def test_param_pspecs_divisibility_fallback(tiny_model):
    """Rules never emit a spec whose sharded dim does not divide the mesh."""
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    import jax.sharding as jsh

    mesh = make_host_mesh((1, 1), ("data", "model"))
    for arch in ("dbrx-132b", "recurrentgemma-9b", "whisper-medium"):
        cfg = get_config(arch)
        model = registry.build(cfg)
        shapes = jax.eval_shape(lambda m=model: m.init(jax.random.key(0)))
        specs = param_pspecs(shapes, mesh, fsdp=True)
        flat_shapes = jax.tree.leaves(shapes)
        flat_specs = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, jsh.PartitionSpec))
        assert len(flat_shapes) == len(flat_specs)
        for sh, sp in zip(flat_shapes, flat_specs):
            for dim, axis in zip(sh.shape, sp):
                if axis is not None:
                    n = np.prod([mesh.shape[a] for a in
                                 (axis if isinstance(axis, tuple)
                                  else (axis,))])
                    assert dim % n == 0


# ----------------------------------------------------------------- int8 KV
def test_int8_kv_decode_close_to_bf16():
    cfg = get_smoke_config("qwen3-14b")
    model = registry.build(cfg)
    params = model.init(jax.random.key(0))
    B, S = 2, 24
    batch = {"tokens": jax.random.randint(jax.random.key(1), (B, S), 0,
                                          cfg.vocab_size)}
    _, c16 = model.prefill(params, batch, max_len=S + 4)
    _, c8 = model.prefill(params, batch, max_len=S + 4, kv_dtype=jnp.int8)
    assert c8["attn"]["k"].dtype == jnp.int8 and "ks" in c8["attn"]
    tok = jnp.zeros((B, 1), jnp.int32)
    d16, _ = model.decode(params, c16, tok)
    d8, _ = model.decode(params, c8, tok)
    # int8 KV shifts logits only slightly; argmax agrees
    assert np.abs(np.asarray(d16) - np.asarray(d8)).max() < 0.5
    assert np.array_equal(np.argmax(np.asarray(d16), -1),
                          np.argmax(np.asarray(d8), -1))
