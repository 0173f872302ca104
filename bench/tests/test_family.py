"""A configuration names its family module, which alone knows the
architecture: the dense family reads what the harness read before it was
split out, and a family of another architecture is added by files alone."""
import json
import os

import numpy as np
import pytest

from bench import check, serve, spec

BM = spec.benchmark()
CELL = "glm4-9b.reason_long_kvhalf"

# a family of the test's own, for a sparse-expert configuration (the
# registry's dbrx at smoke widths): a stand-in reference of one matmul per
# layer and no token mixing
TOY_FAMILY = '''
"""Toy family: h += g_ffn * silu(rmsnorm(h) W) per layer."""
import jax
import jax.numpy as jnp

from bench.reference import _dense, _rms, _widen, perturbed, split_seed_key


def check(cfg, m):
    got = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
           "n_experts": cfg.n_experts, "top_k": cfg.moe_top_k,
           "vocab_size": cfg.vocab_size, "vocab_padded": cfg.vocab_padded}
    bad = {k: (got[k], m[k]) for k in got if got[k] != m[k]}
    if bad:
        raise ValueError(f"toy: {bad}")


def perturb(params, key, vocab_size):
    return params


def init_weights(m, key):
    k_init, k_pert = split_seed_key(key)
    k_e, k_h, k_l = jax.random.split(k_init, 3)
    L, d, Vp = m["n_layers"], m["d_model"], m["vocab_padded"]
    return {"embed": (jax.random.normal(k_e, (Vp, d)) * 0.02
                      ).astype(jnp.bfloat16),
            "head": _dense(k_h, d, Vp),
            "w": jax.vmap(lambda k: _dense(k, d, d))(
                jax.random.split(k_l, L)),
            "s": perturbed(k_pert, "toy/norm", (L, d)),
            "s_final": perturbed(k_pert, "final_norm/scale", (d,))}


def hidden(m, w, tokens, gm, gf, lowp=False):
    mat, eps = _widen(lowp), m["norm_eps"]

    def layer(h, x):
        lw, s, g = x
        return h + g * jax.nn.silu(_rms(h, s, eps) @ mat(lw)), None

    h = w["embed"][tokens].astype(jnp.float32)
    h, _ = jax.lax.scan(layer, h, (w["w"], w["s"], gf))
    return _rms(h, w["s_final"], eps)


def matmul_flops_per_token(cfg):
    m = cfg["model"]
    return 2.0 * m["d_model"] * (m["n_layers"] * m["d_model"]
                                 + m["vocab_padded"])


def attn_flops(cfg, ctx):
    m = cfg["model"]
    return 4.0 * ctx * m["d_model"] * m["n_layers"]


def decode_attn_bytes(cfg, ctx, kv_bytes=2, act_bytes=2):
    m = cfg["model"]
    return (2.0 * ctx * m["d_model"] * kv_bytes
            + 2.0 * m["d_model"] * act_bytes) * m["n_layers"]


def kv_bytes_per_ctx_token(cfg):
    m = cfg["model"]
    return 2 * m["d_model"] * 2 * m["n_layers"]
'''

TOY_MODEL = {"n_layers": 2, "d_model": 64, "n_experts": 4, "top_k": 2,
             "vocab_size": 512, "vocab_padded": 512, "norm_eps": 1e-5}


def _toy_root(tmp_path, family_src=TOY_FAMILY, family="toy",
              model=TOY_MODEL):
    """A checkout of nothing but the files a new cell of a new family
    brings (and the mix it reuses); the repo's files are not touched."""
    root = tmp_path / "checkout"
    for sub in ("configs", "families", "traffic", "limits"):
        (root / "bench" / sub).mkdir(parents=True)
    cfg = {"name": "toy-moe", "source": "test", "arch": "dbrx-132b",
           "overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                         "n_kv_heads": 2, "head_dim": 16, "d_ff": 64,
                         "n_experts": 4, "moe_top_k": 2, "vocab_size": 512,
                         "vocab_round_to": 64},
           "model": model}
    if family is not None:
        cfg["family"] = family
    (root / "bench" / "configs" / "toy-moe.json").write_text(json.dumps(cfg))
    (root / "bench" / "families" / "toy.py").write_text(family_src)
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                      "reason_long_kvhalf.json"))
    (root / "bench" / "traffic" / "reason_long_kvhalf.json").write_text(
        json.dumps(mix))
    (root / "bench" / "limits" / "toy-moe.reason.json").write_text(
        json.dumps({"max_logit_gap": {"limit": 1.0},
                    "tokens_compared": {"limit": 10}}))
    bm = json.loads(json.dumps(BM))
    bm["configs"].append({"name": "toy-moe", "source": "test",
                          "file": "bench/configs/toy-moe.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "toy-moe.reason", "config": "toy-moe",
                            "traffic": "reason_long_kvhalf", "chips": 1,
                            "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return str(root)


def test_glm4_resolves_to_dense_gqa_with_the_parents_counts():
    cell = spec.resolve(CELL)
    assert cell.config["family"] == "dense_gqa"
    assert cell.family.__file__ == os.path.join(spec.BENCH_DIR, "families",
                                                "dense_gqa.py")
    fam, cfg = cell.family, cell.config
    # the values the harness computed before the family split
    assert fam.layer_params(cfg) == 203948032
    assert fam.matmul_flops_per_token(cfg) == 9399435264.0
    assert fam.attn_flops(cfg, 1000.0) == 327680000.0
    assert fam.decode_attn_bytes(cfg, 1000.0) == 20807680.0
    assert fam.kv_bytes_per_ctx_token(cfg) == 20480 == 2 * 2 * 128 * 2 * 20


def test_every_configuration_names_a_family_that_resolves():
    for c in BM["configs"]:
        cfg = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        fam = spec.family_module(cfg)
        assert all(callable(getattr(fam, f)) for f in spec.FAMILY_CONTRACT)


def test_a_family_is_added_by_files_alone(tmp_path):
    """A toy family resolves from a temporary root, checks the registry's
    configuration, feeds the device readers its counts and the check its
    reference, with no file of the repo edited."""
    root = _toy_root(tmp_path)
    cell = spec.resolve("toy-moe.reason", root=root)
    assert cell.family.__file__ == os.path.join(root, "bench", "families",
                                                "toy.py")
    cfg = serve.model_config(cell)
    assert (cfg.n_experts, cfg.moe_top_k) == (4, 2)
    bad = dict(cell.config, model=dict(TOY_MODEL, top_k=4))
    with pytest.raises(ValueError, match="top_k"):
        serve.model_config(type(cell)(**{**cell.__dict__, "config": bad}))
    # the dense family refuses the sparse-expert layers at matching widths
    dense = spec.resolve(CELL).family
    block = dict(spec.resolve(CELL).config["model"], n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, head_dim=16, d_ff=64,
                 vocab_size=512, vocab_padded=512, qkv_bias=cfg.qkv_bias,
                 qk_norm=cfg.qk_norm, rope_theta=float(cfg.rope_theta),
                 norm_eps=cfg.norm_eps, tie_embeddings=cfg.tie_embeddings,
                 dtype=cfg.dtype)
    with pytest.raises(ValueError, match="moe"):
        dense.check(cfg, block)

    # the device readers take the toy's counts
    from bench.run import Context, _request_work
    marks = {"trace_open": {"progress": {"a": (-1, 1)}},
             "trace_close": {"progress": {"a": (-1, 11)}}}
    ctx = Context(cell=cell, config=cell.config, marks=marks,
                  prompt_len={"a": 90},
                  peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
                  trace={"kernel_s": {"rap_paged_decode_attention": 0.5},
                         "busy_s": 1.0, "window_s": 2.0})
    ctx.work = lambda a, b: _request_work(ctx, a, b)
    d, L, Vp = 64, 2, 512
    ctxs = [90 + i for i in range(1, 11)]
    work = sum(2.0 * d * (L * d + Vp) + 4.0 * c * d * L for c in ctxs)
    assert spec.metric_module("mfu.decode").compute(ctx) == \
        pytest.approx(100 * work / (2.0 * 1e12))
    need = sum((2.0 * c * d * 2 + 2.0 * d * 2) * L for c in ctxs)
    t = max(need / 1e9, sum(4.0 * c * d * L for c in ctxs) / 1e12)
    roof = spec.metric_module("paged_attn_roofline.decode").compute(ctx)
    assert roof == pytest.approx(100 * t / 0.5)

    # the check reads the toy's reference through the shared gap loop
    rng = np.random.default_rng(0)
    items = [("r", rng.integers(0, 512, 20), rng.integers(0, 512, 7),
              np.ones(2 * L, bool))]
    r = check.readings(cell, 11, items)
    assert r["tokens_compared"] == 7 and np.isfinite(r["max_logit_gap"])
    assert r["max_logit_gap"] >= 0.0


@pytest.mark.parametrize("missing", spec.FAMILY_CONTRACT)
def test_a_family_lacking_a_contract_function_fails_at_resolve(tmp_path,
                                                               missing):
    src = TOY_FAMILY.replace(f"\ndef {missing}(", f"\ndef _{missing}(")
    root = _toy_root(tmp_path, family_src=src)
    with pytest.raises(AttributeError, match=missing):
        spec.resolve("toy-moe.reason", root=root)


@pytest.mark.parametrize("family,error", [(None, KeyError),
                                          ("no_such_family",
                                           FileNotFoundError)])
def test_a_configuration_without_its_family_fails_at_resolve(tmp_path,
                                                             family, error):
    root = _toy_root(tmp_path, family=family)
    with pytest.raises(error, match="family"):
        spec.resolve("toy-moe.reason", root=root)

