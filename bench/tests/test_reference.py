"""The plain reference of the cell's family: it rebuilds the served weights
from the seed bitwise, and in float32 it computes what the program's
forward computes."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, reference, serve
from bench.families import dense_gqa
from bench.tests.smoke import smoke_cell

CELL = "glm4-9b.reason_long_kvhalf"
SEED = 2**31 + 5


def _variant(qk_norm: bool):
    """The cell at smoke size, with qk-norm switched on or as published."""
    cell = smoke_cell(CELL)
    if qk_norm:
        cfg = copy.deepcopy(cell.config)
        cfg["overrides"]["qk_norm"] = True
        cfg["model"]["qk_norm"] = True
        cell = type(cell)(**{**cell.__dict__, "config": cfg})
    return cell


def _program(cell):
    from repro.models import registry
    model = registry.build(serve.model_config(cell))
    return model, serve.make_params(cell.family, model, SEED)


@pytest.mark.parametrize("qk_norm", [False, True])
def test_weights_are_rebuilt_bitwise(qk_norm):
    cell = _variant(qk_norm)
    model, p = _program(cell)
    m = cell.config["model"]
    w = cell.family.init_weights(m, jax.random.key(serve.seed32(SEED)))
    a, f = p["stacks"]["attn"], p["stacks"]["dense"]
    pairs = [(w["embed"], p["embed"]), (w["head"], p["lm_head"]),
             (w["wq"], a["wq"]), (w["wk"], a["wk"]), (w["wv"], a["wv"]),
             (w["wo"], a["wo"]), (w["wi"], f["wi"]), (w["wd"], f["wo"]),
             (w["s_attn"], a["norm"]["scale"]),
             (w["s_ffn"], f["norm"]["scale"]),
             (w["s_final"], p["final_norm"]["scale"])]
    if m["qkv_bias"]:
        pairs += [(w["bq"], a["bq"]), (w["bk"], a["bk"]), (w["bv"], a["bv"])]
    if m["qk_norm"]:
        pairs += [(w["s_q"], a["q_norm"]), (w["s_k"], a["k_norm"])]
    for x, y in pairs:
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
    assert float(jnp.abs(w["s_attn"].astype(jnp.float32)).max()) > 0


@pytest.mark.parametrize("qk_norm", [False, True])
def test_reference_matches_the_program_forward_in_f32(qk_norm):
    """With float32 activations the program's full forward and the
    reference agree to rounding; a masked block is dropped alike."""
    cell = _variant(qk_norm)
    cfg32 = copy.deepcopy(cell.config)
    cfg32["overrides"]["dtype"] = "float32"
    cfg32["model"]["dtype"] = "float32"
    cell32 = type(cell)(**{**cell.__dict__, "config": cfg32})
    model, p = _program(cell32)
    m = cell.config["model"]
    w = cell.family.init_weights(m, jax.random.key(serve.seed32(SEED)))
    L = m["n_layers"]
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, m["vocab_size"], 40).astype(np.int32)
    served = rng.integers(0, m["vocab_size"], 9).astype(np.int32)
    mask = np.ones(2 * L, bool)
    mask[L] = False                                  # layer 0's FFN
    seq = np.concatenate([prompt, served[:-1]])
    with jax.default_matmul_precision("highest"):
        lg = model.logits(p, {"tokens": jnp.asarray(seq[None])},
                          gates={"mixer": jnp.asarray(mask[:L], jnp.float32),
                                 "ffn": jnp.asarray(mask[L:], jnp.float32)})
    lg = np.asarray(lg[0, len(prompt) - 1:, :m["vocab_size"]])
    want = lg.max(-1) - lg[np.arange(len(served)), served]
    got = reference.served_gaps(m, w, [(prompt, served, mask)],
                                cell.family.hidden)[0]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


def test_the_cells_family_is_the_moved_reference_bit_for_bit():
    """Served and control gaps read through ``cell.family`` (as the check
    reads them) equal the shared gap loop on ``dense_gqa.hidden`` called
    directly on the same seed, bit for bit."""
    cell = smoke_cell(CELL)
    m = cell.config["model"]
    L = m["n_layers"]
    rng = np.random.default_rng(1)
    items = []
    for S, n in ((40, 9), (23, 17)):
        mask = np.ones(2 * L, bool)
        mask[rng.integers(2 * L)] = False
        items.append((f"r{S}", rng.integers(0, m["vocab_size"], S),
                      rng.integers(0, m["vocab_size"], n), mask))
    plain = [(p, s, k) for _, p, s, k in items]
    key = jax.random.key(serve.seed32(SEED))
    w = jax.jit(lambda k: dense_gqa.init_weights(m, k))(key)
    for control, gaps in ((False, reference.served_gaps),
                          (True, reference.control_gaps)):
        want = np.concatenate(gaps(m, w, plain, dense_gqa.hidden))
        got = check.readings(cell, SEED, items, control=control)
        assert got["max_logit_gap"] == float(want.max())
        assert got["median_logit_gap"] == float(np.median(want))
        assert got["tokens_compared"] == want.size
        np.testing.assert_array_equal(
            np.concatenate(gaps(m, w, plain, cell.family.hidden)), want)
