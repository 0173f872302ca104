"""Compile the dense GQA cells' paged decode kernel and serving steps for a
described TPU v5e (no chip attached): the kernel at each cell's page-table
width (glm4-9b: K=2, G=16), and the full-width decode horizon and a
one-token prefill chunk at each cell's pool size, which have to fit the
chip's memory beside the weights. The pools here are the family's K and V
pages. A cell of another family is listed in ``CELLS`` of that family's
own ``test_chip_compile_<family>.py``; one that is not fails here."""
import os

import pytest

from bench import spec

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BYTES_LIMIT = 16909336064          # bytes_limit one v5e chip reports
_BM = spec.benchmark()
_FAMILY = {c["name"]: spec.load_json(os.path.join(spec.ROOT, c["file"]))
           .get("family") for c in _BM["configs"]}
_CELL_FAMILY = {w["name"]: _FAMILY[w["config"]] for w in _BM["workloads"]}
CELLS = [c for c, f in _CELL_FAMILY.items() if f == "dense_gqa"]


@pytest.mark.parametrize("cell_name", sorted(_CELL_FAMILY))
def test_every_cell_has_a_v5e_compile_test(cell_name):
    family = _CELL_FAMILY[cell_name]
    if family == "dense_gqa":
        assert cell_name in CELLS
        return
    path = os.path.join(spec.BENCH_DIR, "tests",
                        f"test_chip_compile_{family}.py")
    assert os.path.isfile(path), \
        f"{cell_name}: family {family} has no compile test {path}"
    mod = spec._load(path, f"bench_compile_test_{family}")
    assert cell_name in getattr(mod, "CELLS", ()), \
        f"{cell_name} is not in CELLS of {path}"


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def _shapes(cell_name, sharding):
    import jax
    import jax.numpy as jnp
    from bench import serve
    from repro.core import masks, memory
    from repro.models import registry
    cell = spec.resolve(cell_name)
    cfg = serve.model_config(cell)
    model = registry.build(cfg)
    e = cell.mix["engine"]
    mm = memory.build_memory_model(cfg)
    budget = serve.device_budget(e, BYTES_LIMIT)
    L, K, D, pt = cfg.n_layers, cfg.n_kv_heads, cfg.dh, 16
    page_bytes = pt * cell.family.kv_bytes_per_ctx_token(cell.config)
    n_pages = int((budget - mm.param_bytes(masks.full_mask(L)))
                  // page_bytes)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    params = jax.tree.map(lambda s: S(s.shape, s.dtype),
                          jax.eval_shape(model.init, jax.random.key(0)))
    pools = {n: S((L, n_pages + 1, K, pt, D), jnp.bfloat16)
             for n in ("k", "v")}
    return cfg, e, params, pools, n_pages, S


@pytest.mark.parametrize("cell_name", CELLS)
def test_paged_kernel_at_the_cells_widths(one_chip, cell_name):
    import jax
    import jax.numpy as jnp
    from repro.kernels import paged_decode_attention as pdec
    cfg, e, _, _, _, S = _shapes(cell_name, one_chip)
    B, maxp = e["slots"], -(-e["max_len"] // 16)
    f = jax.jit(lambda q, k, v, t, n: pdec.paged_decode_attention(
        q, k, v, t, n, interpret=False))
    c = f.lower(S((B, 1, cfg.n_heads, cfg.dh), jnp.bfloat16),
                S((4097, cfg.n_kv_heads, 16, cfg.dh), jnp.bfloat16),
                S((4097, cfg.n_kv_heads, 16, cfg.dh), jnp.bfloat16),
                S((B, maxp), jnp.int32), S((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("cell_name", CELLS)
def test_serving_steps_fit_beside_the_pool(one_chip, cell_name):
    import jax
    import jax.numpy as jnp
    from repro.models import decoder
    cfg, e, params, pools, n_pages, S = _shapes(cell_name, one_chip)
    B, L, maxp = e["slots"], cfg.n_layers, -(-e["max_len"] // 16)

    def horizon(p, pools, table, pos, tok, gates):
        toks, pools, pos = decoder.paged_decode_horizon(
            p, cfg, pools, table, pos, tok[:, None], 8,
            gates={"mixer": gates[0], "ffn": gates[1]}, impl="pallas")
        return toks, pools, pos

    def chunk(p, pools, table, tokens, start, gm, gf):
        return decoder.paged_prefill_chunk(
            p, cfg, pools, table, tokens, start, scratch_page=n_pages,
            gates={"mixer": gm, "ffn": gf})

    steps = [
        jax.jit(horizon, donate_argnums=(1, 3, 4)).lower(
            params, pools, S((B, maxp), jnp.int32), S((B,), jnp.int32),
            S((B,), jnp.int32), S((2, L, B), jnp.float32)),
        jax.jit(chunk, donate_argnums=(1,)).lower(
            params, pools, S((1, maxp), jnp.int32), S((1, 1), jnp.int32),
            S((), jnp.int32), S((L,), jnp.float32), S((L,), jnp.float32))]
    for lowered in steps:
        ma = lowered.compile().memory_analysis()
        total = ma.argument_size_in_bytes + ma.temp_size_in_bytes
        assert total < BYTES_LIMIT - (1 << 28)
