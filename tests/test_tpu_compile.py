"""The served Pallas kernels compile for a TPU v5e at TinyLlama-1.1B widths
(and the packed GEMM at the widths the chip benchmark serves).

Interpret mode (every other kernel test) never shows what Mosaic refuses:
unsupported casts, blocks that break the (8, 128) tiling, reshapes of the
lane axis. These tests compile each kernel of the served path for a
*described* v5e (no chip needed) and check that the compiled program
holds the Mosaic kernel. The topology is described inside a module
fixture: only one process at a time may load the TPU compiler's library,
so it must never be loaded while test modules are imported.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, packing

D, D_FF, KV_DIM, H, DH = 2048, 5632, 256, 32, 64     # TinyLlama-1.1B
BATCH = 4
DS_D, DS_FF, DS_KV, DS_LANES = 8192, 22016, 1024, 64  # DeepSeek-67B, served


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out while these tests run
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _assert_mosaic(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,k,n,t3", [
    (BATCH, D, D_FF, False),       # decode rows, ffn_in
    (BATCH, D_FF, D, True),        # decode rows, ffn_down (fused T3)
    (256, D, KV_DIM, False),       # prefill rows, k/v projection
    (256, D_FF, D, True),          # prefill rows, ffn_down (fused T3)
    (DS_LANES, DS_D, DS_FF, False),    # the benchmark's decode, ffn_in
    (DS_LANES, DS_FF, DS_D, True),     # the benchmark's decode, ffn_down
    (256, DS_D, DS_KV, False),         # the benchmark's prefill, k/v
])
def test_mx_gemm_packed_compiles(spec, m, k, n, t3):
    _assert_mosaic(
        lambda x, w, s: ops.mx_gemm_packed(x, w, s, "mxfp4", t3=t3,
                                           interpret=False),
        spec((m, k), jnp.float32), spec((k // 2, n), jnp.uint8),
        spec((k // 32, n), jnp.uint8))


def _pool(spec, pages, page, fmt):
    code_bytes = KV_DIM * packing.kv_fmt_bits(fmt) // 8
    return (spec((pages, page, code_bytes), jnp.uint8),
            spec((pages, page, KV_DIM // 32), jnp.uint8)) * 2


@pytest.mark.parametrize("page,fmt", [(64, "mxfp8"), (128, "mxfp8"),
                                      (1024, "mxfp8"), (1024, "mxfp4")])
def test_mx_flash_decode_paged_compiles(spec, page, fmt):
    maxp = 4096 // page
    _assert_mosaic(
        lambda q, kc, ks, vc, vs, bt, qp, kl: ops.mx_flash_decode_paged(
            q, kc, ks, vc, vs, bt, qp, kl, fmt, interpret=False),
        spec((BATCH, H, DH), jnp.float32), *_pool(spec, 64, page, fmt),
        spec((BATCH, maxp), jnp.int32), spec((BATCH,), jnp.int32),
        spec((BATCH,), jnp.int32))


@pytest.mark.parametrize("page,fmt", [(64, "mxfp8"), (128, "mxfp8"),
                                      (128, "mxfp4")])
def test_mx_flash_prefill_compiles(spec, page, fmt):
    lanes, chunk = 2, 128                  # 256 prefill rows
    _assert_mosaic(
        lambda q, k, v, kc, ks, vc, vs, bt, qs, kl: ops.mx_flash_prefill(
            q, k, v, kc, ks, vc, vs, bt, qs, kl, fmt, interpret=False)[0],
        spec((lanes, chunk, H, DH), jnp.float32),
        spec((lanes, chunk, KV_DIM), jnp.float32),
        spec((lanes, chunk, KV_DIM), jnp.float32),
        *_pool(spec, 64, page, fmt),
        spec((lanes, 8), jnp.int32), spec((lanes,), jnp.int32),
        spec((lanes,), jnp.int32))
