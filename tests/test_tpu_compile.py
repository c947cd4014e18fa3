"""Main-path kernels compiled for a described TPU v5e, no chip attached.

Interpret mode cannot see Mosaic's tiling, layout or memory-space rules;
the TPU compiler installed with JAX can, for a topology it is only told
about.  Each test lowers one kernel at the widths ``chip_smoke.py`` runs
(D = 2^22, m = 3) and compiles it for one v5e chip.  The batched share
kernels hold every party's keys in SMEM, so they also compile at the
party engine's default party chunk.  The party engine's own executables
(keys and share kernel; the reconstruct epilogue) compile at its
benchmark round's 16 parties.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.aggregation import (PARTY_CHUNK, SecureAggregator,
                                    _kernel_mean, _kernel_shares)
from repro.core.fixed_point import DEFAULT_FIELD, DEFAULT_RING
from repro.kernels.reconstruct.ops import _reconstruct_jit
from repro.kernels.shamir.ops import (_shamir_reconstruct_jit,
                                      _shamir_share_batch_jit,
                                      _shamir_share_jit)
from repro.kernels.share_gen.ops import _share_gen_batch_jit, _share_gen_jit
from repro.kernels.verify_shares.ops import _verify_shares_jit

D, M, PARTIES = 1 << 22, 3, 4
#: parties of the party engine's benchmark round
ENGINE_L = 16
ROWS = D // 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-device compile can be written to the cache but never
    read back, so the persistent cache stays off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_case(name, spec, parties=PARTIES, d_batch=D):
    """(jitted op, keyword arguments) of one kernel's compiled path."""
    u32 = jnp.uint32
    compiled = dict(use_ref=False, interpret=False)
    flat = spec((D,), jnp.float32)
    flats = spec((parties, d_batch), jnp.float32)
    keys, scalar = spec((parties, 2), u32), spec((), u32)
    stack = spec((M, ROWS, 128), u32)
    engine_flats = spec((ENGINE_L, D), jnp.float32)
    return {
        "share_gen": (_share_gen_jit, dict(
            flat=flat, key0=scalar, key1=scalar, m=M, cfg=DEFAULT_RING,
            hi_base=0, block_rows=64, layout="tiled", **compiled)),
        "share_gen_batch": (_share_gen_batch_jit, dict(
            flats=flats, keys=keys, row_base=scalar, m=M, cfg=DEFAULT_RING,
            hi_base=0, block_rows=64, layout="flat", **compiled)),
        "shamir_share": (_shamir_share_jit, dict(
            flat=flat, key0=scalar, key1=scalar, m=M, cfg=DEFAULT_FIELD,
            degree=None, hi_base=0, block_rows=64, layout="tiled",
            **compiled)),
        "shamir_share_batch": (_shamir_share_batch_jit, dict(
            flats=flats, keys=keys, row_base=scalar, m=M,
            cfg=DEFAULT_FIELD, degree=None, hi_base=0, block_rows=64,
            layout="flat", **compiled)),
        "reconstruct": (_reconstruct_jit, dict(
            shares=stack, n=1, cfg=DEFAULT_RING, block_rows=64,
            **compiled)),
        "shamir_reconstruct": (_shamir_reconstruct_jit, dict(
            member_sums=stack, n=1, cfg=DEFAULT_FIELD, points=None,
            block_rows=64, **compiled)),
        "verify_shares": (_verify_shares_jit, dict(
            rows=spec((M, D), u32), commits_em=spec((D, M, 2), u32),
            points=(1, 2, 3), block_rows=8, **compiled)),
        # the party engine's kernel path: keys + share kernel, and the
        # reconstruct epilogue, each one executable
        "engine_shares": (_kernel_shares, dict(
            agg=SecureAggregator(m=M), interpret=False, flats=engine_flats,
            seed_words=(scalar, scalar), stream_lo=spec((ENGINE_L,), u32),
            stream_hi=scalar, row_base=scalar)),
        "engine_shamir_shares": (_kernel_shares, dict(
            agg=SecureAggregator(scheme="shamir", m=M), interpret=False,
            flats=engine_flats, seed_words=(scalar, scalar),
            stream_lo=spec((ENGINE_L,), u32), stream_hi=scalar,
            row_base=scalar)),
        "engine_mean": (_kernel_mean, dict(
            agg=SecureAggregator(m=M), interpret=False, points=None,
            member_sums=spec((M, D), u32), n=spec((), jnp.float32))),
        "engine_shamir_mean": (_kernel_mean, dict(
            agg=SecureAggregator(scheme="shamir", m=M), interpret=False,
            points=None, member_sums=spec((M, D), u32),
            n=spec((), jnp.float32))),
    }[name]


@pytest.mark.parametrize("name", [
    "share_gen", "share_gen_batch", "shamir_share", "shamir_share_batch",
    "reconstruct", "shamir_reconstruct", "verify_shares", "engine_shares",
    "engine_shamir_shares", "engine_mean", "engine_shamir_mean"])
def test_mpc_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, kwargs = _kernel_case(name, spec)
    compiled = fn.lower(**kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["share_gen_batch", "shamir_share_batch"])
def test_batch_kernel_compiles_at_party_chunk(name, one_chip,
                                              no_compile_cache):
    """All ``PARTY_CHUNK`` parties' keys fit the kernel's SMEM operand."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, kwargs = _kernel_case(name, spec, parties=PARTY_CHUNK,
                              d_batch=64 * 128)
    compiled = fn.lower(**kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_training_attention_grad_compiles_for_v5e(one_chip, no_compile_cache,
                                                  monkeypatch):
    """``attention(impl="auto")`` under ``jax.grad`` on a TPU takes the
    XLA path: the step compiles and holds no (VJP-less) Pallas kernel."""
    from repro.configs import get_config
    from repro.models.attention import attention

    cfg = get_config("qwen1.5-0.5b")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = (8, cfg.n_heads, 128, cfg.d_head)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v, cfg).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile()
    assert "tpu_custom_call" not in compiled.as_text()
