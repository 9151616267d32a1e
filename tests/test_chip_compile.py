"""Ask the TPU v5e's compiler about the verifier without a chip.

The compiler for a described (not attached) `v5e:2x2` is installed
beside JAX, so what it refuses shows here at no chip time. Tier 1
compiles the PIECES of the production kernel at real width (B = 1024,
the bucket a 1,000-transaction set dispatches), each a standalone jit;
the two whole kernels at 1,024 and 4,096 lanes and the
four-device shard_map program cost minutes each and are marked `slow`.

`fe8._use_rolled` asks `jax.default_backend()` at trace time and these
tests run under JAX_PLATFORMS=cpu, so it is steered here: the multiply
that ships on the chip (`_mul_rolled`) is the one compiled. A compile
that passes is not a chip run — nothing executes.

All of it stays in ONE file: the process that describes the topology
holds libtpu's lock until it exits (on-chip-measurement guide §2).
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from stellar_core_tpu.ops import ed25519_kernel as ek
from stellar_core_tpu.ops import fe8, sha512
from stellar_core_tpu.ops.verifier import _bucket_size, make_sharded_verify

B_TXSET = _bucket_size(1000)         # 1,000-payment transaction set
B_CHECKPOINT = _bucket_size(4000)    # a checkpoint of 60 small ledgers


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                           # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A described-chip executable is written to the persistent cache
    but cannot be read back without a chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _chip_multiply(monkeypatch):
    monkeypatch.setattr(fe8, "_use_rolled", lambda: True)


def _compile(fn, *shapes):
    """Lower + compile `fn` for the described chip; returns
    (compiled, seconds)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled, time.perf_counter() - t0


def _limbs(sharding, b=B_TXSET, rows=32):
    return jax.ShapeDtypeStruct((rows, b), jnp.int32, sharding=sharding)


def _u8(sharding, b=B_TXSET):
    return jax.ShapeDtypeStruct((b, 32), jnp.uint8, sharding=sharding)


def _report(name, compiled, secs):
    m = compiled.memory_analysis()
    print(f"\nCHIP_COMPILE {name}: {secs:.1f}s "
          f"code={m.generated_code_size_in_bytes} "
          f"temp={m.temp_size_in_bytes} "
          f"args={m.argument_size_in_bytes} "
          f"out={m.output_size_in_bytes}")
    return m


# ------------------------------------------------- pieces (tier 1) --------

def test_mul_rolled_compiles(one_chip):
    x = _limbs(one_chip)
    compiled, secs = _compile(fe8._mul_rolled, x, x)
    _report("fe8._mul_rolled", compiled, secs)


def _select_and_add(x, y, z, t, table, ws, wk):
    """The second half of `double_scalarmult_w2`'s scan body: the
    arithmetic one-hot table select and one cached add."""
    sel = ((ws + 4 * wk)[None, :] ==
           jnp.arange(16, dtype=jnp.int32)[:, None])
    q = jnp.einsum("tclb,tb->clb", table, sel.astype(jnp.int32))
    return ek.ge_add_cached((x, y, z, t), (q[0], q[1], q[2], q[3]))


def _ladder_args(one_chip):
    x = _limbs(one_chip)
    table = jax.ShapeDtypeStruct((16, 4, 32, B_TXSET), jnp.int32,
                                 sharding=one_chip)
    w = jax.ShapeDtypeStruct((B_TXSET,), jnp.int32, sharding=one_chip)
    return (x, x, x, x, table, w, w)


def test_ladder_doubling_compiles(one_chip):
    x = _limbs(one_chip)
    compiled, secs = _compile(
        lambda a, b, c: ek.ge_dbl_w((a, b, c, None)), x, x, x)
    _report("ge_dbl_w", compiled, secs)


def test_ladder_select_and_add_compiles(one_chip):
    compiled, secs = _compile(_select_and_add, *_ladder_args(one_chip))
    _report("table select + ge_add_cached", compiled, secs)


def test_sha512_96_compiles(one_chip):
    u = _u8(one_chip)
    compiled, secs = _compile(sha512.sha512_96, u, u, u)
    _report("sha512.sha512_96", compiled, secs)


def test_k_mod_l_compiles(one_chip):
    compiled, secs = _compile(sha512.mod_l, _limbs(one_chip, rows=64))
    _report("sha512.mod_l", compiled, secs)


# ------------------------- pieces over ten seconds here (slow) -----------

@pytest.mark.slow
def test_ladder_step_compiles(one_chip):
    """One whole iteration of the scan body: two doublings, the select
    and the add (14.5 s on this sandbox's 8 cores)."""
    def step(x, y, z, t, table, ws, wk):
        p = ek.ge_dbl_w(ek.ge_dbl_w((x, y, z, t), need_t=False))
        return _select_and_add(*p, table, ws, wk)
    compiled, secs = _compile(step, *_ladder_args(one_chip))
    _report("ladder step", compiled, secs)


@pytest.mark.slow
def test_decompress_chain_compiles(one_chip):
    """Strict decompression with its 2^252-3 power chain (23.5 s)."""
    sign = jax.ShapeDtypeStruct((B_TXSET,), jnp.int32, sharding=one_chip)
    compiled, secs = _compile(ek.decompress_neg, _limbs(one_chip), sign)
    _report("decompress_neg", compiled, secs)


# ------------------------------------- whole programs (slow, minutes) -----

@pytest.mark.slow
@pytest.mark.parametrize("bucket", [B_TXSET, B_CHECKPOINT])
@pytest.mark.parametrize("kernel", ["verify_kernel_msg32",
                                    "verify_kernel_full"])
def test_whole_kernel_compiles(one_chip, kernel, bucket):
    u = _u8(one_chip, bucket)
    compiled, secs = _compile(getattr(ek, kernel), u, u, u, u)
    m = _report(f"{kernel} B={bucket}", compiled, secs)
    # one chip holds 16 GB; the program must leave room for its inputs
    assert m.temp_size_in_bytes + m.generated_code_size_in_bytes < 12e9


@pytest.mark.slow
def test_four_device_program_compiles(topo):
    mesh = Mesh(np.array(topo.devices), ("dp",))
    assert mesh.size == 4
    sh = NamedSharding(mesh, PartitionSpec("dp", None))
    u = jax.ShapeDtypeStruct((B_CHECKPOINT, 32), jnp.uint8, sharding=sh)
    t0 = time.perf_counter()
    compiled = make_sharded_verify(
        mesh, "dp", ek.verify_kernel_msg32).lower(u, u, u, u).compile()
    _report(f"make_sharded_verify msg32 4 devices B={B_CHECKPOINT}",
            compiled, time.perf_counter() - t0)
    # signatures share no state: the only collective a data-parallel
    # verify may carry is the result gather, never an exchange of tuples
    text = compiled.as_text()
    assert "all-to-all" not in text and "collective-permute" not in text
