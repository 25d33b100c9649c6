"""The serving path's Pallas kernels compile for a TPU v5e at
camformer-bert's widths (16 kv heads x head_dim 64, page 64, CAM group 16,
batch 8, max_len 1024), with no chip attached.

The chip's compiler is installed with jax and compiles for a described
``v5e:2x2`` topology; it refuses what interpret mode accepts (block shapes
off the (8, 128) tiling, operations Mosaic does not lower).  Each test
asserts the compiled program holds the kernel as a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and test workers import every test file.  Keep these tests in this one
file, so that only the worker given it loads the library.  libtpu is a
pinned dependency, so a topology that cannot be described fails the
tests; nothing here skips.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.bacam_decode import bacam_paged_topk_stage1
from repro.kernels.paged_flash_decode import paged_flash_decode

B, HKV, G, D, PAGE, GROUP = 8, 16, 1, 64, 64, 16
NP = 1024 // PAGE
N_PAGES = 1 + B * NP


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _scalars(s, np_=NP):
    return (_sds(s, (B, np_), jnp.int32), _sds(s, (B,), jnp.int32),
            _sds(s, (B,), jnp.int32))


def _assert_kernel(compiled, name):
    """The compiled program holds the kernel as a ``tpu_custom_call``
    instruction named after it (``%<name>.<n>``: the kernel's ``name=``,
    which the device trace's op events carry)."""
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    named = re.compile(rf"^\s*(ROOT )?%{name}(\.\d+)? = ")
    assert any(named.match(ln) for ln in calls), calls


# camformer-bert (GQA group 1, max_len 1024), and a long-context GQA-8
# deployment (max_len 131072): the output blocks must not grow with max_len
# (blocks holding a slot's whole (R, NP, ncp) candidate table need 32 MiB of
# scoped VMEM there, over the 16 MiB limit)
@pytest.mark.parametrize("rows,np_", [(G, NP), (8, 2048)],
                         ids=["camformer-bert", "gqa8-128k"])
def test_bacam_paged_topk_stage1_compiles(one_chip, rows, np_):
    w = D // 32
    fn = lambda q, k, pt, kvl, qp: bacam_paged_topk_stage1(
        q, k, pt, kvl, qp, d=D, group=GROUP, stage1_k=2, interpret=False)
    compiled = jax.jit(fn).lower(
        _sds(one_chip, (B, HKV, rows, w), jnp.uint32),
        _sds(one_chip, (1 + B * np_, HKV, PAGE, w), jnp.uint32),
        *_scalars(one_chip, np_)).compile()
    _assert_kernel(compiled, "bacam_paged_topk_stage1")


@pytest.mark.parametrize("sq,binary", [(1, False), (1, True), (256, False)],
                         ids=["decode-dense", "decode-binary", "chunk-256"])
def test_paged_flash_decode_compiles(one_chip, sq, binary):
    fn = lambda q, k, v, pt, kvl, qp: paged_flash_decode(
        q, k, v, pt, kvl, qp, sq=sq, binary=binary, interpret=False)
    pool = _sds(one_chip, (N_PAGES, HKV, PAGE, D), jnp.bfloat16)
    compiled = jax.jit(fn).lower(
        _sds(one_chip, (B, HKV, G * sq, D), jnp.float32), pool, pool,
        *_scalars(one_chip)).compile()
    _assert_kernel(compiled, "paged_flash_decode")
