"""The checkpoint kernels compile for a described TPU v5e at real sizes.

Interpret mode runs the kernels' Python semantics and cannot see what
the TPU compiler refuses (unsigned reductions, unaligned tiles, VMEM
limits).  These tests hand the real compiler a described ``v5e:2x2``
topology; nothing runs, so no chip is needed.  The topology is
described inside a fixture, never at import time: only one process at a
time may load the TPU library.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.delta_mask import delta_mask_pallas
from repro.kernels.page_digest import page_digest_pallas

# one 256 MiB leaf in 16 KiB pages: (pages, u32 words per page)
DIGEST_SHAPE = (16384, 4096)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without the chip: keep it out entirely
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compiled_text(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("kernel,shape", [
    (lambda x: page_digest_pallas(x), DIGEST_SHAPE),
    (lambda x: delta_mask_pallas(x, x), (DIGEST_SHAPE[0], 2)),
], ids=["page_digest", "delta_mask"])
def test_kernel_compiles_for_v5e(kernel, shape, one_chip, no_compile_cache):
    x = jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(jax.jit(kernel), x)


def test_expert_gmm_compiles_for_v5e(one_chip, no_compile_cache):
    """The expert layer's grouped products, forward and both gradients, at
    OLMoE's widths: 4,096 tokens x top-8 pairs over the 8 experts held."""
    from repro.kernels.grouped_matmul import grouped_matmul_pallas

    def step(lhs, rhs, sizes):
        return jax.grad(lambda a, b: jnp.sum(grouped_matmul_pallas(a, b, sizes)),
                        argnums=(0, 1))(lhs, rhs)

    lhs = jax.ShapeDtypeStruct((32768, 2048), jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((8, 2048, 1024), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    text = _compiled_text(jax.jit(step), lhs, rhs, sizes)
    assert "tpu_custom_call" in text
    # the kernels' device ops carry the names a trace selects them by
    # (``bench/metrics/expert_gmm_roofline.py``)
    ops = re.findall(r"^\s*(?:ROOT )?%(\S+) = \S+ custom-call\(", text, re.M)
    kernel = re.compile(r"(^|_)expert_t?gmm(___)?(\.\d+)?$")
    assert {"gmm", "tgmm"} == {re.search(r"t?gmm", o).group() for o in ops if kernel.search(o)}
