"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.delta_mask import delta_mask_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.linear_scan import linear_scan_pallas
from repro.kernels.page_digest import page_digest_pallas

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------- page digest
@pytest.mark.parametrize("n_pages,n_words", [(1, 512), (3, 512), (8, 1024), (17, 1536)])
def test_page_digest_matches_ref(n_pages, n_words):
    x = jnp.asarray(RNG.integers(0, 2**32, (n_pages, n_words), dtype=np.uint32))
    got = page_digest_pallas(x, interpret=True)
    want = ref.ref_page_digest(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_page_digest_order_sensitive():
    x = jnp.asarray(RNG.integers(0, 2**32, (1, 512), dtype=np.uint32))
    perm = x[:, ::-1]
    a = np.asarray(page_digest_pallas(x, interpret=True))
    b = np.asarray(page_digest_pallas(perm, interpret=True))
    assert not np.array_equal(a, b)


def test_page_digest_single_bit_sensitivity():
    x = jnp.zeros((2, 512), jnp.uint32)
    for word in [0, 137, 511]:
        y = x.at[1, word].set(1)
        d = np.asarray(page_digest_pallas(y, interpret=True))
        assert not np.array_equal(d[0], d[1]), f"word {word} collision"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_ops_page_digest_dtypes(dtype, monkeypatch):
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    x = jnp.asarray(RNG.standard_normal(5000), jnp.float32).astype(dtype)
    d_pal = ops.page_digest(x, page_bytes=4096)
    monkeypatch.delenv("REPRO_PALLAS")
    d_ref = ops.page_digest(x, page_bytes=4096)
    np.testing.assert_array_equal(np.asarray(d_pal), np.asarray(d_ref))


# A leaf sharded over a 1x4 mesh is digested under shard_map, each device
# on its share of the pages.  Runs in a child with 4 virtual CPU devices so
# this process keeps seeing one.
_MESH_CHILD = """
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels import ops
from repro.kernels.hostdigest import host_page_digest
from repro.launch.mesh import make_mesh

PB = 2048
mesh = make_mesh((1, 4), ("data", "model"))
split = NamedSharding(mesh, P(None, "model"))
one = jax.devices()[0]
base = np.random.default_rng(0).standard_normal((4, 1200)) * 1000
out = {}
for name in ("float32", "bfloat16", "int32"):
    x = jnp.asarray(base, jnp.float32).astype(name)
    y = x.at[0, 7].set(12345).at[3, 1199].set(-777)
    d_mesh = ops.page_digest(jax.device_put(x, split), PB)
    d_one = ops.page_digest(jax.device_put(x, one), PB)
    o_mesh = ops.page_digest(jax.device_put(y, split), PB)
    o_one = ops.page_digest(jax.device_put(y, one), PB)
    m_mesh = ops.delta_mask(d_mesh, o_mesh)
    m_one = ops.delta_mask(d_one, o_one)
    raw = np.asarray(x).reshape(-1).view(np.uint8)
    host = [host_page_digest(raw[p * PB:(p + 1) * PB].tobytes(), PB)
            for p in range(d_one.shape[0])]
    out[name] = {
        "n_pages": int(d_one.shape[0]),
        "devices": [len(d_mesh.sharding.device_set), len(m_mesh.sharding.device_set)],
        "digest_equal": bool(np.array_equal(np.asarray(d_mesh), np.asarray(d_one))),
        "host_equal": [tuple(int(v) for v in r) for r in np.asarray(d_one)] == host,
        "mask_mesh": np.asarray(m_mesh).tolist(),
        "mask_one": np.asarray(m_one).tolist(),
    }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_digests():
    import json
    import os
    import subprocess
    import sys
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu", REPRO_PALLAS="interpret",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    run = subprocess.run([sys.executable, "-c", _MESH_CHILD], env=env, cwd=root,
                         capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stderr[-3000:]
    return json.loads(run.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
def test_ops_digest_on_mesh_matches_one_device(mesh_digests, dtype):
    got = mesh_digests[dtype]
    assert got["n_pages"] % 4 != 0  # the pages are padded to the mesh size
    assert got["devices"] == [4, 4]  # the shard_map path ran
    assert got["digest_equal"] and got["host_equal"]
    assert got["mask_mesh"] == got["mask_one"]
    assert got["mask_one"][0] and got["mask_one"][-1] and sum(got["mask_one"]) == 2


# ---------------------------------------------------------------- delta mask
def test_delta_mask_matches_ref():
    new = jnp.asarray(RNG.integers(0, 2**32, (300, 2), dtype=np.uint32))
    old = new.at[17, 0].add(1).at[255, 1].add(3)
    got = delta_mask_pallas(new, old, interpret=True) != 0
    want = ref.ref_delta_mask(new, old)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(got.sum()) == 2


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize(
    "B,Hq,Hkv,Tq,Tk,D,causal,window",
    [
        (2, 4, 2, 64, 64, 32, True, None),     # GQA causal
        (1, 8, 1, 37, 37, 16, True, None),     # MQA, ragged T
        (2, 2, 2, 50, 70, 8, False, None),     # cross-ish, pad_k
        (1, 4, 2, 96, 96, 64, True, 24),       # sliding window
        (1, 2, 1, 1, 40, 16, True, None),      # decode shape
        (1, 4, 4, 128, 128, 128, True, None),  # TPU-aligned
    ],
)
def test_flash_attention_matches_ref(B, Hq, Hkv, Tq, Tk, D, causal, window):
    q = jnp.asarray(RNG.standard_normal((B, Hq, Tq, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, Hkv, Tk, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, Hkv, Tk, D)), jnp.float32)
    qo = Tk - Tq if causal else 0
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_offset=qo, interpret=True)
    want = ref.ref_attention(q, k, v, causal=causal, window=window, q_offset=qo)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.standard_normal((1, 4, 64, 32)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, interpret=True)
    want = ref.ref_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
    )


def test_flash_attention_softcap():
    q = jnp.asarray(RNG.standard_normal((1, 2, 32, 16)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 32, 16)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 32, 16)), jnp.float32)
    got = flash_attention_pallas(q, k, v, softcap=20.0, interpret=True)
    want = ref.ref_attention(q, k, v, softcap=20.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# --------------------------------------------------------------- linear scan
@pytest.mark.parametrize("B,T,D", [(2, 64, 32), (3, 100, 17), (1, 1, 8), (4, 257, 130)])
def test_linear_scan_matches_ref(B, T, D):
    a = jnp.asarray(RNG.uniform(0.5, 0.999, (B, T, D)), jnp.float32)
    x = jnp.asarray(RNG.standard_normal((B, T, D)), jnp.float32)
    got = linear_scan_pallas(a, x, interpret=True)
    want = ref.ref_linear_scan(a, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_linear_scan_is_exclusive_prefix_correct():
    # h_0 must equal x_0 (no pre-existing state)
    a = jnp.full((1, 4, 2), 0.5, jnp.float32)
    x = jnp.ones((1, 4, 2), jnp.float32)
    h = linear_scan_pallas(a, x, interpret=True)
    np.testing.assert_allclose(np.asarray(h[0, 0]), [1.0, 1.0])
    np.testing.assert_allclose(np.asarray(h[0, 1]), [1.5, 1.5])
