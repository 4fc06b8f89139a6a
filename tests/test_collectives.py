"""Direct unit tests for repro.distributed.collectives.

The multi-device integration angles (8-shard unbiasedness, ZeRO-2 shapes
under a real FSDP axis) live in tests/test_distributed.py; this module
pins the primitives themselves: (a) the stochastic-rounding quantizer is
unbiased with bounded variance — tested without any mesh, the math is
device-free; (b) ``compressed_psum`` on a 1-shard mesh reduces to an
(unbiased) quantize/dequantize round trip and is exact on zeros; (c)
``reduce_scatter_grads`` falls back to a whole-tensor psum for leaves
whose leading dim does not divide the axis (subprocess, 4 devices); (d)
the ``shard_map`` shim passes everything to ``jax.shard_map(check_vma=)``
and runs a collective.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import collectives
from repro.distributed.collectives import (_dequantize_block,
                                           _quantize_block,
                                           compressed_psum,
                                           reduce_scatter_grads, shard_map)
from tests.conftest import run_with_devices


# --------------------------------------------------------------------- #
# (a) the quantizer: unbiased, variance-bounded, pure function
# --------------------------------------------------------------------- #
def test_quantizer_unbiased_and_variance_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 100)).astype(np.float32)) * 5.0
    n = 200
    outs = []
    for s in range(n):
        q, scale, shape, pad = _quantize_block(x, jax.random.PRNGKey(s))
        outs.append(np.asarray(_dequantize_block(q, scale, shape, pad)))
    outs = np.stack(outs)
    scale_np = np.asarray(scale).max()
    # unbiased: the empirical mean converges to x (CLT tolerance ~4 sigma
    # of the mean estimator; per-sample sd <= scale/2, the worst case of
    # uniform stochastic rounding)
    tol = 4.0 * (scale_np / 2.0) / np.sqrt(n)
    assert np.abs(outs.mean(0) - np.asarray(x)).max() < tol + 1e-6
    # variance of uniform stochastic rounding is at most scale^2 / 4
    assert outs.var(0).max() <= scale_np**2 / 4 + 1e-6


def test_quantizer_pads_and_restores_shape():
    x = jnp.arange(10, dtype=jnp.float32).reshape(2, 5)  # 10 % 256 != 0
    q, scale, shape, pad = _quantize_block(x, jax.random.PRNGKey(0))
    assert pad == 256 - 10 and shape == (2, 5)
    back = _dequantize_block(q, scale, shape, pad)
    assert back.shape == (2, 5)
    # max-abs scaling keeps every value within one quantum of the input
    assert np.abs(np.asarray(back) - np.asarray(x)).max() \
        <= float(np.asarray(scale).max()) + 1e-6


# --------------------------------------------------------------------- #
# (b) compressed_psum on a single-shard mesh (in-process, 1 device)
# --------------------------------------------------------------------- #
def test_compressed_psum_single_shard_round_trip():
    mesh = jax.make_mesh((1,), ("d",))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 128)).astype(np.float32)) * 2.0

    def f(xs, key):
        return compressed_psum(xs, "d", key)

    g = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("d"), P()),
                          out_specs=P("d"), check_vma=False))
    out = np.asarray(g(x, jax.random.PRNGKey(0)))
    # one shard: the psum is a quantize/dequantize round trip — within
    # one quantization step of the input everywhere
    step = np.abs(np.asarray(x)).max() / 127.0
    assert np.abs(out - np.asarray(x)).max() <= step + 1e-6


def test_compressed_psum_exact_on_zeros():
    mesh = jax.make_mesh((1,), ("d",))
    x = jnp.zeros((1, 64), jnp.float32)
    g = jax.jit(shard_map(lambda xs, k: compressed_psum(xs, "d", k),
                          mesh=mesh, in_specs=(P("d"), P()),
                          out_specs=P("d"), check_vma=False))
    np.testing.assert_array_equal(np.asarray(g(x, jax.random.PRNGKey(0))),
                                  np.zeros((1, 64), np.float32))


# --------------------------------------------------------------------- #
# (c) reduce_scatter_grads: divisible leaves scatter, the rest psum whole
# --------------------------------------------------------------------- #
@pytest.mark.slow
def test_reduce_scatter_non_divisible_fallback():
    code = """
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import reduce_scatter_grads, shard_map

mesh = jax.make_mesh((4,), ("d",))
rng = np.random.default_rng(0)
grads = {
    "w": jnp.asarray(rng.standard_normal((4, 8, 3)).astype(np.float32)),
    "odd": jnp.asarray(rng.standard_normal((4, 5)).astype(np.float32)),
    "scalar": jnp.asarray(rng.standard_normal(4).astype(np.float32)),
}

def f(g):
    g = {"w": g["w"].reshape(8, 3), "odd": g["odd"].reshape(5),
         "scalar": g["scalar"].reshape(())}
    out = reduce_scatter_grads(g, "d")
    # divisible leaf: each shard holds only its slice (ZeRO-2 shape)
    assert out["w"].shape == (2, 3), out["w"].shape
    # non-divisible and scalar leaves: whole-tensor psum fallback
    assert out["odd"].shape == (5,), out["odd"].shape
    assert out["scalar"].shape == (), out["scalar"].shape
    return out["w"], out["odd"], out["scalar"]

g = jax.jit(shard_map(f, mesh=mesh,
    in_specs=({"w": P("d"), "odd": P("d"), "scalar": P("d")},),
    out_specs=(P("d"), P(), P()), check_vma=False))
w, odd, scalar = g(grads)
np.testing.assert_allclose(np.asarray(w),
                           np.asarray(grads["w"]).sum(0), atol=1e-5)
np.testing.assert_allclose(np.asarray(odd),
                           np.asarray(grads["odd"]).sum(0), atol=1e-5)
np.testing.assert_allclose(np.asarray(scalar),
                           np.asarray(grads["scalar"]).sum(), atol=1e-5)
print("RS-FALLBACK-OK")
"""
    out = run_with_devices(code, 4)
    assert "RS-FALLBACK-OK" in out


# --------------------------------------------------------------------- #
# (d) the shard_map shim
# --------------------------------------------------------------------- #
def test_shard_map_shim_new_api_branch(monkeypatch):
    """The shim must call ``jax.shard_map`` with ``check_vma``, passing
    everything through."""
    seen = {}

    def fake(f, mesh, in_specs, out_specs, check_vma):
        seen.update(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                    check_vma=check_vma)
        return "sentinel"

    monkeypatch.setattr(jax, "shard_map", fake, raising=False)
    out = shard_map(lambda x: x, mesh="m", in_specs=(P(),),
                    out_specs=P(), check_vma=False)
    assert out == "sentinel"
    assert seen == {"mesh": "m", "in_specs": (P(),), "out_specs": P(),
                    "check_vma": False}


def test_shard_map_shim_runs_a_collective():
    """The shim builds a real shard_map program: a psum over a one-device
    mesh returns its input."""
    mesh = jax.make_mesh((1,), ("d",))
    fn = jax.jit(collectives.shard_map(
        lambda x: jax.lax.psum(x, "d"), mesh=mesh,
        in_specs=(P("d"),), out_specs=P(), check_vma=False))
    out = np.asarray(fn(jnp.ones((1, 4), jnp.float32)))
    np.testing.assert_array_equal(out, np.ones((1, 4), np.float32))
