"""The main path's Pallas kernels, compiled for a described TPU v5e.

No chip is attached: `jax.experimental.topologies` describes a v5e and
the TPU compiler builds for it, so Mosaic refuses here what it would
refuse on the chip (tiling, scoped VMEM).  Shapes are the headline
widths: Handel 2048 nodes (Q16, S12, W64), GSFSignature 4096 nodes
(Q16, S16, W128), and the headline ring R16 x H256 x N2048 x C12 with
a K=2 binning batch.  Kernels are called with ``interpret=False``
explicitly, since the program's own choice follows the process backend
(the CPU here).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every pytest-xdist
worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

I32, U32 = jnp.int32, jnp.uint32


@pytest.fixture(scope="module")
def topo():
    prev = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if prev is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory on one described chip, with the
    persistent compile cache off (a described-chip compile is written
    to it but can never be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _has_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_merge_queue_compiles(sds):
    from wittgenstein_tpu.ops.pallas_merge import merge_queue_pallas
    m, q, s, w = 2048, 16, 12, 64
    args = [sds((m, q), I32)] * 3 + [sds((m, q), bool),
                                     sds((m, q, w), U32)] + \
        [sds((m, s), I32)] * 3 + [sds((m, s), bool), sds((m, s, w), U32)]
    _has_kernel(merge_queue_pallas.lower(*args, q_cap=q,
                                         interpret=False).compile())


def test_score_queue_compiles(sds):
    from wittgenstein_tpu.ops.pallas_score import score_queue_pallas
    m, q, w = 2048, 16, 64
    args = [sds((m, q, w), U32), sds((m, q), I32), sds((m,), I32)] + \
        [sds((m, w), U32)] * 3
    _has_kernel(score_queue_pallas.lower(*args, interpret=False).compile())


def test_gsf_score_compiles(sds):
    from wittgenstein_tpu.ops.pallas_score import gsf_score_pallas
    m, q, w = 4096, 16, 128
    args = [sds((m, q, w), U32), sds((m, q), I32), sds((m,), I32)] + \
        [sds((m, w), U32)] * 2
    _has_kernel(gsf_score_pallas.lower(*args, interpret=False).compile())


def test_gsf_merge_compiles(sds):
    from wittgenstein_tpu.ops.pallas_gsf_merge import gsf_merge_pallas
    m, q, s, w, levels = 4096, 16, 16, 128, 12
    args = [sds((m, q), I32)] * 2 + [sds((m, q), bool)] * 2 + \
        [sds((m, q, w), U32)] + [sds((m, s), I32)] * 2 + \
        [sds((m, s), bool)] * 2 + [sds((m, s, w), U32)]
    _has_kernel(gsf_merge_pallas.lower(*args, levels=levels,
                                       interpret=False).compile())


def test_route_kernel_refused_at_headline(sds):
    """The routing kernel's launcher refuses the headline ring at build
    time: no destination block obeys the (8, 128) tiling rule inside
    its VMEM budget, and the error points to the XLA path."""
    from wittgenstein_tpu.ops.pallas_route import bin_into_ring_planes
    r, hzn, n, c, f, k = 16, 256, 2048, 12, 2, 2
    m = k * n
    flat = sds((r, hzn * n * c), I32)

    def launch(data, src, size, cnt, h, dest, msrc, msize, pay, valid):
        return bin_into_ring_planes(
            data, (src,), (size,), cnt, h, dest, msrc, msize, pay, valid,
            horizon=hzn, cap=c, n=n, split=1, payload_words=f,
            seed_axis=True, interpret=False)

    args = ((flat,) * f, flat, flat, sds((r, hzn, n), I32)) + \
        (sds((r, m), I32),) * 4 + (sds((r, m, f), I32), sds((r, m), bool))
    with pytest.raises(ValueError, match=r"\(8, 128\) tiling.*XLA"):
        jax.jit(launch).lower(*args)
