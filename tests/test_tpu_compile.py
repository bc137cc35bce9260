"""The fused FlyMC kernels compile for a TPU v5e at the paper's shapes.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, so Mosaic's refusals (unaligned DMA slices, scalar stores to
VMEM, loops it cannot legalize, more SMEM or VMEM than the chip has) fail
here rather than on the chip. Every case passes ``interpret=False`` and
compiles at the Table-1 shape and capacity (``benchmarks/table1.py``), for
one chain and for the 8-chain grid. The topology is described inside a
fixture (one process may hold the TPU library at a time, so nothing here
touches it at import), and the persistent compilation cache is off around
these compiles: an entry written without a chip cannot be read back.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.table1 import PROBLEMS, capacity_for

_LOGISTIC, _SOFTMAX, _ROBUST = PROBLEMS
_FAMILY_SHAPE = {  # family -> (N, D, classes)
    "logistic": (_LOGISTIC.n, _LOGISTIC.d, 0),
    "softmax": (_SOFTMAX.n, _SOFTMAX.d, 3),
    "student_t": (_ROBUST.n, _ROBUST.d, 0),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # No escape: without a TPU compiler every case here fails, loudly.
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, args, chains, batched):
    """Compile ``fn`` (vmapped over the ``batched`` args for chains > 1)."""
    if chains > 1:
        fn = jax.vmap(fn, in_axes=tuple(0 if b else None for b in batched))
        args = [
            jax.ShapeDtypeStruct((chains,) + a.shape, a.dtype,
                                 sharding=a.sharding) if b else a
            for a, b in zip(args, batched)
        ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1
    return compiled


@pytest.mark.parametrize("chains", [1, 8])
@pytest.mark.parametrize("family", ["logistic", "student_t", "softmax"])
def test_bright_glm_compiles_for_v5e(one_chip, family, chains):
    from repro.kernels.bright_glm.ops import bright_glm
    from repro.kernels.common import pad_to

    n, d, k = _FAMILY_SHAPE[family]
    cap = capacity_for(n)
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    if family == "softmax":
        t, xi, theta = s((n,), jnp.int32), s((n, k)), s((k, d))
    else:
        t, xi, theta = s((n,)), s((n,)), s((d,))
    args = [s((n, 1, pad_to(d, 128))), t, xi, s((cap,), jnp.int32),
            s((), jnp.int32), theta]

    def fn(x_rows, t, xi, idx, nb, theta):
        return bright_glm(x_rows, t, xi, idx, nb, theta, family=family,
                          interpret=False)

    _compile(fn, args, chains, batched=(False, False, False, True, True, True))


@pytest.mark.parametrize("chains", [1, 8])
@pytest.mark.parametrize("n", [p.n for p in PROBLEMS])
def test_z_candidates_compiles_for_v5e(one_chip, n, chains):
    from repro.kernels.z_update.ops import z_candidates

    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def fn(arr, num, words):
        return z_candidates(arr, num, words, 0.01, capacity_for(n),
                            interpret=False)

    _compile(fn, [s((n,)), s(()), s((2,))], chains,
             batched=(True, True, True))
