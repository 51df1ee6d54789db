"""The peaks table and the ``sim_tick`` bytes function, checked against
the kernel's own operand and result shapes."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kernels
from repro.kernels.sim_tick.kernel import fleet_tick_kernel


def _pallas_bytes(F, MC, MP, NP):
    i32, f32 = jnp.int32, jnp.float32
    args = [jax.ShapeDtypeStruct((F, MC), d)
            for d in (i32, i32, i32, f32, f32, i32)]
    args += [jax.ShapeDtypeStruct((F, MP), i32)] * 3
    args += [jax.ShapeDtypeStruct((F,), i32)]
    jaxpr = jax.make_jaxpr(
        lambda *a: fleet_tick_kernel(*a, num_pools=NP, interpret=True))(*args)
    eqns = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    if not eqns:  # the kernel is jitted: look one level down
        inner = jaxpr.jaxpr.eqns[0].params["jaxpr"].jaxpr
        eqns = [e for e in inner.eqns if e.primitive.name == "pallas_call"]
    (call,) = eqns
    avals = [v.aval for v in list(call.invars) + list(call.outvars)]
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in avals)


@pytest.mark.parametrize("F", [1, 64, 300])
def test_sim_tick_bytes_match_the_kernel_shapes(F):
    assert kernels.sim_tick_bytes(F, 64, 2048, 2) == _pallas_bytes(
        F, 64, 2048, 2)


def test_peaks_are_keyed_by_device_kind():
    v5e = kernels.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        kernels.peaks("cpu")
