"""The tick's Pallas kernels compile for a TPU v5e at paper-scale widths.

Interpret-mode parity tests cannot see what Mosaic, the TPU kernel
compiler, refuses (unaligned slices, lane reshapes, selects between
boolean vectors, integer argmin, ...).  These tests compile each kernel
with ``interpret=False`` for a described ``v5e:2x2`` topology, no chip
attached, at the widths of ``perm_1024n_3t`` (and of ``alltoall_3t`` for
the round-robin pick, which only runs with several flows per sender), and
check that the program holds the compiled kernel.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cc_update import kernel as cc_kernel
from repro.kernels.cc_update import ref as cc_ref
from repro.kernels.enqueue_arb import kernel as arb_kernel
from repro.kernels.ring_drain import kernel as drain_kernel
from repro.netsim import scenarios, state

I32, F32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def dims():
    """Static widths of the two scenarios the kernels are compiled at,
    plus the switch fan-in table's shape ``[switches, max fan-in]``."""
    def of(name):
        sc = scenarios.scenario(name)
        _, _, d, consts = state.derive(sc.cfg, sc.wl)
        return d, tuple(consts.in_tbl.shape)
    (perm, groups), (alltoall, _) = of("perm_1024n_3t"), of("alltoall_3t")
    return dict(perm=perm, alltoall=alltoall, groups=groups)


@pytest.fixture(scope="module")
def programs(one_chip, dims):
    """(kernel callable with its statics bound, argument shapes) per
    kernel."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    d, a = dims["perm"], dims["alltoall"]
    nf, w, ww, maxw = d.NF, d.W, d.WW, d.MAXW
    groups = dims["groups"]
    flows = lambda dt, names: tuple(arg((nf,), dt) for _ in names)
    return {
        "cc_update": (
            lambda *xs: cc_kernel.cc_update(*xs, interpret=False),
            (arg((len(cc_ref.PARAM_FIELDS),), F32), arg((), I32),
             arg((nf,), F32), arg((nf,), F32), arg((nf,), F32),
             flows(F32, cc_ref.STATE_F32), flows(I32, cc_ref.STATE_I32),
             flows(F32, cc_ref.EVENT_F32), flows(I32, cc_ref.EVENT_I32))),
        "enqueue_rank": (
            lambda *xs: arb_kernel.enqueue_rank(
                *xs, cap=d.CAP, nq=d.NQ, interpret=False),
            (arg(groups, I32),) * 3),
        "rr_pick": (
            lambda e, r: arb_kernel.rr_pick(e, r, kmax=a.FMAX,
                                            interpret=False),
            (arg((a.N, a.FMAX), jnp.bool_), arg((a.N,), I32))),
        "ring_drain": (
            lambda *xs: drain_kernel.ring_drain(
                *xs, w=w, ww=ww, maxw=maxw, interpret=False),
            (arg((), I32), arg((nf,), F32), arg((nf,), jnp.bool_),
             arg((nf,), jnp.bool_), arg((nf,), I32), arg((nf, ww), I32),
             arg((nf, maxw), I32), arg((nf, w), I32), arg((nf, w), I32),
             arg((nf, w), I32))),
    }


def test_paper_scale_widths(dims):
    d, a = dims["perm"], dims["alltoall"]
    assert (d.NF, d.W, d.WW, d.MAXW) == (1024, 64, 2, 2)
    assert dims["groups"] == (176, 20)
    assert a.FMAX == 31 and a.N == 512


@pytest.mark.parametrize("name", ["cc_update", "enqueue_rank", "rr_pick",
                                  "ring_drain"])
def test_kernel_compiles_for_v5e(name, programs, no_persistent_cache):
    fn, args = programs[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes > 0
