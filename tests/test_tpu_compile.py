"""The front kernels compile for a TPU v5e at the sizes the main path runs.

Nothing here runs on a chip: each test lowers and compiles a kernel for a
*described* v5e:2x2 topology with the TPU compiler, so Mosaic's refusals
(unsupported primitives, scoped-VMEM overflow, a kernel that cannot be
partitioned) show up here instead of on the chip.  Interpret mode, which
every other kernel test uses, sees none of them.

The topology is described inside a module fixture (never at import: one
process at a time may load the TPU library), which skips the file only
when the topology cannot be described at all.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

import repro.kernels.ops as ops
from repro.kernels.frontal_cholesky import (
    front_factor_vmem,
    panel_factor,
    syrk_downdate,
)


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host, with the persistent compile cache off
    (entries compiled for a described chip cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding) for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _custom_calls(compiled) -> list:
    return [
        ln for ln in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in ln
    ]


@pytest.mark.parametrize("mp", [128, 512, 1024])
def test_front_factor_vmem_compiles(one_chip, mp):
    c = _compile(lambda f: front_factor_vmem(f, mp), (mp, mp), sharding=one_chip)
    assert len(_custom_calls(c)) == 1


def test_batched_front_factor_compiles(one_chip):
    c = _compile(
        lambda f: ops._batched_front_factor(f, 256, False), (8, 512, 512),
        sharding=one_chip,
    )
    assert len(_custom_calls(c)) == 1


def test_panel_factor_compiles(one_chip):
    c = _compile(panel_factor, (2048, 512), sharding=one_chip)
    assert len(_custom_calls(c)) == 1


def test_syrk_downdate_compiles(one_chip):
    c = _compile(syrk_downdate, (2048, 2048), (2048, 512), sharding=one_chip)
    assert len(_custom_calls(c)) == 1


def test_large_front_pipeline_compiles(one_chip):
    """m = 1280 > VMEM_FRONT_MAX: padding, panel kernel, SYRK kernel and
    the output gather in one jitted program."""
    c = _compile(
        lambda f: ops._partial_cholesky_impl(f, 256, False), (1280, 1280),
        sharding=one_chip,
    )
    assert len(_custom_calls(c)) == 2  # one panel, one trailing SYRK


@pytest.mark.parametrize(
    "fn,shape,names",
    [
        (lambda f: ops._batched_front_factor(f, 256, False), (8, 512, 512),
         ["front_factor_vmem"]),
        (lambda f: ops._partial_cholesky_impl(f, 256, False), (1280, 1280),
         ["panel_factor", "syrk_downdate"]),
    ],
    ids=["batched_front_factor", "partial_cholesky"],
)
def test_kernel_names_reach_the_compiled_custom_calls(one_chip, fn, shape, names):
    """The HLO name of each Pallas custom call (what a device trace's
    ``XLA Ops`` line shows) carries the kernel's ``name=``:
    ``%vmap_front_factor_vmem_.1``, ``%panel_factor.2``, ..."""
    c = _compile(fn, shape, sharding=one_chip)
    ops_ = [ln.split(" = ", 1)[0].split()[-1] for ln in _custom_calls(c)]
    assert len(ops_) == len(names)
    for op, name in zip(ops_, names):
        assert name in op, (op, name)


def test_sharded_batch_runs_one_kernel_per_device_without_gather(topo):
    """The executor's sharded dispatch on a 4-chip mesh: each device runs
    the kernel on its own quarter of the batch, and nothing is gathered
    (GSPMD alone refuses to partition a Mosaic call)."""
    mesh = Mesh(np.array(topo.devices), ("front",))
    sharding = NamedSharding(mesh, PartitionSpec("front"))
    x = jax.ShapeDtypeStruct((8, 512, 512), jnp.float32, sharding=sharding)
    c = ops._batched_front_factor.lower(
        x, nbp=256, interpret=False, mesh=mesh
    ).compile()
    calls = _custom_calls(c)
    assert len(calls) == 1  # the per-device SPMD program
    assert "f32[2,512,512]" in calls[0]  # its local lanes: 8 / 4
    text = c.as_text()
    for collective in ("all-gather", "all-to-all", "all-reduce", "collective-permute"):
        assert collective not in text
    assert c.output_shardings.is_equivalent_to(sharding, 3)
    with pytest.raises(NotImplementedError, match="shard_map"):
        ops._batched_front_factor.lower(x, nbp=256, interpret=False).compile()

