"""Every Pallas kernel variant the registry can dispatch on a TPU must
get through Mosaic for the chip the system serves from.

The parity tests (test_pallas_queue.py) run the kernels in interpret
mode, which accepts programs Mosaic refuses — the min-frag kernels once
carried a select over i1 vectors that the interpreter ran and the
compiler could not legalize.  This test AOT-compiles each variant
against a v5e topology description from the CPU sandbox: no chip is
needed, libtpu is.  A compile here is a necessary condition, not a run;
chip_smoke.py is the run.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from k8s_spark_scheduler_tpu.ops import pallas_queue as pq

SHAPES = [(1024, 64), (10240, 1024)]


@pytest.fixture(scope="module")
def v5e_sharding():
    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _queue_args(n, a):
    """(avail, driver_rank, exec_ok, drivers, executors, counts, app_valid)
    with the dtypes TpuFifoSolver.solve_tensor passes."""
    return (
        _sds((n, 3), jnp.int32), _sds((n,), jnp.int32), _sds((n,), jnp.bool_),
        _sds((a, 3), jnp.int32), _sds((a, 3), jnp.int32), _sds((a,), jnp.int32),
        _sds((a,), jnp.bool_),
    )


def _single_az_args(n, a):
    q = _queue_args(n, a)
    return (
        *q[:3], _sds((n,), jnp.int32), *q[3:],
        _sds((n,), jnp.int32), _sds((n,), jnp.int32), _sds((n,), jnp.float32),
        _sds((n,), jnp.int32), _sds((1,), jnp.int32), _sds((1,), jnp.int32),
    )


def _single_az_packed_args(n, a):
    """(avail, node_cols, app_cols, scalars) as the served single-AZ
    path uploads them (TpuSingleAzFifoSolver._launcher)."""
    return (
        _sds((n, 3), jnp.int32), _sds((n, 7), jnp.int32), _sds((a, 9), jnp.int32),
        _sds((3,), jnp.int32),
    )


def _served_slots(n):
    from k8s_spark_scheduler_tpu.ops.batch_solver import snapshot_slots

    return snapshot_slots(n)


def _compacted_slots(n):
    """The slots of the min-frag valve, which reads them compacted."""
    from k8s_spark_scheduler_tpu.ops.batch_solver import snapshot_slots

    return snapshot_slots(n, compacted=True)


# (registry policy, jitted entry point, arg builder, static kwargs)
VARIANTS = [
    ("tpu-batch", pq.pallas_solve_queue, _queue_args, dict(evenly=False)),
    ("tpu-batch-distribute-evenly", pq.pallas_solve_queue, _queue_args, dict(evenly=True)),
    ("tpu-batch-minimal-fragmentation", pq.pallas_solve_queue_min_frag, _queue_args, {}),
    ("tpu-batch-single-az", pq.pallas_solve_queue_single_az, _single_az_args,
     dict(n_zones=3, az_aware=False)),
    ("tpu-batch-az-aware", pq.pallas_solve_queue_single_az, _single_az_args,
     dict(n_zones=3, az_aware=True)),
    ("tpu-batch-single-az-minimal-fragmentation", pq.pallas_solve_queue_single_az,
     _single_az_args, dict(n_zones=3, minfrag=True, strict=True)),
    ("tpu-batch-single-az-minimal-fragmentation (strict off)",
     pq.pallas_solve_queue_single_az, _single_az_args,
     dict(n_zones=3, minfrag=True, strict=False)),
    # the served launches: packed inputs, the snapshot slots of the shape
    ("tpu-batch-single-az (served)", pq.pallas_solve_queue_single_az_packed,
     _single_az_packed_args, dict(n_zones=3, az_aware=False, n_slots=_served_slots)),
    ("tpu-batch-az-aware (served)", pq.pallas_solve_queue_single_az_packed,
     _single_az_packed_args, dict(n_zones=3, az_aware=True, n_slots=_served_slots)),
    ("tpu-batch-single-az-minimal-fragmentation (served)",
     pq.pallas_solve_queue_single_az_packed, _single_az_packed_args,
     dict(n_zones=3, minfrag=True, strict=True, n_slots=_compacted_slots, compact=True)),
]


@pytest.mark.parametrize("n,a", SHAPES, ids=lambda v: str(v))
@pytest.mark.parametrize("policy,fn,args_of,static", VARIANTS, ids=[v[0] for v in VARIANTS])
def test_kernel_compiles_for_v5e(v5e_sharding, policy, fn, args_of, static, n, a):
    # the module-level entry points are already jitted for the default
    # backend; re-jit the underlying function for the topology's device
    static = {k: v(n) if callable(v) else v for k, v in static.items()}
    target = jax.jit(
        functools.partial(fn.__wrapped__, **static),
        in_shardings=v5e_sharding,
        out_shardings=v5e_sharding,
    )
    compiled = target.lower(*args_of(n, a)).compile()
    assert compiled is not None


# the served program of the plain policies: queue pass and the current
# driver's solve in one (batch_solver.solve_filter), with the queue
# kernel's name the device trace has to show for it
# (benchmarks/readers/device_op_ms.py finds the kernel by that name)
SERVED = [
    ("tightly-pack", "pallas_solve_queue"),
    ("distribute-evenly", "pallas_solve_queue"),
    ("minimal-fragmentation", "pallas_solve_queue_min_frag"),
]


@pytest.mark.parametrize("n,a", SHAPES, ids=lambda v: str(v))
@pytest.mark.parametrize("policy,kernel", SERVED, ids=[v[0] for v in SERVED])
def test_served_filter_program_compiles_for_v5e_and_names_its_kernel(v5e_sharding, policy, kernel, n, a):
    from k8s_spark_scheduler_tpu.ops.batch_solver import solve_filter

    target = jax.jit(
        functools.partial(solve_filter.__wrapped__, policy=policy, pallas=True),
        in_shardings=v5e_sharding,
        out_shardings=v5e_sharding,
    )
    compiled = target.lower(_sds((n, 5), jnp.int32), _sds((a, 8), jnp.int32)).compile()
    calls = [line.split(" = ")[0].strip().lstrip("%") for line in compiled.as_text().splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 1 and calls[0].split(".")[0] == kernel, calls


# the shapes fifo10k-groups' five instance groups are served at (benchmarks/configs/
# fifo10k-groups.json, ``shapes_served``): the Filter's program and the marker's
GROUP_SHAPES = [(5120, 1024), (4096, 256), (1024, 256)]


@pytest.mark.parametrize("n,a", GROUP_SHAPES, ids=lambda v: str(v))
def test_programs_compile_for_v5e_at_the_shapes_instance_groups_are_served_at(v5e_sharding, n, a):
    from k8s_spark_scheduler_tpu.ops.batch_solver import VERDICT_ROWS, feasible_apps, solve_filter

    served = jax.jit(
        functools.partial(solve_filter.__wrapped__, policy="tightly-pack", pallas=True),
        in_shardings=v5e_sharding,
        out_shardings=v5e_sharding,
    )
    compiled = served.lower(_sds((n, 5), jnp.int32), _sds((a, 8), jnp.int32)).compile()
    assert "pallas_solve_queue" in compiled.as_text()
    marker = jax.jit(feasible_apps.__wrapped__, in_shardings=v5e_sharding, out_shardings=v5e_sharding)
    assert marker.lower(_sds((n, 6), jnp.int32), _sds((VERDICT_ROWS, 8), jnp.int32)).compile() is not None
