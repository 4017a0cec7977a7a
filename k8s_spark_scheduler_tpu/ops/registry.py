"""Binpacker registry (reference ``internal/binpacker/binpack.go``).

Name → algorithm map with the reference's names plus the TPU-native
``tpu-batch`` solver.  Unknown names fall back to the default
``distribute-evenly`` (binpack.go:52-58).
"""

from __future__ import annotations

from dataclasses import dataclass

from .. import compat
from . import packers
from .packers import SparkBinPackFunction

TIGHTLY_PACK = "tightly-pack"
DISTRIBUTE_EVENLY = "distribute-evenly"
AZ_AWARE_TIGHTLY_PACK = "az-aware-tightly-pack"
SINGLE_AZ_TIGHTLY_PACK = "single-az-tightly-pack"
SINGLE_AZ_MINIMAL_FRAGMENTATION = "single-az-minimal-fragmentation"
MINIMAL_FRAGMENTATION = "minimal-fragmentation"
TPU_BATCH = "tpu-batch"
TPU_BATCH_SINGLE_AZ = "tpu-batch-single-az"
TPU_BATCH_AZ_AWARE = "tpu-batch-az-aware"
TPU_BATCH_MIN_FRAG = "tpu-batch-minimal-fragmentation"
TPU_BATCH_EVENLY = "tpu-batch-distribute-evenly"
TPU_BATCH_SINGLE_AZ_MIN_FRAG = "tpu-batch-single-az-minimal-fragmentation"

DEFAULT = DISTRIBUTE_EVENLY


@dataclass
class Binpacker:
    name: str
    binpack_func: SparkBinPackFunction
    is_single_az: bool
    # device-side whole-queue FIFO solver (set for tpu-batch); None means
    # the extender uses the host earlier-drivers loop
    queue_solver: object = None


_REGISTRY = {}


def register(name: str, fn: SparkBinPackFunction, is_single_az: bool) -> None:
    _REGISTRY[name] = Binpacker(name, fn, is_single_az)


register(TIGHTLY_PACK, packers.tightly_pack, False)
register(DISTRIBUTE_EVENLY, packers.distribute_evenly, False)
register(AZ_AWARE_TIGHTLY_PACK, packers.az_aware_tightly_pack, True)
register(SINGLE_AZ_TIGHTLY_PACK, packers.single_az_tightly_pack, True)
register(SINGLE_AZ_MINIMAL_FRAGMENTATION, packers.single_az_minimal_fragmentation, True)
register(MINIMAL_FRAGMENTATION, packers.minimal_fragmentation_pack, False)


def _minfrag_binpacker(name: str, strict: bool) -> Binpacker:
    """The two host min-frag policies, built for either compat mode —
    the only policies with a switchable quirk (efficiency write-back)."""
    if name == SINGLE_AZ_MINIMAL_FRAGMENTATION:
        return Binpacker(
            name, packers.make_single_az_minimal_fragmentation(strict), True
        )
    return Binpacker(name, packers.make_minimal_fragmentation_pack(strict), False)


def select_binpacker(
    name: str, strict_reference_parity: bool = compat.DEFAULT_STRICT
) -> Binpacker:
    """binpack.go:52-58; unknown → distribute-evenly.

    strict_reference_parity threads the compat policy (compat.py) into
    the minimal-fragmentation variants."""
    if not strict_reference_parity and name in (
        MINIMAL_FRAGMENTATION,
        SINGLE_AZ_MINIMAL_FRAGMENTATION,
    ):
        return _minfrag_binpacker(name, strict_reference_parity)
    if name in (
        TPU_BATCH,
        TPU_BATCH_SINGLE_AZ,
        TPU_BATCH_AZ_AWARE,
        TPU_BATCH_MIN_FRAG,
        TPU_BATCH_EVENLY,
        TPU_BATCH_SINGLE_AZ_MIN_FRAG,
    ):
        # imported lazily: pulls in jax.  A tpu-batch name with no
        # importable solver is a broken install and raises — answering
        # from the host policy under the device policy's name would hide
        # that the device never served.
        from .batch_adapter import (
            tpu_batch_az_aware_binpacker,
            tpu_batch_binpacker,
            tpu_batch_evenly_binpacker,
            tpu_batch_min_frag_binpacker,
            tpu_batch_single_az_binpacker,
            tpu_batch_single_az_min_frag_binpacker,
        )

        if name == TPU_BATCH_MIN_FRAG:
            return tpu_batch_min_frag_binpacker(strict_reference_parity)
        if name == TPU_BATCH_SINGLE_AZ:
            return tpu_batch_single_az_binpacker()
        if name == TPU_BATCH_AZ_AWARE:
            return tpu_batch_az_aware_binpacker()
        if name == TPU_BATCH_EVENLY:
            return tpu_batch_evenly_binpacker()
        if name == TPU_BATCH_SINGLE_AZ_MIN_FRAG:
            return tpu_batch_single_az_min_frag_binpacker(strict_reference_parity)
        return tpu_batch_binpacker()
    return _REGISTRY.get(name, _REGISTRY[DEFAULT])


# -- kernel chaos hook --------------------------------------------------------
#
# The simulator's kernel_fault injection point: when armed, every device
# lane entry (tensor driver path, device FIFO solve, tensor reschedule)
# raises through the extender's REAL exception-fallback path, so lane
# demotion/re-probe (resilience/lanehealth.py) is exercised against the
# same control flow production faults take.  None (the default) costs one
# module-attribute read per dispatch.

_kernel_fault_hook = None


def set_kernel_fault_hook(fn) -> None:
    """fn(lane_name) -> Optional[Exception]; None disarms."""
    global _kernel_fault_hook
    _kernel_fault_hook = fn


def check_kernel_fault(lane: str) -> None:
    fn = _kernel_fault_hook
    if fn is not None:
        err = fn(lane)
        if err is not None:
            raise err


def available_binpackers() -> list[str]:
    return sorted(
        _REGISTRY.keys()
        | {
            TPU_BATCH,
            TPU_BATCH_SINGLE_AZ,
            TPU_BATCH_AZ_AWARE,
            TPU_BATCH_MIN_FRAG,
            TPU_BATCH_EVENLY,
            TPU_BATCH_SINGLE_AZ_MIN_FRAG,
        }
    )
