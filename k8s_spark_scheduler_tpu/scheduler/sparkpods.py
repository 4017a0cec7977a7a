"""Spark pod lister: FIFO queue view + annotation parsing
(reference ``internal/extender/sparkpods.go``)."""

from __future__ import annotations

import logging
import threading
from bisect import bisect_left, bisect_right
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from ..kube.informer import Informer
from ..types.objects import Pod
from ..types.resources import NodeGroupResources, Resources
from ..utils.quantity import Quantity
from . import labels as L

logger = logging.getLogger(__name__)


@dataclass
class SparkApplicationResources:
    """internal/types SparkApplicationResources."""

    driver_resources: Resources
    executor_resources: Resources
    min_executor_count: int
    max_executor_count: int


class AnnotationError(ValueError):
    pass


def spark_resources(pod: Pod) -> SparkApplicationResources:
    """Parse the app's resource annotations (sparkpods.go:73-137).

    Error cases mirror the reference: bad DA boolean, missing
    executor-count without DA, missing DA min/max with DA, missing
    driver/executor cpu/mem, unparseable quantity.
    """
    annotations = pod.annotations
    da_raw = annotations.get(L.DYNAMIC_ALLOCATION_ENABLED)
    dynamic_allocation_enabled = False
    if da_raw is not None:
        if da_raw.lower() in ("true", "1", "t"):
            dynamic_allocation_enabled = True
        elif da_raw.lower() in ("false", "0", "f"):
            dynamic_allocation_enabled = False
        else:
            raise AnnotationError(
                "annotation DynamicAllocationEnabled could not be parsed as a boolean"
            )

    parsed: Dict[str, Quantity] = {}
    for key in (
        L.DRIVER_CPU,
        L.DRIVER_MEMORY,
        L.DRIVER_NVIDIA_GPUS,
        L.EXECUTOR_CPU,
        L.EXECUTOR_MEMORY,
        L.EXECUTOR_NVIDIA_GPUS,
        L.EXECUTOR_COUNT,
        L.DA_MIN_EXECUTOR_COUNT,
        L.DA_MAX_EXECUTOR_COUNT,
    ):
        value = annotations.get(key)
        if value is None:
            if key in (L.DRIVER_NVIDIA_GPUS, L.EXECUTOR_NVIDIA_GPUS):
                continue  # optional: GPUs not required
            if not dynamic_allocation_enabled and key == L.EXECUTOR_COUNT:
                raise AnnotationError(
                    "annotation ExecutorCount is required when DynamicAllocationEnabled is false"
                )
            if dynamic_allocation_enabled and key in (
                L.DA_MIN_EXECUTOR_COUNT,
                L.DA_MAX_EXECUTOR_COUNT,
            ):
                raise AnnotationError(
                    f"annotation {key} is required when DynamicAllocationEnabled is true"
                )
            if key in (L.EXECUTOR_COUNT, L.DA_MIN_EXECUTOR_COUNT, L.DA_MAX_EXECUTOR_COUNT):
                continue  # not needed in this mode
            raise AnnotationError(f"annotation {key} is missing from driver")
        try:
            parsed[key] = Quantity(value)
        except ValueError:
            raise AnnotationError(
                f"annotation {key} does not have a parseable value {value}"
            ) from None

    if dynamic_allocation_enabled:
        min_executor_count = parsed[L.DA_MIN_EXECUTOR_COUNT].value()
        max_executor_count = parsed[L.DA_MAX_EXECUTOR_COUNT].value()
    else:
        min_executor_count = parsed[L.EXECUTOR_COUNT].value()
        max_executor_count = min_executor_count

    zero = Quantity(0)
    return SparkApplicationResources(
        driver_resources=Resources(
            parsed[L.DRIVER_CPU], parsed[L.DRIVER_MEMORY], parsed.get(L.DRIVER_NVIDIA_GPUS, zero)
        ),
        executor_resources=Resources(
            parsed[L.EXECUTOR_CPU],
            parsed[L.EXECUTOR_MEMORY],
            parsed.get(L.EXECUTOR_NVIDIA_GPUS, zero),
        ),
        min_executor_count=min_executor_count,
        max_executor_count=max_executor_count,
    )


# (uid, resourceVersion) → (SparkApplicationResources, AppDemand) |
# AnnotationError.  Annotations are immutable per resource version, and
# the FIFO pass re-reads the same ~queue-depth pods on EVERY Filter
# request — without this cache, Quantity re-parsing alone cost
# ~200ms/request at the 10k-node × 1k-queue shape.  The AppDemand
# instance is STABLE across requests so the tensorize layer can stash
# its exact base-unit rows on it (tensorize._app_base_rows).
_SPARK_RESOURCES_CACHE: OrderedDict = OrderedDict()
_SPARK_RESOURCES_CACHE_MAX = 16384
_spark_resources_lock = threading.Lock()


def _cache_lookup(pod: Pod):
    key = (pod.meta.uid, pod.meta.resource_version)
    if not key[0]:
        return None, None  # no identity to key on
    with _spark_resources_lock:
        hit = _SPARK_RESOURCES_CACHE.get(key)
        if hit is not None:
            _SPARK_RESOURCES_CACHE.move_to_end(key)
    return key, hit


def _cache_store(key, value) -> None:
    with _spark_resources_lock:
        _SPARK_RESOURCES_CACHE[key] = value
        while len(_SPARK_RESOURCES_CACHE) > _SPARK_RESOURCES_CACHE_MAX:
            _SPARK_RESOURCES_CACHE.popitem(last=False)


def _cached_entry(pod: Pod):
    """(SparkApplicationResources, AppDemand) for the pod's current
    version, parsed at most once; AnnotationErrors are cached too (a bad
    annotation stays bad for that version) and re-raised fresh."""
    from ..ops.sparkapp import AppDemand

    key, hit = _cache_lookup(pod)
    if hit is None:
        try:
            sar = spark_resources(pod)
            demand = AppDemand(
                sar.driver_resources,
                sar.executor_resources,
                sar.min_executor_count,
            )
            # precompute the exact tensor rows BEFORE the instance is
            # shared: request threads then only read the stash, so the
            # tensorize-layer lazy fallback never writes to a shared
            # AppDemand from concurrent requests (ADVICE r4 #3)
            from ..ops.tensorize import _app_base_rows

            _app_base_rows(demand)
            hit = (sar, demand)
        except AnnotationError as err:
            hit = err
        if key is not None:
            _cache_store(key, hit)
    if isinstance(hit, AnnotationError):
        raise AnnotationError(*hit.args)
    return hit


def spark_resources_cached(pod: Pod) -> SparkApplicationResources:
    """``spark_resources`` memoized by (uid, resourceVersion)."""
    return _cached_entry(pod)[0]


def spark_app_demand_cached(pod: Pod):
    """(SparkApplicationResources, stable AppDemand) for the pod's
    current version — the FIFO queue loops use this so per-app tensor
    rows are computed once per pod version, not once per request."""
    return _cached_entry(pod)


def spark_resource_usage(
    driver_resources: Resources,
    executor_resources: Resources,
    driver_node: str,
    executor_nodes: List[str],
) -> NodeGroupResources:
    """sparkpods.go:139-146.

    QUIRK (reference behavior): per-node entries are *assigned*, not
    accumulated — a node hosting N executors contributes one executor's
    worth, and a driver node that also hosts executors is counted as
    executors only.  The FIFO pass subtracts this, so preserving it is
    required for decision parity.
    """
    usage: NodeGroupResources = {}
    usage[driver_node] = driver_resources
    for node in executor_nodes:
        usage[node] = executor_resources
    return usage


# what a read of the pending-driver view did to answer (the
# ``queueView`` tag and the QUEUE_VIEW_READS counter's ``result``)
VIEW_HIT = "hit"  # served from the kept columns
VIEW_REBUILD = "rebuild"  # no view yet: derived from the store first
VIEW_STALE = "stale"  # an event had failed to apply: derived again
VIEW_PER_POD = "per-pod"  # the caller walked the pods itself


def _namespace_and_name(pod: Pod) -> Tuple[str, str]:
    return pod.namespace, pod.name


class _PendingGroup:
    """The pending drivers of one (scheduler name, instance group) as
    parallel columns in (creation timestamp, namespace, name) order."""

    __slots__ = ("stamps", "pods", "demands", "names", "unparsed")

    def __init__(self):
        self.stamps: List[float] = []
        self.pods: List[Pod] = []
        # the stable AppDemand of spark_app_demand_cached (tensor rows
        # stashed on it); None where the annotations do not parse
        self.demands: List = []
        self.names: List[str] = []
        self.unparsed = 0

    def locate(self, stamp: float, namespace: str, name: str) -> int:
        """Where (stamp, namespace, name) stands or would stand."""
        lo = bisect_left(self.stamps, stamp)
        hi = bisect_right(self.stamps, stamp, lo)
        return bisect_left(
            self.pods, (namespace, name), lo, hi, key=_namespace_and_name
        )


class PendingDriverView:
    """The earlier-drivers queue, kept between requests.

    One :class:`_PendingGroup` per (scheduler name, instance group as
    ``find_instance_group_from_pod_spec`` reads it) holds the
    unscheduled, undeleted driver pods.  The informer hands every
    applied pod event to :meth:`apply` under its own lock
    (``Informer.attach_view``), and readers hold that lock, so a read
    sees exactly what a from-scratch derivation over the store would
    return at that moment.  Equal creation timestamps are ordered by
    (namespace, name): one of the orders the reference's unstable
    ``sort.Slice`` can produce, the same in every process.

    The view starts unbuilt and derives itself from the store on its
    first read; an event that fails to apply marks it stale, which
    sends the next read through the same derivation.
    """

    def __init__(self, informer: Informer, instance_group_label: str):
        self._informer = informer
        self._instance_group_label = instance_group_label
        self._groups: Dict[Tuple[str, str], _PendingGroup] = {}
        # (namespace, name) → (group key, creation timestamp) of every
        # pod the columns hold, so a removal needs no old object
        self._members: Dict[Tuple[str, str], Tuple[Tuple[str, str], float]] = {}
        # what the next read has to do first: VIEW_HIT is "nothing"
        self._state = VIEW_REBUILD
        informer.attach_view(self)

    # -- writes: the informer, under its lock ---------------------------------

    def apply(self, key: Tuple[str, str], pod: Optional[Pod]) -> None:
        """One applied pod event (``pod`` None: deleted).  O(1) for an
        executor; a bisect and a C-level move for a driver."""
        if self._state != VIEW_HIT:
            return  # the next read derives everything from the store
        try:
            member = self._members.pop(key, None)
            if member is not None:
                self._remove(key, *member)
            if pod is not None and pod.labels.get(L.SPARK_ROLE_LABEL) == L.DRIVER:
                self._insert(key, pod)
        except Exception:
            logger.exception("pending-driver view failed to apply %s/%s", *key)
            self._state = VIEW_STALE

    def _insert(self, key: Tuple[str, str], pod: Pod) -> None:
        if pod.node_name != "" or pod.meta.deletion_timestamp is not None:
            return
        instance_group, ok = L.find_instance_group_from_pod_spec(
            pod, self._instance_group_label
        )
        if not ok:
            return  # matches no driver's group (podspec.go:22-26)
        group_key = (pod.scheduler_name, instance_group)
        group = self._groups.get(group_key)
        if group is None:
            group = self._groups[group_key] = _PendingGroup()
        try:
            demand = spark_app_demand_cached(pod)[1]
        except AnnotationError:
            demand = None
            group.unparsed += 1
        stamp = pod.creation_timestamp
        at = group.locate(stamp, *key)
        group.stamps.insert(at, stamp)
        group.pods.insert(at, pod)
        group.demands.insert(at, demand)
        group.names.insert(at, pod.name)
        self._members[key] = (group_key, stamp)

    def _remove(self, key: Tuple[str, str], group_key, stamp: float) -> None:
        group = self._groups[group_key]
        at = group.locate(stamp, *key)
        if _namespace_and_name(group.pods[at]) != key:
            raise LookupError(f"{key} is not where the view's index says")
        if group.demands[at] is None:
            group.unparsed -= 1
        del group.stamps[at], group.pods[at], group.demands[at], group.names[at]
        if not group.stamps:
            del self._groups[group_key]

    # -- reads: hold the informer's lock --------------------------------------

    def _fresh_group(self, driver: Pod) -> Tuple[Optional[_PendingGroup], str]:
        """``driver``'s group (None: nothing pending there) and what it
        took.  The caller holds the informer's lock."""
        how = self._state
        if how != VIEW_HIT:
            self._groups = {}
            self._members = {}
            drivers = self._informer.list(label_selector={L.SPARK_ROLE_LABEL: L.DRIVER})
            # in the view's order, so that every insert is an append
            drivers.sort(key=lambda p: (p.creation_timestamp, p.namespace, p.name))
            for pod in drivers:
                self._insert((pod.namespace, pod.name), pod)
            self._state = VIEW_HIT
        instance_group, ok = L.find_instance_group_from_pod_spec(
            driver, self._instance_group_label
        )
        if not ok:
            return None, how
        return self._groups.get((driver.scheduler_name, instance_group)), how

    def pods(self, driver: Pod, earlier_only: bool) -> List[Pod]:
        """The pending drivers ``driver`` competes with, oldest first:
        those created strictly earlier, or all of them."""
        with self._informer.store_lock:
            group, _ = self._fresh_group(driver)
            if group is None:
                return []
            if not earlier_only:
                return group.pods[:]
            return group.pods[: bisect_left(group.stamps, driver.creation_timestamp)]

    def queue_ahead(self, driver: Pod, skip_cutoff: float):
        """``(earlier_apps, skip_allowed, queue_names), how`` for the
        drivers created strictly before ``driver``: their demands, the
        enforce-after-age verdict of each (created after
        ``skip_cutoff``: young enough to skip) and their names, all as
        slices of the kept columns.  The triple is None where one of
        those pods' annotations do not parse: the caller walks the pods
        itself, with the warning that loop logs."""
        with self._informer.store_lock:
            group, how = self._fresh_group(driver)
            if group is None:
                return ([], [], []), how
            count = bisect_left(group.stamps, driver.creation_timestamp)
            apps = group.demands[:count]
            if group.unparsed and None in apps:
                return None, how
            old = bisect_right(group.stamps, skip_cutoff, 0, count)
            skips = [False] * old + [True] * (count - old)
            return (apps, skips, group.names[:count]), how


class SparkPodLister:
    """sparkpods.go:36-71 + driver lookups."""

    def __init__(self, pod_informer: Informer, instance_group_label: str):
        self._informer = pod_informer
        # the FIFO queue, kept between requests and changed by pod
        # events: deriving it per Filter cost ~5 ms at a 1k-deep queue
        self.pending_view = PendingDriverView(pod_informer, instance_group_label)

    @property
    def informer(self) -> Informer:
        return self._informer

    def list(self, namespace: Optional[str] = None, label_selector=None) -> List[Pod]:
        return self._informer.list(namespace=namespace, label_selector=label_selector)

    def list_earlier_drivers(self, driver: Pod) -> List[Pod]:
        """Unscheduled drivers in the same instance group, targeted at the
        same scheduler, created strictly earlier, sorted by creation time
        (sparkpods.go:45-71)."""
        return self.pending_view.pods(driver, earlier_only=True)

    def list_pending_drivers(self, driver: Pod) -> List[Pod]:
        """The full pending-driver set ``driver`` competes with: same
        filters as :meth:`list_earlier_drivers` MINUS the creation-time
        cut (and including ``driver`` itself when pending), still
        creation-time sorted.  The policy engine re-orders this set
        under non-FIFO comparators."""
        return self.pending_view.pods(driver, earlier_only=False)

    def get_driver_pod_for_executor(self, executor: Pod) -> Optional[Pod]:
        return self.get_driver_pod(
            executor.labels.get(L.SPARK_APP_ID_LABEL, ""), executor.namespace
        )

    def get_driver_pod(self, app_id: str, namespace: str) -> Optional[Pod]:
        """sparkpods.go:152-159 (exactly one match or None)."""
        drivers = self._informer.list(
            namespace=namespace,
            label_selector={L.SPARK_APP_ID_LABEL: app_id, L.SPARK_ROLE_LABEL: L.DRIVER},
        )
        if len(drivers) != 1:
            return None
        return drivers[0]
