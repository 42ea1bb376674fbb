"""ctypes bridge to the native core (``cpp/htpu``, built as
``horovod_tpu/lib/libhtpu_core.so``).

Mirrors the reference's ctypes ``HorovodBasics`` pattern
(``horovod/common/__init__.py:51-84``): a narrow ``extern "C"`` API, bytes
in the htpu wire format (:mod:`horovod_tpu.wire`) as the interchange.

Exposes drop-in replacements for the control-plane classes in
:mod:`horovod_tpu.core`: :class:`CppMessageTable`, :func:`cpp_plan_fusion`,
:class:`CppTimeline`.  ``load()`` builds the library with ``make`` on first
use if it is missing (the toolchain is a build requirement, like the
reference's ``mpicxx``); set ``HOROVOD_TPU_NO_CPP=1`` to force the
pure-Python fallback.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import time
import warnings
from typing import List

from horovod_tpu import wire
from horovod_tpu.core import Request, Response, env_flag

_LIB_PATH = os.path.join(os.path.dirname(__file__), "lib", "libhtpu_core.so")
_CPP_DIR = os.path.join(os.path.dirname(__file__), os.pardir, "cpp")

_lib = None
_lib_lock = threading.Lock()


def _configure(lib) -> None:
    lib.htpu_version.restype = ctypes.c_char_p
    lib.htpu_free.restype = None
    lib.htpu_free.argtypes = [ctypes.c_void_p]
    lib.htpu_table_create.restype = ctypes.c_void_p
    lib.htpu_table_create.argtypes = [ctypes.c_int]
    lib.htpu_table_destroy.restype = None
    lib.htpu_table_destroy.argtypes = [ctypes.c_void_p]
    lib.htpu_table_increment.restype = ctypes.c_int
    lib.htpu_table_increment.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    lib.htpu_table_construct_response.restype = ctypes.c_int
    lib.htpu_table_construct_response.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p)]
    lib.htpu_table_num_pending.restype = ctypes.c_int
    lib.htpu_table_num_pending.argtypes = [ctypes.c_void_p]
    lib.htpu_table_clear.restype = None
    lib.htpu_table_clear.argtypes = [ctypes.c_void_p]
    lib.htpu_table_stalled.restype = ctypes.c_int
    lib.htpu_table_stalled.argtypes = [
        ctypes.c_void_p, ctypes.c_double, ctypes.POINTER(ctypes.c_void_p)]
    lib.htpu_table_configure_algo.restype = None
    lib.htpu_table_configure_algo.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_longlong]
    lib.htpu_plan_fusion.restype = ctypes.c_int
    lib.htpu_plan_fusion.argtypes = [
        ctypes.c_char_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p)]
    lib.htpu_timeline_create.restype = ctypes.c_void_p
    lib.htpu_timeline_create.argtypes = [ctypes.c_char_p]
    lib.htpu_timeline_destroy.restype = None
    lib.htpu_timeline_destroy.argtypes = [ctypes.c_void_p]
    # Newer symbols are guarded so a prebuilt library from an older round
    # still loads (the hasattr idiom used for htpu_wire_encode below).
    if hasattr(lib, "htpu_timeline_create_rank"):
        lib.htpu_timeline_create_rank.restype = ctypes.c_void_p
        lib.htpu_timeline_create_rank.argtypes = [
            ctypes.c_char_p, ctypes.c_int]
    if hasattr(lib, "htpu_timeline_instant"):
        lib.htpu_timeline_instant.restype = None
        lib.htpu_timeline_instant.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p]
    if hasattr(lib, "htpu_timeline_tick_span"):
        lib.htpu_timeline_tick_span.restype = None
        lib.htpu_timeline_tick_span.argtypes = [
            ctypes.c_void_p, ctypes.c_ulonglong, ctypes.c_longlong]
    for fn in ("negotiate_start", "start"):
        f = getattr(lib, f"htpu_timeline_{fn}")
        f.restype = None
        f.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    lib.htpu_timeline_negotiate_rank_ready.restype = None
    lib.htpu_timeline_negotiate_rank_ready.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    for fn in ("negotiate_end", "end", "activity_end"):
        f = getattr(lib, f"htpu_timeline_{fn}")
        f.restype = None
        f.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.htpu_timeline_activity_start.restype = None
    lib.htpu_timeline_activity_start.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p]
    if hasattr(lib, "htpu_timeline_activity_span"):   # PR 34
        lib.htpu_timeline_activity_span.restype = None
        lib.htpu_timeline_activity_span.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_longlong, ctypes.c_longlong]
    lib.htpu_timeline_counter.restype = None
    lib.htpu_timeline_counter.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong]
    lib.htpu_timeline_cache_hit_tick.restype = None
    lib.htpu_timeline_cache_hit_tick.argtypes = [
        ctypes.c_void_p, ctypes.c_longlong]
    lib.htpu_timeline_flush.restype = None
    lib.htpu_timeline_flush.argtypes = [ctypes.c_void_p]
    lib.htpu_timeline_close.restype = None
    lib.htpu_timeline_close.argtypes = [ctypes.c_void_p]
    lib.htpu_control_create.restype = ctypes.c_void_p
    lib.htpu_control_create.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.htpu_control_destroy.restype = None
    lib.htpu_control_destroy.argtypes = [ctypes.c_void_p]
    lib.htpu_control_tick.restype = ctypes.c_int
    lib.htpu_control_tick.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_void_p)]
    lib.htpu_control_allreduce.restype = ctypes.c_int
    lib.htpu_control_allreduce.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,
        ctypes.c_longlong, ctypes.POINTER(ctypes.c_void_p)]
    lib.htpu_control_allreduce_wire.restype = ctypes.c_int
    lib.htpu_control_allreduce_wire.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_void_p,
        ctypes.c_longlong, ctypes.POINTER(ctypes.c_void_p)]
    lib.htpu_control_allreduce_algo.restype = ctypes.c_int
    lib.htpu_control_allreduce_algo.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_void_p, ctypes.c_longlong, ctypes.POINTER(ctypes.c_void_p)]
    lib.htpu_wire_roundtrip.restype = ctypes.c_longlong
    lib.htpu_wire_roundtrip.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_longlong,
        ctypes.c_void_p]
    for fn in ("htpu_wire_encode", "htpu_wire_decode"):
        f = getattr(lib, fn, None)
        if f is not None:
            f.restype = ctypes.c_longlong
            f.argtypes = [ctypes.c_char_p, ctypes.c_void_p,
                          ctypes.c_longlong, ctypes.c_void_p]
    if hasattr(lib, "htpu_wire_bytes"):
        lib.htpu_wire_bytes.restype = ctypes.c_longlong
        lib.htpu_wire_bytes.argtypes = [ctypes.c_char_p, ctypes.c_longlong]
    lib.htpu_sum_into.restype = ctypes.c_int
    lib.htpu_sum_into.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_longlong]
    lib.htpu_control_allgather.restype = ctypes.c_int
    lib.htpu_control_allgather.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_void_p)]
    lib.htpu_control_broadcast.restype = ctypes.c_int
    lib.htpu_control_broadcast.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_void_p)]
    lib.htpu_control_stalled.restype = ctypes.c_int
    lib.htpu_control_stalled.argtypes = [
        ctypes.c_void_p, ctypes.c_double, ctypes.POINTER(ctypes.c_void_p)]
    lib.htpu_control_last_error.restype = ctypes.c_int
    lib.htpu_control_last_error.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_void_p)]
    lib.htpu_control_data_bytes.restype = None
    lib.htpu_control_data_bytes.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_longlong)]
    if hasattr(lib, "htpu_control_membership"):
        lib.htpu_control_membership.restype = None
        lib.htpu_control_membership.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int)]
        lib.htpu_control_elastic.restype = ctypes.c_int
        lib.htpu_control_elastic.argtypes = [ctypes.c_void_p]
    lib.htpu_control_ring_transport.restype = ctypes.c_char_p
    lib.htpu_control_ring_transport.argtypes = [ctypes.c_void_p]
    lib.htpu_control_data_transport.restype = ctypes.c_char_p
    lib.htpu_control_data_transport.argtypes = [ctypes.c_void_p]
    lib.htpu_control_set_timeline.restype = None
    lib.htpu_control_set_timeline.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p]
    lib.htpu_metrics_snapshot.restype = ctypes.c_int
    lib.htpu_metrics_snapshot.argtypes = [ctypes.POINTER(ctypes.c_void_p)]
    lib.htpu_metrics_reset.restype = None
    lib.htpu_metrics_reset.argtypes = []
    if hasattr(lib, "htpu_flight_record"):
        lib.htpu_flight_record.restype = None
        lib.htpu_flight_record.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_longlong,
            ctypes.c_int, ctypes.c_int]
        lib.htpu_flight_set_capacity.restype = None
        lib.htpu_flight_set_capacity.argtypes = [ctypes.c_longlong]
        lib.htpu_flight_set_rank.restype = None
        lib.htpu_flight_set_rank.argtypes = [ctypes.c_int]
        lib.htpu_flight_dump.restype = ctypes.c_int
        lib.htpu_flight_dump.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p)]
        lib.htpu_flight_snapshot.restype = ctypes.c_int
        lib.htpu_flight_snapshot.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p)]
    # Fleet observatory (guarded: a prebuilt .so from before the
    # observatory still loads for the rest of the surface).
    if hasattr(lib, "htpu_observe_enabled"):
        lib.htpu_observe_enabled.restype = ctypes.c_int
        lib.htpu_observe_enabled.argtypes = []
        lib.htpu_observe_set_enabled.restype = None
        lib.htpu_observe_set_enabled.argtypes = [ctypes.c_int]
        lib.htpu_observe_note_step.restype = None
        lib.htpu_observe_note_step.argtypes = [
            ctypes.c_double, ctypes.c_double, ctypes.c_double,
            ctypes.c_double, ctypes.c_double]
        lib.htpu_observe_record_xfer.restype = None
        lib.htpu_observe_record_xfer.argtypes = [
            ctypes.c_int, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_double]
        lib.htpu_observe_snapshot.restype = ctypes.c_int
        lib.htpu_observe_snapshot.argtypes = [
            ctypes.POINTER(ctypes.c_void_p)]
        lib.htpu_observe_reset.restype = None
        lib.htpu_observe_reset.argtypes = []
        lib.htpu_observe_trailer_encode.restype = ctypes.c_int
        lib.htpu_observe_trailer_encode.argtypes = [
            ctypes.POINTER(ctypes.c_void_p)]
        lib.htpu_observe_trailer_probe.restype = ctypes.c_int
        lib.htpu_observe_trailer_probe.argtypes = [
            ctypes.c_char_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_void_p)]
    # Aggregation tier (guarded: a prebuilt .so predating the
    # hierarchical control topology still loads for the rest of the
    # surface).
    if hasattr(lib, "htpu_agg_merge"):
        lib.htpu_agg_merge.restype = ctypes.c_int
        lib.htpu_agg_merge.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_void_p)]
        lib.htpu_agg_roundtrip.restype = ctypes.c_int
        lib.htpu_agg_roundtrip.argtypes = [
            ctypes.c_char_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_void_p)]
    # Scheduler API (guarded: a prebuilt .so predating the plane-agnostic
    # scheduler still loads for the rest of the surface).
    if hasattr(lib, "htpu_sched_create"):
        lib.htpu_plan_tick.restype = ctypes.c_int
        lib.htpu_plan_tick.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_char_p),
            ctypes.c_int, ctypes.c_int64, ctypes.POINTER(ctypes.c_void_p)]
        lib.htpu_resolve_algo.restype = ctypes.c_int
        lib.htpu_resolve_algo.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_void_p)]
        lib.htpu_sched_create.restype = ctypes.c_void_p
        lib.htpu_sched_create.argtypes = [ctypes.c_int64]
        lib.htpu_sched_destroy.restype = None
        lib.htpu_sched_destroy.argtypes = [ctypes.c_void_p]
        lib.htpu_sched_register.restype = ctypes.c_int
        lib.htpu_sched_register.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p]
        lib.htpu_sched_seal.restype = ctypes.c_int
        lib.htpu_sched_seal.argtypes = [ctypes.c_void_p]
        lib.htpu_sched_bucket_of.restype = ctypes.c_int
        lib.htpu_sched_bucket_of.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.htpu_sched_bucket_bytes.restype = ctypes.c_int64
        lib.htpu_sched_bucket_bytes.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.htpu_sched_note_ready.restype = ctypes.c_int
        lib.htpu_sched_note_ready.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.htpu_sched_next_issue.restype = ctypes.c_int
        lib.htpu_sched_next_issue.argtypes = [ctypes.c_void_p]
        lib.htpu_sched_note_complete.restype = None
        lib.htpu_sched_note_complete.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.htpu_sched_all_complete.restype = ctypes.c_int
        lib.htpu_sched_all_complete.argtypes = [ctypes.c_void_p]
        lib.htpu_sched_reset.restype = None
        lib.htpu_sched_reset.argtypes = [ctypes.c_void_p]
    # Fleet-policy API (guarded like the scheduler: a prebuilt .so from
    # before the policy engine still loads for the rest of the surface).
    if hasattr(lib, "htpu_policy_create"):
        lib.htpu_policy_create.restype = ctypes.c_void_p
        lib.htpu_policy_create.argtypes = []
        lib.htpu_policy_destroy.restype = None
        lib.htpu_policy_destroy.argtypes = [ctypes.c_void_p]
        lib.htpu_policy_active.restype = ctypes.c_int
        lib.htpu_policy_active.argtypes = [ctypes.c_void_p]
        lib.htpu_policy_observe.restype = None
        lib.htpu_policy_observe.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_double),
            ctypes.c_int]
        lib.htpu_policy_next_eviction.restype = ctypes.c_int
        lib.htpu_policy_next_eviction.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
        lib.htpu_policy_rerank.restype = None
        lib.htpu_policy_rerank.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int), ctypes.c_int]
        lib.htpu_policy_autoscale_target.restype = ctypes.c_int
        lib.htpu_policy_autoscale_target.argtypes = [
            ctypes.c_void_p, ctypes.c_int64]
        lib.htpu_policy_ewma.restype = ctypes.c_double
        lib.htpu_policy_ewma.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.htpu_policy_consecutive_slow.restype = ctypes.c_int
        lib.htpu_policy_consecutive_slow.argtypes = [
            ctypes.c_void_p, ctypes.c_int]
    # Per-set straggler state (PR 15); hasattr-guarded so a prebuilt .so
    # that predates process sets still loads.
    if hasattr(lib, "htpu_policy_observe_set"):
        lib.htpu_policy_observe_set.restype = None
        lib.htpu_policy_observe_set.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_double),
            ctypes.c_int]
        lib.htpu_policy_ewma_set.restype = ctypes.c_double
        lib.htpu_policy_ewma_set.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
        lib.htpu_policy_consecutive_slow_set.restype = ctypes.c_int
        lib.htpu_policy_consecutive_slow_set.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
        lib.htpu_policy_next_eviction_set.restype = ctypes.c_int
        lib.htpu_policy_next_eviction_set.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int]
    # Precision controller (PR 19), same guard: a prebuilt .so from
    # before the autopilot still loads for the rest of the surface.
    if hasattr(lib, "htpu_policy_precision_auto"):
        lib.htpu_policy_precision_auto.restype = ctypes.c_int
        lib.htpu_policy_precision_auto.argtypes = [ctypes.c_void_p]
        lib.htpu_policy_precision_observe.restype = None
        lib.htpu_policy_precision_observe.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_double]
        lib.htpu_policy_precision_bandwidth.restype = None
        lib.htpu_policy_precision_bandwidth.argtypes = [
            ctypes.c_void_p, ctypes.c_double]
        lib.htpu_policy_precision_level.restype = ctypes.c_int
        lib.htpu_policy_precision_level.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p]
        lib.htpu_policy_precision_ewma.restype = ctypes.c_double
        lib.htpu_policy_precision_ewma.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p]
        lib.htpu_policy_precision_counts.restype = None
        lib.htpu_policy_precision_counts.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong)]
        lib.htpu_policy_precision_dirty.restype = ctypes.c_int
        lib.htpu_policy_precision_dirty.argtypes = [ctypes.c_void_p]
    if hasattr(lib, "htpu_wire_request_list_roundtrip"):
        lib.htpu_wire_request_list_roundtrip.restype = ctypes.c_longlong
        lib.htpu_wire_request_list_roundtrip.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_void_p,
            ctypes.c_longlong]
    # Multi-tenant process-set registry (PR 15), same guard.
    if hasattr(lib, "htpu_process_sets_create"):
        lib.htpu_process_sets_create.restype = ctypes.c_void_p
        lib.htpu_process_sets_create.argtypes = [ctypes.c_longlong]
        lib.htpu_process_sets_destroy.restype = None
        lib.htpu_process_sets_destroy.argtypes = [ctypes.c_void_p]
        lib.htpu_process_sets_parse_spec.restype = ctypes.c_int
        lib.htpu_process_sets_parse_spec.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p]
        lib.htpu_process_sets_add.restype = ctypes.c_int
        lib.htpu_process_sets_add.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int),
            ctypes.c_int]
        lib.htpu_process_sets_remove.restype = ctypes.c_int
        lib.htpu_process_sets_remove.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.htpu_process_sets_id_of.restype = ctypes.c_int
        lib.htpu_process_sets_id_of.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p]
        lib.htpu_process_sets_count.restype = ctypes.c_int
        lib.htpu_process_sets_count.argtypes = [ctypes.c_void_p]
        lib.htpu_process_sets_size.restype = ctypes.c_int
        lib.htpu_process_sets_size.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.htpu_process_sets_local_rank.restype = ctypes.c_int
        lib.htpu_process_sets_local_rank.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
        lib.htpu_process_sets_generation.restype = ctypes.c_int
        lib.htpu_process_sets_generation.argtypes = [
            ctypes.c_void_p, ctypes.c_int]
        lib.htpu_process_sets_reconfigure.restype = ctypes.c_int
        lib.htpu_process_sets_reconfigure.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
        lib.htpu_process_sets_increment.restype = ctypes.c_int
        lib.htpu_process_sets_increment.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
        lib.htpu_process_sets_construct.restype = ctypes.c_int
        lib.htpu_process_sets_construct.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_void_p)]
    # Integrity plane (PR 17: CRC32C + checked transfers), same guard —
    # a prebuilt .so from before the integrity layer still loads.
    if hasattr(lib, "htpu_crc32c"):
        lib.htpu_crc32c.restype = ctypes.c_uint
        lib.htpu_crc32c.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        lib.htpu_crc32c_sw.restype = ctypes.c_uint
        lib.htpu_crc32c_sw.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        lib.htpu_crc32c_hw.restype = ctypes.c_int
        lib.htpu_crc32c_hw.argtypes = []
        lib.htpu_control_set_xfer_context.restype = None
        lib.htpu_control_set_xfer_context.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p]


def load():
    """Load (building if necessary) the native core; None if unavailable."""
    global _lib
    if env_flag("HOROVOD_TPU_NO_CPP"):
        return None
    with _lib_lock:
        if _lib is not None:
            return _lib
        # The build (a no-op when up to date, half a minute in a new
        # checkout) and the load, as one span of the ring.
        from horovod_tpu.timeline import ring
        with ring.span("init/native_core"):
            return _build_and_load()


def _build_and_load():
    global _lib
    if os.path.isdir(_CPP_DIR):
        # Run make even when the .so exists: it no-ops when up to date
        # and rebuilds a stale library whose symbols predate this module.
        try:
            subprocess.run(["make", "-C", _CPP_DIR], check=True,
                           capture_output=True, timeout=120)
        except subprocess.CalledProcessError as e:
            # Fall through: a prebuilt .so may still be usable — but say
            # so, or the pure-Python fallback engages silently.
            warnings.warn(
                "horovod_tpu: native core build failed; falling back to "
                "the pure-Python control path if no prebuilt library "
                "exists.\n--- make stderr ---\n"
                + e.stderr.decode(errors="replace")[-2000:],
                RuntimeWarning)
        except (subprocess.SubprocessError, OSError) as e:
            warnings.warn(
                f"horovod_tpu: native core build did not run ({e}); "
                "falling back to the pure-Python control path if no "
                "prebuilt library exists.", RuntimeWarning)
    if not os.path.exists(_LIB_PATH):
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
        _configure(lib)
    except (OSError, AttributeError) as e:
        # AttributeError = stale library missing newer symbols.
        warnings.warn(
            f"horovod_tpu: native core library unusable ({e}); using "
            "the pure-Python control path.", RuntimeWarning)
        return None
    _lib = lib
    return _lib


def available() -> bool:
    return load() is not None


def _take_buffer(lib, out_ptr: ctypes.c_void_p, length: int) -> bytes:
    if length < 0:
        raise RuntimeError("native core returned an error")
    try:
        if length == 0:
            return b""
        return ctypes.string_at(out_ptr, length)
    finally:
        lib.htpu_free(out_ptr)


# ------------------------------------------------------- flight recorder

def _flight_lib():
    """The loaded library iff it exports the flight-recorder API, else
    None — every helper below degrades to a no-op on a pure-Python run or
    a stale prebuilt .so."""
    lib = load()
    if lib is None or not hasattr(lib, "htpu_flight_record"):
        return None
    return lib


def flight_record(kind: str, detail: str = "", nbytes: int = 0,
                  a: int = 0, b: int = 0) -> None:
    """Append one event to the native flight-recorder ring (no-op without
    the native core).  Python-side callers use this to mark host-level
    context — op-timeout pending tensors, shutdown phases — so the abort
    dump interleaves them with the C++ tick/transfer events."""
    lib = _flight_lib()
    if lib is not None:
        lib.htpu_flight_record(kind.encode("utf-8"), detail.encode("utf-8"),
                               int(nbytes), int(a), int(b))


def flight_set_capacity(events: int) -> None:
    lib = _flight_lib()
    if lib is not None:
        lib.htpu_flight_set_capacity(int(events))


def flight_set_rank(rank: int) -> None:
    lib = _flight_lib()
    if lib is not None:
        lib.htpu_flight_set_rank(int(rank))


def flight_dump(why: str = "manual") -> str:
    """Dump the ring to its per-rank JSON file; returns the path, or ""
    when the dump failed or the native core is absent."""
    lib = _flight_lib()
    if lib is None:
        return ""
    out = ctypes.c_void_p()
    n = lib.htpu_flight_dump(why.encode("utf-8"), ctypes.byref(out))
    if n < 0:
        return ""
    return _take_buffer(lib, out, n).decode("utf-8", errors="replace")


def flight_snapshot(why: str = "snapshot") -> str:
    """The ring serialized as JSON (without touching disk); "" when the
    native core is absent."""
    lib = _flight_lib()
    if lib is None:
        return ""
    out = ctypes.c_void_p()
    n = lib.htpu_flight_snapshot(why.encode("utf-8"), ctypes.byref(out))
    if n < 0:
        return ""
    return _take_buffer(lib, out, n).decode("utf-8", errors="replace")


class CppMessageTable:
    """Native MessageTable with the Python-class interface of
    :class:`horovod_tpu.core.MessageTable`."""

    def __init__(self, size: int, timeline=None):
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native core not available")
        self._ptr = self._lib.htpu_table_create(size)
        self._size = size
        self._timeline = timeline
        self._pending_names = set()   # for timeline negotiate_start hooks

    def __del__(self):
        lib, ptr = getattr(self, "_lib", None), getattr(self, "_ptr", None)
        if lib is not None and ptr:
            lib.htpu_table_destroy(ptr)
            self._ptr = None

    def __len__(self):
        return self._lib.htpu_table_num_pending(self._ptr)

    def clear(self):
        self._lib.htpu_table_clear(self._ptr)
        self._pending_names.clear()

    def increment(self, msg: Request) -> bool:
        # Single-message boundary frames always carry the algo field (the
        # C side parses with with_algo=true — no flag byte on this path).
        data = wire.serialize_request(msg, with_algo=True)
        rc = self._lib.htpu_table_increment(self._ptr, data, len(data))
        if rc < 0:
            raise RuntimeError("native core failed to parse request")
        if self._timeline:
            # The native table doesn't call back into Python; replicate the
            # negotiation hooks here, tracking first-appearance locally.
            if msg.tensor_name not in self._pending_names:
                self._pending_names.add(msg.tensor_name)
                self._timeline.negotiate_start(msg.tensor_name,
                                               msg.request_type)
            self._timeline.negotiate_rank_ready(msg.tensor_name,
                                                msg.request_rank)
            if rc == 1:
                self._timeline.negotiate_end(msg.tensor_name)
        return rc == 1

    def construct_response(self, name: str) -> Response:
        self._pending_names.discard(name)
        out = ctypes.c_void_p()
        n = self._lib.htpu_table_construct_response(
            self._ptr, name.encode("utf-8"), ctypes.byref(out))
        return wire.parse_single_response(_take_buffer(self._lib, out, n))

    def pending_names_older_than(self, age_s: float):
        out = ctypes.c_void_p()
        n = self._lib.htpu_table_stalled(self._ptr, age_s, ctypes.byref(out))
        return _parse_stall_records(_take_buffer(self._lib, out, n))

    def configure_algo_selection(self, num_hosts: int, num_procs: int,
                                 crossover_bytes: int) -> None:
        """Topology + crossover inputs for allreduce algorithm resolution
        ("auto" -> ring / hier / small per payload size)."""
        self._lib.htpu_table_configure_algo(
            self._ptr, num_hosts, num_procs, crossover_bytes)


def cpp_plan_fusion(responses: List[Response], entry_bytes, entry_dtype,
                    threshold: int) -> List[Response]:
    """Native fusion planner with the signature of
    :func:`horovod_tpu.core.plan_fusion`."""
    lib = load()
    if lib is None:
        raise RuntimeError("native core not available")
    blob = wire.serialize_response_list(responses)
    names = sorted({n for r in responses for n in r.tensor_names})
    n = len(names)
    name_arr = (ctypes.c_char_p * n)(*[s.encode("utf-8") for s in names])
    bytes_arr = (ctypes.c_int64 * n)(*[entry_bytes(s) for s in names])
    dtype_arr = (ctypes.c_char_p * n)(
        *[entry_dtype(s).encode("utf-8") for s in names])
    out = ctypes.c_void_p()
    rc = lib.htpu_plan_fusion(blob, len(blob), name_arr, bytes_arr, dtype_arr,
                              n, threshold, ctypes.byref(out))
    fused, _, _ = wire.parse_response_list(_take_buffer(lib, out, rc))
    return fused


def _sched_lib():
    """The loaded library iff it exports the plane-agnostic scheduler API,
    else None (pure-Python run or stale prebuilt .so)."""
    lib = load()
    if lib is None or not hasattr(lib, "htpu_sched_create"):
        return None
    return lib


def cpp_plan_tick(responses: List[Response], entry_bytes, entry_dtype,
                  threshold: int) -> List[Response]:
    """Native per-tick policy (fusion + first-ready issue order) with the
    signature of :func:`horovod_tpu.scheduler.plan_tick`."""
    lib = _sched_lib()
    if lib is None:
        return cpp_plan_fusion(responses, entry_bytes, entry_dtype, threshold)
    blob = wire.serialize_response_list(responses)
    names = sorted({n for r in responses for n in r.tensor_names})
    n = len(names)
    name_arr = (ctypes.c_char_p * n)(*[s.encode("utf-8") for s in names])
    bytes_arr = (ctypes.c_int64 * n)(*[entry_bytes(s) for s in names])
    dtype_arr = (ctypes.c_char_p * n)(
        *[entry_dtype(s).encode("utf-8") for s in names])
    out = ctypes.c_void_p()
    rc = lib.htpu_plan_tick(blob, len(blob), name_arr, bytes_arr, dtype_arr,
                            n, threshold, ctypes.byref(out))
    fused, _, _ = wire.parse_response_list(_take_buffer(lib, out, rc))
    return fused


def cpp_resolve_algo(pref: str, nbytes: int, num_hosts: int, num_procs: int,
                     crossover_bytes: int) -> str:
    """Native allreduce-algorithm selection ("" = flat ring)."""
    lib = _sched_lib()
    if lib is None:
        raise RuntimeError("native scheduler not available")
    out = ctypes.c_void_p()
    rc = lib.htpu_resolve_algo(pref.encode("utf-8"), nbytes, num_hosts,
                               num_procs, crossover_bytes, ctypes.byref(out))
    return _take_buffer(lib, out, rc).decode("utf-8")


class NativeBucketPlanner:
    """ctypes wrapper over the C++ backward-overlap bucket planner.  Same
    surface as the pure-Python fallback in horovod_tpu/scheduler.py."""

    def __init__(self, bucket_bytes: int):
        lib = _sched_lib()
        if lib is None:
            raise RuntimeError("native scheduler not available")
        self._lib = lib
        self._ptr = lib.htpu_sched_create(int(bucket_bytes))

    def close(self) -> None:
        if self._ptr:
            self._lib.htpu_sched_destroy(self._ptr)
            self._ptr = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def register_leaf(self, name: str, nbytes: int, dtype: str) -> int:
        return self._lib.htpu_sched_register(
            self._ptr, name.encode("utf-8"), int(nbytes),
            dtype.encode("utf-8"))

    def seal(self) -> int:
        return self._lib.htpu_sched_seal(self._ptr)

    def bucket_of(self, leaf: int) -> int:
        return self._lib.htpu_sched_bucket_of(self._ptr, int(leaf))

    def bucket_bytes(self, bucket: int) -> int:
        return self._lib.htpu_sched_bucket_bytes(self._ptr, int(bucket))

    def note_ready(self, leaf: int) -> int:
        return self._lib.htpu_sched_note_ready(self._ptr, int(leaf))

    def next_issue(self) -> int:
        return self._lib.htpu_sched_next_issue(self._ptr)

    def note_complete(self, bucket: int) -> None:
        self._lib.htpu_sched_note_complete(self._ptr, int(bucket))

    def all_complete(self) -> bool:
        return bool(self._lib.htpu_sched_all_complete(self._ptr))

    def reset(self) -> None:
        self._lib.htpu_sched_reset(self._ptr)


def _policy_lib():
    """The loaded library iff it exports the fleet-policy API, else None
    (pure-Python run or stale prebuilt .so)."""
    lib = load()
    if lib is None or not hasattr(lib, "htpu_policy_create"):
        return None
    return lib


class NativeFleetPolicy:
    """ctypes wrapper over the C++ fleet-policy decision engine.  Covers
    the decision surface (observe/evict/rerank/autoscale plus the ewma
    and consecutive-slow probes) of the pure-Python mirror in
    horovod_tpu/policy.py; used for parity tests and offline replay —
    the in-job native policy lives inside the ControlPlane itself."""

    def __init__(self):
        lib = _policy_lib()
        if lib is None:
            raise RuntimeError("native fleet policy not available")
        self._lib = lib
        self._ptr = lib.htpu_policy_create()

    def close(self) -> None:
        if self._ptr:
            self._lib.htpu_policy_destroy(self._ptr)
            self._ptr = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def active(self) -> bool:
        return bool(self._lib.htpu_policy_active(self._ptr))

    def observe_tick(self, tick: int, wait_s) -> None:
        n = len(wait_s)
        arr = (ctypes.c_double * n)(*[float(w) for w in wait_s])
        self._lib.htpu_policy_observe(self._ptr, int(tick), arr, n)

    def next_eviction(self, process_count: int, seat_available: bool) -> int:
        return self._lib.htpu_policy_next_eviction(
            self._ptr, int(process_count), 1 if seat_available else 0)

    def rerank_order(self, old_pidx):
        n = len(old_pidx)
        arr = (ctypes.c_int * n)(*[int(p) for p in old_pidx])
        self._lib.htpu_policy_rerank(self._ptr, arr, n)
        return list(arr)

    def autoscale_target(self, tick: int) -> int:
        return self._lib.htpu_policy_autoscale_target(self._ptr, int(tick))

    def ewma(self, proc: int) -> float:
        return float(self._lib.htpu_policy_ewma(self._ptr, int(proc)))

    def consecutive_slow(self, proc: int) -> int:
        return self._lib.htpu_policy_consecutive_slow(self._ptr, int(proc))

    # -- per-set straggler state (PR 15).  A stale .so without the set
    # endpoints raises, matching the parity tests' skip condition.

    def observe_tick_set(self, process_set: int, wait_s) -> None:
        if not hasattr(self._lib, "htpu_policy_observe_set"):
            raise RuntimeError("native per-set policy not available")
        n = len(wait_s)
        arr = (ctypes.c_double * n)(*[float(w) for w in wait_s])
        self._lib.htpu_policy_observe_set(self._ptr, int(process_set), arr, n)

    def ewma_set(self, process_set: int, proc: int) -> float:
        if not hasattr(self._lib, "htpu_policy_ewma_set"):
            raise RuntimeError("native per-set policy not available")
        return float(self._lib.htpu_policy_ewma_set(
            self._ptr, int(process_set), int(proc)))

    def consecutive_slow_set(self, process_set: int, proc: int) -> int:
        if not hasattr(self._lib, "htpu_policy_consecutive_slow_set"):
            raise RuntimeError("native per-set policy not available")
        return self._lib.htpu_policy_consecutive_slow_set(
            self._ptr, int(process_set), int(proc))

    def next_eviction_set(self, process_set: int, process_count: int,
                          seat_available: bool) -> int:
        if not hasattr(self._lib, "htpu_policy_next_eviction_set"):
            raise RuntimeError("native per-set policy not available")
        return self._lib.htpu_policy_next_eviction_set(
            self._ptr, int(process_set), int(process_count),
            1 if seat_available else 0)

    # -- precision controller (PR 19).  A stale .so without the
    # precision endpoints raises, matching the parity tests' skip
    # condition.

    def _precision_lib(self):
        if not hasattr(self._lib, "htpu_policy_precision_auto"):
            raise RuntimeError("native precision controller not available")
        return self._lib

    def precision_auto(self) -> bool:
        return bool(self._precision_lib().htpu_policy_precision_auto(
            self._ptr))

    def observe_precision(self, name: str, residual_norm: float) -> None:
        self._precision_lib().htpu_policy_precision_observe(
            self._ptr, name.encode(), float(residual_norm))

    def note_precision_bandwidth(self, min_leg_bps: float) -> None:
        self._precision_lib().htpu_policy_precision_bandwidth(
            self._ptr, float(min_leg_bps))

    def precision_level(self, name: str) -> int:
        return self._precision_lib().htpu_policy_precision_level(
            self._ptr, name.encode())

    def precision_wire(self, name: str) -> str:
        from .policy import PRECISION_WIRE
        return PRECISION_WIRE[self.precision_level(name)]

    def precision_ewma(self, name: str) -> float:
        return float(self._precision_lib().htpu_policy_precision_ewma(
            self._ptr, name.encode()))

    @property
    def precision_promotions(self) -> int:
        counts = (ctypes.c_longlong * 2)()
        self._precision_lib().htpu_policy_precision_counts(self._ptr, counts)
        return int(counts[0])

    @property
    def precision_demotions(self) -> int:
        counts = (ctypes.c_longlong * 2)()
        self._precision_lib().htpu_policy_precision_counts(self._ptr, counts)
        return int(counts[1])

    def take_precision_dirty(self) -> bool:
        return bool(self._precision_lib().htpu_policy_precision_dirty(
            self._ptr))


def wire_request_list_roundtrip(frame: bytes):
    """Parse + re-serialize a RequestList frame through the native codec
    (the py<->cpp framing parity hook; payload codecs have their own
    htpu_wire_encode/decode endpoints).  Returns the re-serialized bytes,
    or None when the loaded .so predates the endpoint.  Raises
    ValueError when the native parser rejects the frame."""
    lib = load()
    if lib is None or not hasattr(lib, "htpu_wire_request_list_roundtrip"):
        return None
    cap = len(frame) + 64
    out = ctypes.create_string_buffer(cap)
    n = lib.htpu_wire_request_list_roundtrip(frame, len(frame), out, cap)
    if n < 0:
        raise ValueError("native RequestList parse rejected the frame")
    return out.raw[:n]


def _process_sets_lib():
    """The loaded library iff it exports the process-set API, else None
    (pure-Python run or stale prebuilt .so)."""
    lib = load()
    if lib is None or not hasattr(lib, "htpu_process_sets_create"):
        return None
    return lib


class CppProcessSetTable:
    """ctypes wrapper over the native multi-tenant process-set registry
    (cpp/htpu/process_set.h), with the interface of the Python mirror in
    horovod_tpu/process_set.py.  Set ids start at 1; 0 is the implicit
    default/world set."""

    def __init__(self, cache_capacity: int = 0):
        lib = _process_sets_lib()
        if lib is None:
            raise RuntimeError("native process sets not available")
        self._lib = lib
        self._ptr = lib.htpu_process_sets_create(int(cache_capacity))

    def close(self) -> None:
        if self._ptr:
            self._lib.htpu_process_sets_destroy(self._ptr)
            self._ptr = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def parse_spec(self, spec: str) -> bool:
        return bool(self._lib.htpu_process_sets_parse_spec(
            self._ptr, spec.encode("utf-8")))

    def add(self, name: str, ranks) -> int:
        n = len(ranks)
        arr = (ctypes.c_int * n)(*[int(r) for r in ranks])
        return self._lib.htpu_process_sets_add(
            self._ptr, name.encode("utf-8"), arr, n)

    def remove(self, set_id: int) -> bool:
        return bool(self._lib.htpu_process_sets_remove(self._ptr,
                                                       int(set_id)))

    def id_of(self, name: str) -> int:
        return self._lib.htpu_process_sets_id_of(self._ptr,
                                                 name.encode("utf-8"))

    def count(self) -> int:
        return self._lib.htpu_process_sets_count(self._ptr)

    def size_of(self, set_id: int) -> int:
        return self._lib.htpu_process_sets_size(self._ptr, int(set_id))

    def local_rank(self, set_id: int, global_rank: int) -> int:
        return self._lib.htpu_process_sets_local_rank(
            self._ptr, int(set_id), int(global_rank))

    def generation(self, set_id: int) -> int:
        return self._lib.htpu_process_sets_generation(self._ptr, int(set_id))

    def reconfigure(self, set_id: int, lost_global_rank: int) -> int:
        return self._lib.htpu_process_sets_reconfigure(
            self._ptr, int(set_id), int(lost_global_rank))

    def increment(self, set_id: int, msg: Request) -> int:
        # Same single-message boundary format as CppMessageTable.increment
        # (always with_algo; the set id is the explicit arg, never re-read
        # from the frame).
        data = wire.serialize_request(msg, with_algo=True)
        return self._lib.htpu_process_sets_increment(
            self._ptr, int(set_id), data, len(data))

    def construct_response(self, set_id: int, name: str) -> Response:
        out = ctypes.c_void_p()
        n = self._lib.htpu_process_sets_construct(
            self._ptr, int(set_id), name.encode("utf-8"), ctypes.byref(out))
        if n < 0:
            raise KeyError(f"unknown process set {set_id}")
        resp = wire.parse_single_response(_take_buffer(self._lib, out, n))
        resp.process_set = int(set_id)
        return resp


def wire_roundtrip(wire_dtype: str, values):
    """Encode → decode a float32 array through the ring wire codec
    (chunked exactly like the data plane); returns ``(decoded, wire_bytes)``.
    Unit-test hook for the quantizers — no sockets involved."""
    import numpy as np
    lib = load()
    if lib is None:
        raise RuntimeError("native core not available")
    arr = np.ascontiguousarray(values, dtype=np.float32)
    out = np.empty_like(arr)
    nbytes = lib.htpu_wire_roundtrip(
        wire_dtype.encode("utf-8"), arr.ctypes.data, arr.size,
        out.ctypes.data)
    if nbytes < 0:
        raise ValueError(f"unknown wire dtype: {wire_dtype!r}")
    return out, int(nbytes)


def wire_encode(wire_dtype: str, values) -> bytes:
    """Encode a float32 array into the ring's wire image
    (``EncodeWireChunk`` framing, per 64K-element sub-chunk).  Unit-test
    hook for cross-plane codec parity against the in-jit encoder."""
    import numpy as np
    lib = load()
    if lib is None or getattr(lib, "htpu_wire_encode", None) is None:
        raise RuntimeError("native core wire codec not available")
    arr = np.ascontiguousarray(values, dtype=np.float32).reshape(-1)
    total = lib.htpu_wire_bytes(wire_dtype.encode("utf-8"), arr.size)
    if total < 0:
        raise ValueError(f"unknown wire dtype: {wire_dtype!r}")
    out = np.empty(int(total), dtype=np.uint8)
    rc = lib.htpu_wire_encode(wire_dtype.encode("utf-8"), arr.ctypes.data,
                              arr.size, out.ctypes.data)
    if rc < 0:
        raise ValueError(f"wire encode failed for {wire_dtype!r}")
    return out.tobytes()


def wire_decode(wire_dtype: str, buf: bytes, n_elems: int):
    """Decode a wire image produced by :func:`wire_encode` (or by the
    in-jit encoder — that is the point) back to float32."""
    import numpy as np
    lib = load()
    if lib is None or getattr(lib, "htpu_wire_decode", None) is None:
        raise RuntimeError("native core wire codec not available")
    inp = np.frombuffer(buf, dtype=np.uint8)
    out = np.empty(n_elems, dtype=np.float32)
    rc = lib.htpu_wire_decode(wire_dtype.encode("utf-8"), inp.ctypes.data,
                              n_elems, out.ctypes.data)
    if rc < 0:
        raise ValueError(f"wire decode failed for {wire_dtype!r}")
    return out


def sum_into(dtype: str, acc, inp) -> None:
    """Native ``acc += inp`` elementwise (reduce.h SumInto) on two
    C-contiguous same-size numpy arrays; ``dtype`` is the htpu dtype name
    (may differ from the arrays' numpy dtype — e.g. "bfloat16" over uint16
    storage).  Unit-test hook for the parallel reduction path."""
    lib = load()
    if lib is None:
        raise RuntimeError("native core not available")
    if acc.nbytes != inp.nbytes:
        raise ValueError("size mismatch")
    rc = lib.htpu_sum_into(dtype.encode("utf-8"), acc.ctypes.data,
                           inp.ctypes.data, acc.nbytes)
    if rc != 0:
        raise ValueError(f"SumInto failed for dtype {dtype!r}")


def _parse_stall_records(data: bytes):
    """Decode the stall wire format (c_api.cc SerializeStallRecords):
    repeated { name_len:i32 name age:f64 n_missing:i32 ranks:i32[n] },
    little-endian.  Returns ``(name, age_s, missing_ranks)`` triples."""
    import struct
    result, pos = [], 0
    while pos < len(data):
        (nlen,) = struct.unpack_from("<i", data, pos)
        pos += 4
        name = data[pos:pos + nlen].decode("utf-8")
        pos += nlen
        (age,) = struct.unpack_from("<d", data, pos)
        pos += 8
        (nmiss,) = struct.unpack_from("<i", data, pos)
        pos += 4
        ranks = list(struct.unpack_from(f"<{nmiss}i", data, pos))
        pos += 4 * nmiss
        result.append((name, age, ranks))
    return result


def metrics_snapshot() -> dict:
    """JSON snapshot of the native metrics registry (cpp/htpu/metrics.h):
    ``{"counters": {...}, "gauges": {...}, "histograms": {...}}``.
    Empty dict when the native core is unavailable."""
    import json
    lib = load()
    if lib is None:
        return {}
    out = ctypes.c_void_p()
    n = lib.htpu_metrics_snapshot(ctypes.byref(out))
    if n < 0:
        return {}
    return json.loads(_take_buffer(lib, out, n).decode("utf-8"))


def metrics_reset() -> None:
    """Zero every native counter/gauge/histogram (tests, bench windows)."""
    lib = load()
    if lib is not None:
        lib.htpu_metrics_reset()


def agg_merge(a: bytes, b: bytes):
    """Fold serialized aggregation container ``b`` into ``a`` through the
    native merge (cpp/htpu/aggregate.cc) and return the canonical merged
    container bytes.  ``None`` when the native core is unavailable or
    predates the aggregation tier; raises ``ValueError`` on a corrupt
    container — the parity seam tests/test_aggregate.py drives against
    the Python mirror (horovod_tpu/aggregate.py)."""
    lib = load()
    if lib is None or not hasattr(lib, "htpu_agg_merge"):
        return None
    out = ctypes.c_void_p()
    n = lib.htpu_agg_merge(a, len(a), b, len(b), ctypes.byref(out))
    if n < 0:
        raise ValueError("corrupt aggregation container")
    return _take_buffer(lib, out, n)


def agg_roundtrip(buf: bytes):
    """Parse + canonically re-serialize one aggregation container through
    the native code.  ``None`` when the native core is unavailable or
    predates the aggregation tier; raises ``ValueError`` on a corrupt
    container."""
    lib = load()
    if lib is None or not hasattr(lib, "htpu_agg_roundtrip"):
        return None
    out = ctypes.c_void_p()
    n = lib.htpu_agg_roundtrip(buf, len(buf), ctypes.byref(out))
    if n < 0:
        raise ValueError("corrupt aggregation container")
    return _take_buffer(lib, out, n)


def observe_enabled():
    """Native observatory state: True/False, or ``None`` when the native
    core is unavailable or predates the observatory."""
    lib = load()
    if lib is None or not hasattr(lib, "htpu_observe_enabled"):
        return None
    return bool(lib.htpu_observe_enabled())


def observe_set_enabled(on: bool) -> None:
    """Flip the native observatory at runtime (bench A/B, tests)."""
    lib = load()
    if lib is not None and hasattr(lib, "htpu_observe_set_enabled"):
        lib.htpu_observe_set_enabled(1 if on else 0)


def observe_note_step(step_s: float, compute_s: float = 0.0,
                      hidden_s: float = 0.0, exposed_s: float = 0.0,
                      stall_s: float = 0.0) -> bool:
    """Feed one step's decomposition to the native observatory; returns
    False when the native core is unavailable (caller falls back to the
    Python registry)."""
    lib = load()
    if lib is None or not hasattr(lib, "htpu_observe_note_step"):
        return False
    lib.htpu_observe_note_step(step_s, compute_s, hidden_s, exposed_s,
                               stall_s)
    return True


def observe_snapshot() -> dict:
    """Local telemetry digest (step EWMAs, per-leg bandwidth EWMAs,
    inflight) as a dict; empty when the native core is unavailable."""
    import json
    lib = load()
    if lib is None or not hasattr(lib, "htpu_observe_snapshot"):
        return {}
    out = ctypes.c_void_p()
    n = lib.htpu_observe_snapshot(ctypes.byref(out))
    if n < 0:
        return {}
    return json.loads(_take_buffer(lib, out, n).decode("utf-8"))


def observe_reset() -> None:
    """Zero the native observatory EWMAs and counts (tests, bench A/B)."""
    lib = load()
    if lib is not None and hasattr(lib, "htpu_observe_reset"):
        lib.htpu_observe_reset()


def observe_record_xfer(leg: int, sent_bytes: int, recv_bytes: int,
                        seconds: float) -> None:
    """Test seam: record one transfer on leg 0..3 (classic/shm/uring/
    ctrl) without driving a real job."""
    lib = load()
    if lib is not None and hasattr(lib, "htpu_observe_record_xfer"):
        lib.htpu_observe_record_xfer(leg, sent_bytes, recv_bytes, seconds)


def observe_trailer_encode() -> bytes:
    """The telemetry trailer this process would append to its next tick
    frame — b"" when the observatory is off (golden-frame contract)."""
    lib = load()
    if lib is None or not hasattr(lib, "htpu_observe_trailer_encode"):
        return b""
    out = ctypes.c_void_p()
    n = lib.htpu_observe_trailer_encode(ctypes.byref(out))
    if n <= 0:
        return b""
    return _take_buffer(lib, out, n)


def observe_trailer_probe(blob: bytes) -> dict:
    """Strip-probe arbitrary frame bytes the way the coordinator does:
    ``{"stripped": bool, "payload_len": int, "sample": {...}}``; empty
    dict when the native core is unavailable."""
    import json
    lib = load()
    if lib is None or not hasattr(lib, "htpu_observe_trailer_probe"):
        return {}
    out = ctypes.c_void_p()
    n = lib.htpu_observe_trailer_probe(blob, len(blob), ctypes.byref(out))
    if n < 0:
        return {}
    return json.loads(_take_buffer(lib, out, n).decode("utf-8"))


def crc32c_native(data: bytes):
    """CRC32C (Castagnoli) via the native runtime-dispatched path (SSE4.2
    when available); ``None`` when the native core is unavailable or
    predates the integrity layer — callers fall back to the pure-Python
    table in horovod_tpu.wire."""
    lib = load()
    if lib is None or not hasattr(lib, "htpu_crc32c"):
        return None
    return int(lib.htpu_crc32c(data, len(data)))


def crc32c_native_sw(data: bytes):
    """The native software (table) path, regardless of CPU support — for
    pinning hardware == software == Python on the same inputs."""
    lib = load()
    if lib is None or not hasattr(lib, "htpu_crc32c_sw"):
        return None
    return int(lib.htpu_crc32c_sw(data, len(data)))


def crc32c_hardware() -> bool:
    """True when the native dispatcher selected the SSE4.2 path."""
    lib = load()
    if lib is None or not hasattr(lib, "htpu_crc32c_hw"):
        return False
    return bool(lib.htpu_crc32c_hw())


class CppControlPlane:
    """Multi-process control + eager data plane (TCP, native).

    Replaces the reference's MPI gather/bcast negotiation and CPU MPI data
    plane (``operations.cc:1665-1903, 1232-1353``).  Process 0 is the
    coordinator; construction blocks until the whole job is connected.
    """

    def __init__(self, process_index: int, process_count: int, host: str,
                 port: int, first_rank: int, nranks_total: int,
                 timeout_ms: int = 60000):
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native core not available")
        # Serializes destruction against an attached timeline's __del__
        # detach (CppTimeline.__del__): without it the detach could call
        # into a plane freed between its pointer snapshot and the ctypes
        # call.
        self._teardown_lock = threading.Lock()
        self._ptr = self._lib.htpu_control_create(
            process_index, process_count, host.encode("utf-8"), port,
            first_rank, nranks_total, timeout_ms)
        if not self._ptr:
            raise ConnectionError(
                f"control plane failed to form (coordinator {host}:{port}, "
                f"process {process_index}/{process_count})")

    def tick(self, request_list_blob: bytes,
             fusion_threshold: int) -> bytes:
        out = ctypes.c_void_p()
        n = self._lib.htpu_control_tick(
            self._ptr, request_list_blob, len(request_list_blob),
            fusion_threshold, ctypes.byref(out))
        if n < 0:
            raise ConnectionError("control-plane tick failed")
        return _take_buffer(self._lib, out, n)

    def allreduce(self, dtype: str, data, wire_dtype: str = "",
                  algo: str = "") -> bytes:
        """Allreduce ``data`` (bytes, or a C-contiguous numpy array —
        arrays are read straight from their buffer, skipping a
        ``tobytes`` copy; the payload path is copy-bound at multi-MB
        gradients).  ``wire_dtype`` selects the ring wire compression
        ("" = raw; "bf16"/"fp16"/"int8", float32 payloads only — see
        cpp/htpu/quantize.h).  ``algo`` is the coordinator-resolved
        collective algorithm ("" = flat ring; "hier" = two-level
        hierarchical; "small" = latency-optimal small-tensor path —
        cpp/htpu/control.h)."""
        import numpy as np
        if isinstance(data, np.ndarray):
            if not data.flags["C_CONTIGUOUS"]:
                data = np.ascontiguousarray(data)
            ptr, length = data.ctypes.data, data.nbytes
        else:
            ptr, length = data, len(data)
        out = ctypes.c_void_p()
        n = self._lib.htpu_control_allreduce_algo(
            self._ptr, dtype.encode("utf-8"), wire_dtype.encode("utf-8"),
            algo.encode("utf-8"), ptr, length, ctypes.byref(out))
        if n < 0:
            raise ConnectionError(
                "data-plane allreduce failed"
                + (f" (wire dtype {wire_dtype!r})" if wire_dtype else "")
                + (f" (algo {algo!r})" if algo else ""))
        return _take_buffer(self._lib, out, n)

    def allgather(self, data: bytes) -> bytes:
        out = ctypes.c_void_p()
        n = self._lib.htpu_control_allgather(
            self._ptr, data, len(data), ctypes.byref(out))
        if n < 0:
            raise ConnectionError("data-plane allgather failed")
        return _take_buffer(self._lib, out, n)

    def broadcast(self, root_process: int, data: bytes) -> bytes:
        out = ctypes.c_void_p()
        n = self._lib.htpu_control_broadcast(
            self._ptr, root_process, data, len(data), ctypes.byref(out))
        if n < 0:
            raise ConnectionError("data-plane broadcast failed")
        return _take_buffer(self._lib, out, n)

    def data_bytes(self):
        """(sent, received) cumulative eager data-plane payload bytes of
        this process — the ring keeps both O(payload) per collective
        regardless of process count."""
        sent = ctypes.c_longlong()
        recvd = ctypes.c_longlong()
        self._lib.htpu_control_data_bytes(self._ptr, ctypes.byref(sent),
                                          ctypes.byref(recvd))
        return sent.value, recvd.value

    def ring_transport(self) -> str:
        """'uds' when the ring-next hop rides a Unix domain socket (the
        co-located on-host fast path), 'tcp' across hosts, 'none' when
        single-process."""
        return self._lib.htpu_control_ring_transport(
            self._ptr).decode("ascii")

    def data_transport(self) -> str:
        """Zero-copy transports active on the data plane: 'classic',
        'shm', 'uring', or 'shm+uring' (HOROVOD_TPU_TRANSPORT and any
        runtime fallbacks both reflected)."""
        return self._lib.htpu_control_data_transport(
            self._ptr).decode("ascii")

    def stalled(self, age_s: float):
        out = ctypes.c_void_p()
        n = self._lib.htpu_control_stalled(self._ptr, age_s,
                                           ctypes.byref(out))
        return _parse_stall_records(_take_buffer(self._lib, out, n))

    def membership(self):
        """Current elastic membership identity of this process:
        ``(process_index, process_count, first_rank, generation)``.  All
        four change together on a RECONFIGURE — re-read after any tick
        whose response carried a reconfigure payload.  Generation is 0
        (and the rest Create-time constants) on non-elastic planes or an
        older native core."""
        if not hasattr(self._lib, "htpu_control_membership"):
            return -1, -1, -1, 0
        pi = ctypes.c_int()
        pc = ctypes.c_int()
        fr = ctypes.c_int()
        gen = ctypes.c_int()
        self._lib.htpu_control_membership(
            self._ptr, ctypes.byref(pi), ctypes.byref(pc), ctypes.byref(fr),
            ctypes.byref(gen))
        return pi.value, pc.value, fr.value, gen.value

    def elastic(self) -> bool:
        """True when HOROVOD_TPU_ELASTIC=1 was honoured by this plane."""
        if not hasattr(self._lib, "htpu_control_elastic"):
            return False
        return bool(self._lib.htpu_control_elastic(self._ptr))

    def set_xfer_context(self, tensors: str) -> None:
        """Name the tensors of the collective about to run; a checked
        transfer that exhausts its retransmit budget folds this into the
        attributed error (HOROVOD_TPU_INTEGRITY).  No-op on an older
        native core."""
        if hasattr(self._lib, "htpu_control_set_xfer_context"):
            self._lib.htpu_control_set_xfer_context(
                self._ptr, tensors.encode("utf-8", "replace"))

    def last_error(self):
        """Attribution of the most recent native failure on this process:
        ``(failed_first_rank, reason)`` — rank is -1 when nothing failed.
        Read after a ConnectionError from the data plane to build the
        worker's abort report."""
        rank = ctypes.c_int(-1)
        out = ctypes.c_void_p()
        n = self._lib.htpu_control_last_error(self._ptr, ctypes.byref(rank),
                                              ctypes.byref(out))
        reason = _take_buffer(self._lib, out, n).decode("utf-8", "replace")
        return rank.value, reason

    def close(self):
        if getattr(self, "_leaked", False):
            return   # pointer stays valid for the wedged thread; no free
        with self._teardown_lock:
            ptr, self._ptr = self._ptr, None
            if ptr:
                self._lib.htpu_control_destroy(ptr)

    def leak(self):
        """Disarm destruction WITHOUT invalidating the pointer — for
        shutdown with a wedged background thread still inside (or about
        to make) a control-plane call: destroying would be a
        use-after-free, and nulling the pointer would turn the thread's
        next ctypes call into a NULL dereference in C++.  The object is
        reclaimed by process exit."""
        self._leaked = True

    def __del__(self):
        try:
            self.close()
        except Exception:   # noqa: BLE001 — interpreter teardown
            pass


class CppTimeline:
    """Native Chrome-trace writer with the interface of
    :class:`horovod_tpu.timeline.Timeline`.

    Every method tolerates a closed timeline (no-op) — the executor may race
    a late span against ``Controller.stop()``'s close, and calling into C++
    with a destroyed object would crash the interpreter where the Python
    fallback merely raises.
    """

    def __init__(self, path: str, rank: int = 0):
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native core not available")
        if hasattr(self._lib, "htpu_timeline_create_rank"):
            self._ptr = self._lib.htpu_timeline_create_rank(
                path.encode("utf-8"), int(rank))
        else:   # stale prebuilt .so: trace_t0 reports rank 0
            self._ptr = self._lib.htpu_timeline_create(path.encode("utf-8"))
        if not self._ptr:
            raise OSError(f"cannot open timeline file: {path}")
        self.rank = rank

    def attach_to_control(self, control: "CppControlPlane") -> None:
        """Wire this writer into the native coordinator so its Tick loop
        emits NEGOTIATE_* spans (multi-process mode negotiates in C++,
        bypassing the Python MessageTable's timeline hooks).  Lifetime:
        the Controller closes the control plane before this timeline; for
        teardown paths that skip the Controller (no hvd.shutdown), the
        weakref lets ``__del__`` detach instead of destroying under the
        coordinator's raw pointer."""
        if self._ptr and control._ptr:
            import weakref
            self._lib.htpu_control_set_timeline(control._ptr, self._ptr)
            self._control_ref = weakref.ref(control)

    def negotiate_start(self, tensor_name: str, request_type) -> None:
        if not self._ptr:
            return
        self._lib.htpu_timeline_negotiate_start(
            self._ptr, tensor_name.encode("utf-8"), int(request_type))

    def negotiate_rank_ready(self, tensor_name: str, rank: int) -> None:
        if not self._ptr:
            return
        self._lib.htpu_timeline_negotiate_rank_ready(
            self._ptr, tensor_name.encode("utf-8"), rank)

    def negotiate_end(self, tensor_name: str) -> None:
        if not self._ptr:
            return
        self._lib.htpu_timeline_negotiate_end(
            self._ptr, tensor_name.encode("utf-8"))

    def start(self, tensor_name: str, response_type) -> None:
        if not self._ptr:
            return
        self._lib.htpu_timeline_start(
            self._ptr, tensor_name.encode("utf-8"), int(response_type))

    def end(self, tensor_name: str) -> None:
        if not self._ptr:
            return
        self._lib.htpu_timeline_end(self._ptr, tensor_name.encode("utf-8"))

    def activity_start_all(self, entries, activity: str) -> None:
        if not self._ptr:
            return
        for e in entries:
            self._lib.htpu_timeline_activity_start(
                self._ptr, e.name.encode("utf-8"), activity.encode("utf-8"))

    def activity_end_all(self, entries) -> None:
        if not self._ptr:
            return
        for e in entries:
            self._lib.htpu_timeline_activity_end(
                self._ptr, e.name.encode("utf-8"))

    def activity_span(self, tensor_name: str, activity: str,
                      start_ns: int, end_ns: int) -> None:
        """A whole activity the caller timed itself on
        ``time.perf_counter_ns()``, as one complete event on the lane.  A
        prebuilt library from before PR 34 marks the lane with an empty
        begin / end pair instead."""
        if not self._ptr:
            return
        name = tensor_name.encode("utf-8")
        if hasattr(self._lib, "htpu_timeline_activity_span"):
            self._lib.htpu_timeline_activity_span(
                self._ptr, name, activity.encode("utf-8"),
                (end_ns - start_ns) // 1000,
                (time.perf_counter_ns() - end_ns) // 1000)
        else:
            self._lib.htpu_timeline_activity_start(
                self._ptr, name, activity.encode("utf-8"))
            self._lib.htpu_timeline_activity_end(self._ptr, name)

    def counter(self, name: str, value: int) -> None:
        """Chrome-trace counter sample ("ph": "C") — queue depth, bytes in
        flight — rendered by Perfetto as a rate track."""
        if not self._ptr:
            return
        self._lib.htpu_timeline_counter(
            self._ptr, name.encode("utf-8"), int(value))

    def cache_hit_tick(self, dur_us: int) -> None:
        """CACHED_TICK complete-event span — a negotiation tick served
        entirely from the response cache."""
        if not self._ptr:
            return
        self._lib.htpu_timeline_cache_hit_tick(self._ptr, int(dur_us))

    def tick_span(self, tick: int, dur_us: int) -> None:
        """TICK complete-event span tagged with the tick id — the
        cross-rank alignment anchor trace_merge.py lines traces up by."""
        if not self._ptr or not hasattr(self._lib,
                                        "htpu_timeline_tick_span"):
            return
        self._lib.htpu_timeline_tick_span(self._ptr, int(tick), int(dur_us))

    def instant(self, name: str, args: dict = None) -> None:
        """Global instant event on the control track."""
        if not self._ptr or not hasattr(self._lib, "htpu_timeline_instant"):
            return
        import json
        self._lib.htpu_timeline_instant(
            self._ptr, name.encode("utf-8"),
            json.dumps(args or {}).encode("utf-8"))

    def flush(self) -> None:
        if self._ptr:
            self._lib.htpu_timeline_flush(self._ptr)

    def leak(self):
        """Abandon the native writer WITHOUT destroying it — for shutdown
        with a wedged background thread whose control plane still holds
        the raw Timeline pointer (see Controller.stop).  The file is
        finalized best-effort: ``htpu_timeline_close`` only closes the
        stream under the object's own mutex and every later write no-ops,
        so the wedged thread can still call through its stale pointer
        safely — only ``htpu_timeline_destroy`` is the use-after-free
        hazard, and that never runs for a leaked writer (``__del__`` sees
        a null ``_ptr``).  The close runs on a bounded-wait daemon
        thread: in the usual wedge (thread stuck in a control-plane recv)
        the timeline mutex is free and it finishes instantly, but a
        writer wedged INSIDE ``Emit`` (full disk, hung NFS) holds that
        mutex, and leak() must never convert a 90 s join timeout into an
        unbounded hang of shutdown itself."""
        ptr, self._ptr = self._ptr, None
        if ptr:
            import threading

            def _close():
                try:
                    self._lib.htpu_timeline_close(ptr)
                except Exception:   # noqa: BLE001 — best-effort finalize
                    pass

            t = threading.Thread(target=_close, daemon=True,
                                 name="htpu-timeline-leak-close")
            t.start()
            t.join(timeout=2.0)

    def close(self):
        # Close only finalizes the file; the C++ object stays alive (its
        # methods no-op once closed, under its own mutex) so a racing span
        # from the executor can never hit freed memory.  The object itself
        # is destroyed when this wrapper is garbage collected.
        if self._ptr:
            self._lib.htpu_timeline_close(self._ptr)

    def __del__(self):
        try:
            ptr, self._ptr = self._ptr, None
            if not ptr:
                return
            self._lib.htpu_timeline_close(ptr)
            ctrl = (self._control_ref()
                    if hasattr(self, "_control_ref") else None)
            if ctrl is not None:
                # Interpreter teardown without hvd.shutdown(): the native
                # coordinator may still hold this raw pointer while its
                # tick caller (a daemon thread) is mid-call.  Under the
                # plane's teardown lock — so a concurrent close() cannot
                # destroy the plane between the pointer read and the
                # call — detach so new ticks see no timeline, and LEAK
                # the object instead of destroying under a
                # possibly-in-flight span: a stale pointer into the
                # closed-but-alive writer is a locked no-op, a destroyed
                # one is a use-after-free.  Bounded acquire: this
                # finalizer can run via cyclic GC ON the thread currently
                # holding the lock inside close() — a blocking acquire
                # there would deadlock the interpreter; on timeout, leak
                # the writer without detaching (still safe: close() only
                # destroys the PLANE, and this writer is never destroyed).
                if not ctrl._teardown_lock.acquire(timeout=2.0):
                    return
                try:
                    ctrl_ptr = getattr(ctrl, "_ptr", None)
                    if ctrl_ptr:
                        self._lib.htpu_control_set_timeline(ctrl_ptr, None)
                        return
                finally:
                    ctrl._teardown_lock.release()
                # Plane already closed: nothing references the writer any
                # more — destroying it below is safe.
            self._lib.htpu_timeline_destroy(ptr)
        except Exception:   # noqa: BLE001 — interpreter teardown
            pass
