"""ctypes loader for the native graph engine (libeuler_core.so).

Parity: the reference loads libeuler_core.so / libtf_euler.so via ctypes
(euler/python/start_service.py:27-30, tf_euler/python/euler_ops/base.py).
Here there is a single library exposing the batch C API defined in
euler_tpu/core/cc/capi.cc; this module declares argtypes once and exposes
the raw handle-based functions. Use euler_tpu.graph.GraphEngine for the
numpy-facing wrapper.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import subprocess

_HERE = os.path.dirname(os.path.abspath(__file__))
_CC = os.path.join(_HERE, "cc")
_LIB_PATH = os.path.join(_HERE, "libeuler_core.so")
# content hash of the sources the library was built from, written beside
# it at build time (git-ignored like the library itself)
_STAMP_PATH = _LIB_PATH + ".srchash"

_lib = None


def source_hash() -> str:
    """sha256 over the tracked native sources (cc/*.cc, cc/*.h, the
    Makefile), names included. Content, not mtimes: a copy or checkout
    that rewrites every mtime must neither hide a source change nor
    force a rebuild."""
    h = hashlib.sha256()
    for name in sorted(os.listdir(_CC)):
        if name.endswith((".cc", ".h")) or name == "Makefile":
            h.update(name.encode() + b"\0")
            with open(os.path.join(_CC, name), "rb") as f:
                h.update(f.read())
            h.update(b"\0")
    return h.hexdigest()


def build_stamp():
    """The source hash the library on disk was built from (None when
    there is no library or no stamp)."""
    if not os.path.exists(_LIB_PATH):
        return None
    try:
        with open(_STAMP_PATH) as f:
            return f.read().strip() or None
    except OSError:
        return None


def _build(want: str) -> None:
    # -B: a stale library means the object files beside it cannot be
    # trusted either (make compares mtimes, which a copy rewrites)
    proc = subprocess.run(
        ["make", "-B", "-C", _CC, "-j", "4"],
        capture_output=True,
        text=True,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            "native engine build failed:\n" + proc.stdout + proc.stderr
        )
    tmp = _STAMP_PATH + f".{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        f.write(want + "\n")
    os.replace(tmp, _STAMP_PATH)


def load() -> ctypes.CDLL:
    """Load the native engine library, (re)building it first unless it
    was built from exactly the sources beside it. A failed build raises:
    an engine older than its sources never loads silently."""
    global _lib
    if _lib is not None:
        return _lib
    want = source_hash()
    # one builder at a time: concurrent first imports (shard servers,
    # subprocess drills) must not race make over the same objects
    with open(os.path.join(_HERE, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if build_stamp() != want:
            _build(want)
    lib = ctypes.CDLL(_LIB_PATH)
    _declare(lib)
    _lib = lib
    return lib


c_u64p = ctypes.POINTER(ctypes.c_uint64)
c_i64p = ctypes.POINTER(ctypes.c_int64)
c_i32p = ctypes.POINTER(ctypes.c_int32)
c_f32p = ctypes.POINTER(ctypes.c_float)
c_voidp = ctypes.c_void_p


def _declare(lib: ctypes.CDLL) -> None:
    i64, i32, u64, f32 = (
        ctypes.c_int64,
        ctypes.c_int,
        ctypes.c_uint64,
        ctypes.c_float,
    )
    sigs = {
        "etg_last_error": (ctypes.c_char_p, []),
        "etg_seed": (None, [u64]),
        "etg_set_log_level": (None, [i32]),
        "etg_builder_new": (i64, []),
        "etg_builder_set_feature": (i32, [i64, i32, i32, i32, i64, ctypes.c_char_p]),
        "etg_builder_set_num_types": (i32, [i64, i32, i32]),
        "etg_builder_set_type_name": (i32, [i64, i32, i32, ctypes.c_char_p]),
        "etg_type_id": (i32, [i64, i32, ctypes.c_char_p]),
        "etg_type_name": (i32, [i64, i32, i32, ctypes.c_char_p, i64]),
        "etg_builder_add_nodes": (i32, [i64, i64, c_u64p, c_i32p, c_f32p]),
        "etg_builder_add_edges": (i32, [i64, i64, c_u64p, c_u64p, c_i32p, c_f32p]),
        "etg_builder_set_node_dense": (i32, [i64, c_u64p, i64, i32, i64, c_f32p]),
        "etg_builder_set_node_sparse": (i32, [i64, c_u64p, i64, i32, c_u64p, c_u64p]),
        "etg_builder_set_node_binary": (i32, [i64, u64, i32, ctypes.c_char_p, i64]),
        "etg_builder_set_edge_dense": (i32, [i64, c_u64p, c_u64p, c_i32p, i64, i32, i64, c_f32p]),
        "etg_builder_set_edge_sparse": (i32, [i64, u64, u64, i32, i32, c_u64p, i64]),
        "etg_builder_set_edge_binary": (i32, [i64, u64, u64, i32, i32, ctypes.c_char_p, i64]),
        "etg_builder_finalize": (i64, [i64, i32]),
        "etg_load": (i64, [ctypes.c_char_p, i32, i32, i32, i32]),
        "etg_dump": (i32, [i64, ctypes.c_char_p, i32, i32]),
        "etg_free": (i32, [i64]),
        "etg_node_count": (i64, [i64]),
        "etg_edge_count": (i64, [i64]),
        "etg_num_node_types": (i32, [i64]),
        "etg_num_edge_types": (i32, [i64]),
        "etg_num_node_features": (i32, [i64]),
        "etg_num_edge_features": (i32, [i64]),
        "etg_feature_info": (i32, [i64, i32, i32, c_i32p, c_i64p, ctypes.c_char_p, i64]),
        "etg_all_node_ids": (i32, [i64, c_u64p]),
        "etg_node_rows": (i32, [i64, c_u64p, i64, i32, c_i32p]),
        "etg_builder_set_graph_labels": (i32, [i64, c_u64p, c_u64p, i64]),
        "etg_graph_label_count": (i64, [i64]),
        "etg_sample_graph_label": (i32, [i64, i64, c_u64p]),
        "etg_get_graph_by_label": (i32, [i64, c_u64p, i64, c_voidp]),
        "etg_all_node_weights": (i32, [i64, c_f32p]),
        "etg_node_weight_sums": (i32, [i64, c_f32p]),
        "etg_edge_weight_sums": (i32, [i64, c_f32p]),
        "etg_sample_node": (i32, [i64, i32, i64, c_u64p]),
        "etg_sample_node_with_types": (i32, [i64, c_i32p, i64, c_u64p]),
        "etg_sample_edge": (i32, [i64, i32, i64, c_u64p, c_u64p, c_i32p]),
        "etg_get_node_type": (i32, [i64, c_u64p, i64, c_i32p]),
        "etg_sample_neighbor": (i32, [i64, c_u64p, i64, c_i32p, i64, i64, u64, c_u64p, c_f32p, c_i32p]),
        "etg_sample_in_neighbor": (i32, [i64, c_u64p, i64, c_i32p, i64, i64, u64, c_u64p, c_f32p, c_i32p]),
        "etg_get_top_k_neighbor": (i32, [i64, c_u64p, i64, c_i32p, i64, i64, u64, c_u64p, c_f32p, c_i32p]),
        "etg_sample_fanout": (i32, [i64, c_u64p, i64, c_i32p, i64, c_i32p, c_i64p, u64, ctypes.POINTER(c_u64p), ctypes.POINTER(c_f32p), ctypes.POINTER(c_i32p)]),
        "etg_random_walk": (i32, [i64, c_u64p, i64, i64, f32, f32, u64, c_i32p, i64, c_u64p]),
        "etg_sample_layerwise": (i32, [i64, c_u64p, i64, c_i32p, i64, c_i32p, i64, u64, i32, ctypes.POINTER(c_u64p)]),
        "etg_get_dense_feature": (i32, [i64, c_u64p, i64, i32, i64, c_f32p]),
        "etg_get_edge_dense_feature": (i32, [i64, c_u64p, c_u64p, c_i32p, i64, i32, i64, c_f32p]),
        "etres_new": (c_voidp, []),
        "etres_free": (None, [c_voidp]),
        "etres_offsets_len": (i64, [c_voidp]),
        "etres_offsets": (c_u64p, [c_voidp]),
        "etres_u64_len": (i64, [c_voidp]),
        "etres_u64": (c_u64p, [c_voidp]),
        "etres_f32_len": (i64, [c_voidp]),
        "etres_f32": (c_f32p, [c_voidp]),
        "etres_i32_len": (i64, [c_voidp]),
        "etres_i32": (c_i32p, [c_voidp]),
        "etres_bytes_len": (i64, [c_voidp]),
        "etres_bytes": (ctypes.POINTER(ctypes.c_char), [c_voidp]),
        "etg_get_full_neighbor": (i32, [i64, c_u64p, i64, c_i32p, i64, i32, i32, c_voidp]),
        "etg_get_sparse_feature": (i32, [i64, c_u64p, i64, i32, c_voidp]),
        "etg_get_binary_feature": (i32, [i64, c_u64p, i64, i32, c_voidp]),
        "etg_get_edge_sparse_feature": (i32, [i64, c_u64p, c_u64p, c_i32p, i64, i32, c_voidp]),
        "etg_get_edge_binary_feature": (i32, [i64, c_u64p, c_u64p, c_i32p, i64, i32, c_voidp]),
        # query layer (gremlin → DAG → executor; local or distributed)
        "etq_new_local": (i64, [i64, ctypes.c_char_p, u64]),
        "etq_new_remote": (i64, [ctypes.c_char_p, u64, ctypes.c_char_p]),
        "etq_free": (i32, [i64]),
        "etq_stats": (i32, [i64, c_u64p]),
        "etq_index_dump": (i32, [i64, ctypes.c_char_p]),
        "etg_register_udf": (None, [ctypes.c_char_p, c_voidp]),
        "etg_udf_cache_stats": (None, [ctypes.POINTER(u64), ctypes.POINTER(u64), ctypes.POINTER(u64), ctypes.POINTER(u64)]),
        "etg_udf_cache_clear": (None, []),
        "etg_udf_cache_set_capacity": (None, [u64]),
        "etg_hash64": (u64, [ctypes.c_char_p, u64]),
        # RPC transport (protocol v2 mux / adaptive compression): global
        # config + client-edge counters — see euler_tpu.graph.remote
        # configure_rpc() / rpc_transport_stats() for the friendly wrapper
        # (+ prepared plans / plan-cache size / deflate reuse — the
        # wire-path knobs — and the plan-optimizer block: plan_optimize,
        # coalesce_window_us, reuse_window; stats out buffer is 37 u64s)
        "etg_rpc_config": (None, [i32, i32, i64, i32, i64, i32, i32,
                                  i32, i32, i32, i32, i64, i32]),
        "etg_rpc_stats": (None, [c_u64p]),
        # elastic fleet: epoch-versioned ownership maps — install on a
        # distribute-mode proxy / in-process server, push to a remote
        # server over the kSetOwnership admin verb, read epochs and
        # per-shard request counts (hot-shard detection)
        "etg_push_ownership": (i32, [ctypes.c_char_p, i32, ctypes.c_char_p, c_i64p]),
        "etq_set_ownership": (i32, [i64, ctypes.c_char_p]),
        "etq_ownership_epoch": (i64, [i64]),
        "etq_shard_num": (i32, [i64]),
        "etq_shard_stats": (i32, [i64, c_u64p, c_u64p, i32]),
        "ets_set_ownership": (i32, [i64, ctypes.c_char_p]),
        "ets_map_epoch": (i64, [i64]),
        # tail latency: per-thread deadline handoff for the next query
        # run (remaining ms; <= 0 clears) — REMOTE sub-calls stamp the
        # remaining budget into their v2 request frames
        "etg_set_call_deadline_ms": (None, [ctypes.c_double]),
        # cross-process tracing: per-thread (trace_id, parent_span)
        # handoff for the next query run (trace_id 0 clears); server-
        # side per-verb/phase timing histograms (out[27] = n, sum_us,
        # counts[25]) and the traced-span ring dump (stride-10 u64
        # records into an EtResult)
        "etg_set_call_trace": (None, [u64, u64]),
        "etg_server_trace_hist": (i32, [i32, i32, c_u64p]),
        "etg_server_trace_dump": (i32, [c_voidp]),
        # streaming deltas: graph epoch + batched O(delta) apply +
        # dirty-set retrieval, on embedded handles (etg_*) and query
        # proxies (etq_* — local swaps the handle's graph, distribute
        # broadcasts kApplyDelta to every shard)
        "etg_graph_epoch": (i64, [i64]),
        "etg_apply_delta": (i32, [i64, i64, c_u64p, c_i32p, c_f32p, i64, c_u64p, c_u64p, c_i32p, c_f32p, c_i64p]),
        "etg_delta_since": (i32, [i64, i64, c_voidp, c_i64p, c_i32p]),
        "etg_udf_cache_epoch_evictions": (u64, []),
        "etq_epoch": (i64, [i64]),
        "etq_apply_delta": (i32, [i64, i64, c_u64p, c_i32p, c_f32p, i64, c_u64p, c_u64p, c_i32p, c_f32p, c_i64p]),
        "etq_delta_since": (i32, [i64, i64, c_voidp, c_i64p, c_i32p]),
        "et_udf_emit": (None, [c_voidp, c_u64p, i64, c_f32p, i64]),
        "etq_exec_new": (i64, [i64]),
        "etq_exec_add_input": (i32, [i64, ctypes.c_char_p, i32, i32, c_i64p, c_voidp]),
        "etq_exec_run": (i32, [i64, ctypes.c_char_p]),
        "etq_exec_output_count": (i64, [i64]),
        "etq_exec_output_name": (ctypes.c_char_p, [i64, i64]),
        "etq_exec_output_info": (i32, [i64, i64, c_i32p, c_i32p, c_i64p]),
        "etq_exec_output_dims": (i32, [i64, i64, c_i64p]),
        "etq_exec_output_data": (c_voidp, [i64, i64]),
        "etq_exec_free": (i32, [i64]),
        "ets_start": (i64, [ctypes.c_char_p, i32, i32, i32, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p]),
        # durable form: + wal_dir, fsync_policy (0=never 1=always),
        # compact_bytes, catchup (registry anti-entropy on restart)
        "ets_start2": (i64, [ctypes.c_char_p, i32, i32, i32, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, i32, i64, i32]),
        # out-of-core form: + storage (0=ram 1=mmap), hot_bytes (hub
        # hot-set budget for the mmap tier)
        "ets_start3": (i64, [ctypes.c_char_p, i32, i32, i32, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, i32, i64, i32, i32, i64]),
        "ets_epoch": (i64, [i64]),
        "ets_port": (i32, [i64]),
        "ets_stop": (i32, [i64]),
        # durability counters: appends, fsyncs, replayed_records,
        # compactions, catchup_deltas, refused, torn_records, degraded
        "etg_wal_stats": (None, [c_u64p]),
        # out-of-core columnar store: write a handle's snapshot to a
        # store file / mmap-attach one as a new handle / process-global
        # tier counters (35 slots, store.h slot order)
        "etg_store_write": (i32, [i64, ctypes.c_char_p]),
        "etg_store_open": (i64, [ctypes.c_char_p, i64]),
        "etg_store_stats": (None, [c_u64p]),
        "etr_start": (i64, [i32]),
        "etr_port": (i32, [i64]),
        "etr_stop": (i32, [i64]),
        "etr_scan": (i64, [ctypes.c_char_p, ctypes.c_char_p, i64]),
        "etq_compile_debug": (i64, [ctypes.c_char_p, i32, i32, ctypes.c_char_p, ctypes.c_char_p, i64]),
        # explain(): stage 0 = as-registered plan, stage 1 = what the
        # server's prepare-time optimizer executes (+ rewrite counts,
        # determinism verdict); ets_plan_debug dumps a live server's
        # shared prepared-plan store
        "etq_compile_debug2": (i64, [ctypes.c_char_p, i32, i32, ctypes.c_char_p, i32, ctypes.c_char_p, i64]),
        "ets_plan_debug": (i64, [i64, ctypes.c_char_p, i64]),
    }
    for name, (restype, argtypes) in sigs.items():
        fn = getattr(lib, name)
        fn.restype = restype
        fn.argtypes = argtypes


class EngineError(RuntimeError):
    pass


def check(lib: ctypes.CDLL, rc: int) -> None:
    if rc != 0:
        raise EngineError(lib.etg_last_error().decode())
