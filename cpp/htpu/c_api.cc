// extern "C" surface of the native core, consumed from Python via ctypes.
//
// Equivalent role to the reference's C API + symbol-controlled .so
// (horovod/common/operations.h:66-118, horovod.lds): a narrow, stable
// boundary between the Python layer and the native runtime. Byte payloads
// use the htpu wire format (wire.h), mirrored in horovod_tpu/wire.py.
//
// Memory contract: every function returning a buffer allocates it with
// malloc and the caller releases it with htpu_free().

#include <algorithm>
#include <cstdlib>
#include <cstring>
#include <string>
#include <unordered_map>

#include "htpu/aggregate.h"
#include "htpu/control.h"
#include "htpu/flight_recorder.h"
#include "htpu/integrity.h"
#include "htpu/scheduler.h"
#include "htpu/message_table.h"
#include "htpu/metrics.h"
#include "htpu/policy.h"
#include "htpu/process_set.h"
#include "htpu/quantize.h"
#include "htpu/reduce.h"
#include "htpu/timeline.h"
#include "htpu/wire.h"

namespace {

// Copy a std::string into a malloc'd buffer, returning its length.
int CopyOut(const std::string& s, void** out) {
  void* buf = malloc(s.size());
  if (!buf && !s.empty()) return -1;
  memcpy(buf, s.data(), s.size());
  *out = buf;
  return int(s.size());
}

// Shared serializer for the two stall endpoints: repeated
// { name_len:i32 name:bytes age:f64 n_missing:i32 ranks:i32[n] },
// everything little-endian (mirrored by cpp_core._parse_stall_records).
std::string SerializeStallRecords(const std::vector<htpu::StallInfo>& stalled) {
  std::string buf;
  auto put_i32 = [&buf](int32_t v) {
    for (int i = 0; i < 4; ++i)
      buf.push_back(char((uint32_t(v) >> (8 * i)) & 0xff));
  };
  auto put_f64 = [&buf](double v) {
    uint64_t bits;
    memcpy(&bits, &v, sizeof(bits));
    for (int i = 0; i < 8; ++i)
      buf.push_back(char((bits >> (8 * i)) & 0xff));
  };
  for (const auto& s : stalled) {
    put_i32(int32_t(s.name.size()));
    buf += s.name;
    put_f64(s.age_s);
    put_i32(int32_t(s.missing_ranks.size()));
    for (int r : s.missing_ranks) put_i32(r);
  }
  return buf;
}

}  // namespace

// The library is built -fvisibility=hidden + a version script; only the
// C API below is re-exported.
#define HTPU_API __attribute__((visibility("default")))

extern "C" {

HTPU_API const char* htpu_version() { return "0.1.0"; }

HTPU_API void htpu_free(void* p) { free(p); }

// ------------------------------------------------------------ message table

HTPU_API void* htpu_table_create(int size) {
  return new htpu::MessageTable(size);
}

HTPU_API void htpu_table_destroy(void* t) {
  delete static_cast<htpu::MessageTable*>(t);
}

// Returns 1 when all ranks have reported for this tensor, 0 otherwise,
// -1 on parse error or an out-of-range rank.
HTPU_API int htpu_table_increment(void* t, const void* req_bytes, int len) {
  htpu::Request req;
  size_t pos = 0;
  // Single-message boundary frames always carry the algo field (both
  // serializer and parser agree out of band — no flag byte here).
  if (!htpu::ParseRequest(static_cast<const uint8_t*>(req_bytes), size_t(len),
                          &pos, &req, /*with_algo=*/true) ||
      pos != size_t(len)) {
    return -1;
  }
  try {
    return static_cast<htpu::MessageTable*>(t)->Increment(req) ? 1 : 0;
  } catch (const std::out_of_range&) {
    return -1;
  }
}

// Serialized Response into *out; returns its length (>=0) or -1.
HTPU_API int htpu_table_construct_response(void* t, const char* name, void** out) {
  htpu::Response resp =
      static_cast<htpu::MessageTable*>(t)->ConstructResponse(name);
  std::string buf;
  htpu::SerializeResponse(resp, &buf, /*with_algo=*/true);
  return CopyOut(buf, out);
}

// Topology + crossover inputs for the table's allreduce algorithm
// resolution ("auto" → ring / hier / small per payload size).
HTPU_API void htpu_table_configure_algo(void* t, int num_hosts, int num_procs,
                                        long long crossover_bytes) {
  static_cast<htpu::MessageTable*>(t)->ConfigureAlgoSelection(
      num_hosts, num_procs, crossover_bytes);
}

HTPU_API int htpu_table_num_pending(void* t) {
  return int(static_cast<htpu::MessageTable*>(t)->NumPending());
}

HTPU_API void htpu_table_clear(void* t) {
  static_cast<htpu::MessageTable*>(t)->Clear();
}

// Stalled entries, length-prefixed (names may contain any byte):
// repeated { name_len:i32 name:bytes age:f64 n_missing:i32 ranks:i32[n] }.
HTPU_API int htpu_table_stalled(void* t, double age_s, void** out) {
  auto stalled = static_cast<htpu::MessageTable*>(t)->Stalled(age_s);
  return CopyOut(SerializeStallRecords(stalled), out);
}

// ------------------------------------------------------------------- fusion

// responses: serialized ResponseList. names/bytes/dtypes: parallel arrays
// describing each tensor's payload. Result: serialized ResponseList.
HTPU_API int htpu_plan_fusion(const void* responses_bytes, int len,
                     const char** names, const int64_t* nbytes,
                     const char** dtypes, int n_entries, int64_t threshold,
                     void** out) {
  htpu::ResponseList in;
  if (!htpu::ParseResponseList(static_cast<const uint8_t*>(responses_bytes),
                               size_t(len), &in)) {
    return -1;
  }
  std::unordered_map<std::string, int64_t> size_map;
  std::unordered_map<std::string, std::string> dtype_map;
  for (int i = 0; i < n_entries; ++i) {
    size_map[names[i]] = nbytes[i];
    dtype_map[names[i]] = dtypes[i];
  }
  htpu::ResponseList result;
  result.shutdown = in.shutdown;
  result.responses = htpu::PlanFusion(
      in.responses,
      [&](const std::string& n) {
        auto it = size_map.find(n);
        return it == size_map.end() ? int64_t{0} : it->second;
      },
      [&](const std::string& n) {
        auto it = dtype_map.find(n);
        return it == dtype_map.end() ? std::string() : it->second;
      },
      threshold);
  std::string buf;
  htpu::SerializeResponseList(result, &buf);
  return CopyOut(buf, out);
}

// ----------------------------------------------------------------- timeline

HTPU_API void* htpu_timeline_create(const char* path) {
  auto* tl = new htpu::Timeline(path);
  if (!tl->ok()) {
    delete tl;
    return nullptr;
  }
  return tl;
}

// Rank-tagged variant: the trace opens with a trace_t0 instant carrying
// {rank, t0_wall_us} so tools/trace_merge.py can align per-rank files.
HTPU_API void* htpu_timeline_create_rank(const char* path, int rank) {
  auto* tl = new htpu::Timeline(path, rank);
  if (!tl->ok()) {
    delete tl;
    return nullptr;
  }
  return tl;
}

HTPU_API void htpu_timeline_destroy(void* tl) {
  delete static_cast<htpu::Timeline*>(tl);
}

HTPU_API void htpu_timeline_negotiate_start(void* tl, const char* name, int req_type) {
  static_cast<htpu::Timeline*>(tl)->NegotiateStart(
      name, htpu::RequestType(req_type));
}

HTPU_API void htpu_timeline_negotiate_rank_ready(void* tl, const char* name, int rank) {
  static_cast<htpu::Timeline*>(tl)->NegotiateRankReady(name, rank);
}

HTPU_API void htpu_timeline_negotiate_end(void* tl, const char* name) {
  static_cast<htpu::Timeline*>(tl)->NegotiateEnd(name);
}

HTPU_API void htpu_timeline_start(void* tl, const char* name, int resp_type) {
  static_cast<htpu::Timeline*>(tl)->Start(name, htpu::ResponseType(resp_type));
}

HTPU_API void htpu_timeline_end(void* tl, const char* name) {
  static_cast<htpu::Timeline*>(tl)->End(name);
}

HTPU_API void htpu_timeline_activity_start(void* tl, const char* name,
                                  const char* activity) {
  static_cast<htpu::Timeline*>(tl)->ActivityStart(name, activity);
}

HTPU_API void htpu_timeline_activity_end(void* tl, const char* name) {
  static_cast<htpu::Timeline*>(tl)->ActivityEnd(name);
}

// A whole activity the caller timed itself (the span ring's
// step/dispatch): it lasted dur_us and ended ended_ago_us before now.
HTPU_API void htpu_timeline_activity_span(void* tl, const char* name,
                                 const char* activity, long long dur_us,
                                 long long ended_ago_us) {
  static_cast<htpu::Timeline*>(tl)->ActivitySpan(name, activity, dur_us,
                                                 ended_ago_us);
}

// Chrome-trace counter track sample ("ph": "C") — queue depth, bytes in
// flight — plotted by Perfetto as rate graphs alongside the spans.
HTPU_API void htpu_timeline_counter(void* tl, const char* name,
                                    long long value) {
  static_cast<htpu::Timeline*>(tl)->Counter(name, value);
}

// Complete-event span marking a negotiation tick served entirely from the
// response cache (distinct from NEGOTIATE_* spans in the trace viewer).
HTPU_API void htpu_timeline_cache_hit_tick(void* tl, long long dur_us) {
  static_cast<htpu::Timeline*>(tl)->CacheHitTick(dur_us);
}

// Global instant on the control track; args_json is a caller-built JSON
// object (or NULL/empty for {}).
HTPU_API void htpu_timeline_instant(void* tl, const char* name,
                                    const char* args_json) {
  static_cast<htpu::Timeline*>(tl)->Instant(name ? name : "",
                                            args_json ? args_json : "");
}

// Complete-event TICK span ending now (dur_us long) tagged with the tick
// id — the cross-rank alignment anchor for merged traces.
HTPU_API void htpu_timeline_tick_span(void* tl, unsigned long long tick,
                                      long long dur_us) {
  static_cast<htpu::Timeline*>(tl)->TickSpan(tick, dur_us);
}

HTPU_API void htpu_timeline_flush(void* tl) {
  static_cast<htpu::Timeline*>(tl)->Flush();
}

HTPU_API void htpu_timeline_close(void* tl) {
  static_cast<htpu::Timeline*>(tl)->Close();
}

// ------------------------------------------------- multi-process control

HTPU_API void* htpu_control_create(int process_index, int process_count,
                          const char* coord_host, int coord_port,
                          int first_rank, int nranks_total, int timeout_ms) {
  auto cp = htpu::ControlPlane::Create(process_index, process_count,
                                       coord_host, coord_port, first_rank,
                                       nranks_total, timeout_ms);
  return cp.release();
}

HTPU_API void htpu_control_destroy(void* cp) {
  delete static_cast<htpu::ControlPlane*>(cp);
}

// Elastic membership identity: the four values change together on a
// RECONFIGURE; the Python controller re-reads them after any tick whose
// response carried a reconfigure payload.  Safe from any thread.
HTPU_API void htpu_control_membership(void* cp, int* process_index,
                                      int* process_count, int* first_rank,
                                      int* generation) {
  int32_t pi = 0, pc = 0, fr = 0, gen = 0;
  static_cast<htpu::ControlPlane*>(cp)->Membership(&pi, &pc, &fr, &gen);
  *process_index = pi;
  *process_count = pc;
  *first_rank = fr;
  *generation = gen;
}

// 1 when HOROVOD_TPU_ELASTIC=1 was honoured by this plane (a non-uniform
// rank layout silently falls back to abort-on-failure).
HTPU_API int htpu_control_elastic(void* cp) {
  return static_cast<htpu::ControlPlane*>(cp)->elastic() ? 1 : 0;
}

// Serialized ResponseList into *out; length or -1.
HTPU_API int htpu_control_tick(void* cp, const void* req_blob, int len,
                      long long fusion_threshold, void** out) {
  std::string blob(static_cast<const char*>(req_blob), size_t(len));
  std::string result;
  if (!static_cast<htpu::ControlPlane*>(cp)->Tick(blob, fusion_threshold,
                                                  &result)) {
    return -1;
  }
  return CopyOut(result, out);
}

// Exceptions (e.g. bad_alloc on giant payloads) must not cross the C
// boundary into ctypes; data-plane failures are -1 like any other error.
// One copy total: the input lands straight in the malloc'd output buffer
// and the ring reduces in place (the payload path measured copy-bound at
// multi-MB gradients — docs/benchmarks.md, round-5 eager plane study).
// `wire_dtype` ("", "bf16", "fp16", "int8") selects the compressed wire
// format for fp32 payloads (quantize.h); `algo` ("", "hier", "small") the
// coordinator-resolved collective algorithm (control.h).
HTPU_API int htpu_control_allreduce_algo(void* cp, const char* dtype,
                                const char* wire_dtype, const char* algo,
                                const void* in, long long len,
                                void** out) try {
  char* buf = static_cast<char*>(malloc(len > 0 ? size_t(len) : 1));
  if (!buf) return -1;
  std::memcpy(buf, in, size_t(len));
  bool ok = false;
  try {
    ok = static_cast<htpu::ControlPlane*>(cp)->AllreduceBuf(
        dtype, buf, len, wire_dtype ? wire_dtype : "", algo ? algo : "");
  } catch (...) {
    ok = false;   // e.g. bad_alloc sizing the ring's chunk buffers
  }
  if (!ok) {
    free(buf);
    return -1;
  }
  *out = buf;
  return int(len);
} catch (...) {
  return -1;
}

HTPU_API int htpu_control_allreduce_wire(void* cp, const char* dtype,
                                const char* wire_dtype, const void* in,
                                long long len, void** out) {
  return htpu_control_allreduce_algo(cp, dtype, wire_dtype, "", in, len, out);
}

HTPU_API int htpu_control_allreduce(void* cp, const char* dtype, const void* in,
                           long long len, void** out) {
  return htpu_control_allreduce_wire(cp, dtype, "", in, len, out);
}

HTPU_API int htpu_control_allgather(void* cp, const void* in, long long len,
                           void** out) try {
  std::string contrib(static_cast<const char*>(in), size_t(len));
  std::string result;
  if (!static_cast<htpu::ControlPlane*>(cp)->Allgather(contrib, &result)) {
    return -1;
  }
  return CopyOut(result, out);
} catch (...) {
  return -1;
}

HTPU_API int htpu_control_broadcast(void* cp, int root_process, const void* in,
                           long long len, void** out) try {
  std::string contrib(static_cast<const char*>(in), size_t(len));
  std::string result;
  if (!static_cast<htpu::ControlPlane*>(cp)->Broadcast(root_process, contrib,
                                                       &result)) {
    return -1;
  }
  return CopyOut(result, out);
} catch (...) {
  return -1;
}

// Single-process round trip through the wire codec (quantize.h), framed
// in the same kSubChunkElems sub-chunks the ring uses: encode `n_elems`
// fp32 values, decode them back into `out`.  Returns the wire byte count
// (what the ring would put on the socket per hop for this payload) or -1
// on an unknown wire dtype.  Exists so tests can pin the codec's
// numerics and framing without spawning a 2-process ring.
HTPU_API long long htpu_wire_roundtrip(const char* wire_dtype, const void* in,
                              long long n_elems, void* out) try {
  const int wire = htpu::WireDtypeId(wire_dtype ? wire_dtype : "");
  if (wire < 0 || n_elems < 0) return -1;
  const float* src = static_cast<const float*>(in);
  float* dst = static_cast<float*>(out);
  if (wire == htpu::kWireRaw) {
    std::memcpy(dst, src, size_t(n_elems) * 4);
    return n_elems * 4;
  }
  std::string buf(size_t(htpu::WireChunkBytes(wire, htpu::kSubChunkElems)),
                  '\0');
  long long total = 0;
  for (long long lo = 0; lo < n_elems; lo += htpu::kSubChunkElems) {
    const long long len = std::min<long long>(htpu::kSubChunkElems,
                                              n_elems - lo);
    htpu::EncodeWireChunk(wire, src + lo, len, &buf[0]);
    htpu::DecodeWireChunk(wire, buf.data(), len, dst + lo);
    total += htpu::WireChunkBytes(wire, len);
  }
  return total;
} catch (...) {
  return -1;
}

// Wire bytes a segment of n fp32 elements occupies (WireSegmentBytes
// framing) — lets callers size htpu_wire_encode's output buffer.
HTPU_API long long htpu_wire_bytes(const char* wire_dtype, long long n_elems) {
  const int wire = htpu::WireDtypeId(wire_dtype ? wire_dtype : "");
  if (wire < 0 || n_elems < 0) return -1;
  return htpu::WireSegmentBytes(wire, n_elems);
}

// Encode a segment into its wire image without decoding it back — the
// cross-plane parity hook: the in-jit Pallas/jnp codec must produce this
// byte image bit-for-bit (tests/test_quantized_collectives.py).
HTPU_API long long htpu_wire_encode(const char* wire_dtype, const void* in,
                                    long long n_elems, void* out) try {
  const int wire = htpu::WireDtypeId(wire_dtype ? wire_dtype : "");
  if (wire < 0 || n_elems < 0) return -1;
  const float* src = static_cast<const float*>(in);
  char* dst = static_cast<char*>(out);
  if (wire == htpu::kWireRaw) {
    std::memcpy(dst, src, size_t(n_elems) * 4);
    return n_elems * 4;
  }
  long long total = 0;
  for (long long lo = 0; lo < n_elems; lo += htpu::kSubChunkElems) {
    const long long len = std::min<long long>(htpu::kSubChunkElems,
                                              n_elems - lo);
    htpu::EncodeWireChunk(wire, src + lo, len, dst + total);
    total += htpu::WireChunkBytes(wire, len);
  }
  return total;
} catch (...) {
  return -1;
}

// Decode a wire image produced by htpu_wire_encode (or by any codec with
// the same layout) back to fp32 — the reverse parity direction.
HTPU_API long long htpu_wire_decode(const char* wire_dtype, const void* in,
                                    long long n_elems, void* out) try {
  const int wire = htpu::WireDtypeId(wire_dtype ? wire_dtype : "");
  if (wire < 0 || n_elems < 0) return -1;
  const char* src = static_cast<const char*>(in);
  float* dst = static_cast<float*>(out);
  if (wire == htpu::kWireRaw) {
    std::memcpy(dst, src, size_t(n_elems) * 4);
    return n_elems * 4;
  }
  long long total = 0;
  for (long long lo = 0; lo < n_elems; lo += htpu::kSubChunkElems) {
    const long long len = std::min<long long>(htpu::kSubChunkElems,
                                              n_elems - lo);
    htpu::DecodeWireChunk(wire, src + total, len, dst + lo);
    total += htpu::WireChunkBytes(wire, len);
  }
  return total;
} catch (...) {
  return -1;
}

// Parse a serialized RequestList frame and re-serialize it — the
// py<->cpp framing parity hook (distinct from htpu_wire_encode/decode,
// which cover the PAYLOAD codec): a Python-built frame must survive the
// native parse+serialize byte-for-byte, extensions included
// (tests/test_precision.py drives the FLAG_PRECISION_EXT roundtrip
// through this).  Returns bytes written to `out` (capacity `cap`), or
// -1 on a parse failure / short buffer.
HTPU_API long long htpu_wire_request_list_roundtrip(const void* in,
                                                    long long len, void* out,
                                                    long long cap) try {
  htpu::RequestList list;
  if (len < 0 ||
      !htpu::ParseRequestList(static_cast<const uint8_t*>(in),
                              size_t(len), &list)) {
    return -1;
  }
  std::string blob;
  htpu::SerializeRequestList(list, &blob);
  if ((long long)blob.size() > cap) return -1;
  std::memcpy(out, blob.data(), blob.size());
  return (long long)blob.size();
} catch (...) {
  return -1;
}

// Direct SumInto hook (reduce.h): acc += in elementwise over nbytes of
// `dtype`.  Exists so tests can pin the parallel reduction's bit-exactness
// against the serial path (small slices stay serial; large calls engage
// the worker pool) for every dtype, including bfloat16 which numpy lacks.
HTPU_API int htpu_sum_into(const char* dtype, void* acc, const void* in,
                           long long nbytes) {
  return htpu::SumInto(dtype ? dtype : "", acc, in, nbytes) ? 0 : -1;
}

// Cumulative eager-data-plane payload traffic of this process.
HTPU_API void htpu_control_data_bytes(void* cp, long long* sent, long long* recvd) {
  static_cast<htpu::ControlPlane*>(cp)->DataBytes(sent, recvd);
}

// Ring-next transport: static string "uds" / "tcp" / "none".
HTPU_API const char* htpu_control_ring_transport(void* cp) {
  return static_cast<htpu::ControlPlane*>(cp)->ring_transport();
}

// Zero-copy transports active on the data plane: static string
// "classic" / "shm" / "uring" / "shm+uring".
HTPU_API const char* htpu_control_data_transport(void* cp) {
  return static_cast<htpu::ControlPlane*>(cp)->data_transport();
}

// Attach a native Timeline (htpu_timeline_create) so the coordinator's
// Tick loop emits negotiation spans; pass nullptr to detach.  The caller
// must keep the timeline alive while attached (and detach before
// htpu_timeline_destroy).
HTPU_API void htpu_control_set_timeline(void* cp, void* timeline) {
  if (!cp) return;   // teardown race: plane may be closed under the caller
  static_cast<htpu::ControlPlane*>(cp)->set_timeline(
      static_cast<htpu::Timeline*>(timeline));
}

// Attribution of the most recent failure on this process: writes the
// offending process's first global rank (-1 = nothing failed) into *rank
// and the root-cause string into *out (htpu_free it); returns the string
// length or -1 on allocation failure.
HTPU_API int htpu_control_last_error(void* cp, int* rank, void** out) {
  int32_t r = -1;
  std::string reason;
  static_cast<htpu::ControlPlane*>(cp)->LastError(&r, &reason);
  *rank = int(r);
  return CopyOut(reason, out);
}

// Coordinator-side stall scan; same length-prefixed record format as
// htpu_table_stalled.
HTPU_API int htpu_control_stalled(void* cp, double age_s, void** out) {
  auto stalled = static_cast<htpu::ControlPlane*>(cp)->Stalled(age_s);
  return CopyOut(SerializeStallRecords(stalled), out);
}

// ---------------------------------------------------------- integrity

// CRC32C (Castagnoli) over [data, data+len) — the checksum the integrity
// layer stamps on frames/chunks; exported so the Python mirror
// (horovod_tpu.wire.crc32c) can delegate to the dispatched native path.
HTPU_API unsigned htpu_crc32c(const void* data, long long len) {
  return htpu::Crc32c(data, size_t(len));
}

// Table-driven software path, always taken — the hw/sw parity tests pin
// both implementations against each other through this pair.
HTPU_API unsigned htpu_crc32c_sw(const void* data, long long len) {
  return htpu::Crc32cSoftware(0, data, size_t(len));
}

// 1 when the dispatcher selected the SSE4.2 hardware path on this CPU.
HTPU_API int htpu_crc32c_hw(void) { return htpu::Crc32cHardware() ? 1 : 0; }

// Tensor names of the collective about to run — folded into the
// attributed error when a checked transfer exhausts its retransmit
// budget, so "corruption persisted" names the tensor, not just the peer.
HTPU_API void htpu_control_set_xfer_context(void* cp, const char* tensors) {
  if (!cp) return;
  static_cast<htpu::ControlPlane*>(cp)->SetXferContext(tensors ? tensors
                                                               : "");
}

// ------------------------------------------------------------------ metrics

// JSON snapshot of the process-wide native registry (metrics.h):
// {"counters":{...},"gauges":{...},"histograms":{...}}.  Buffer contract
// as everywhere else: malloc'd, htpu_free to release; returns the length.
HTPU_API int htpu_metrics_snapshot(void** out) {
  return CopyOut(htpu::Metrics::Get().SnapshotJson(), out);
}

// Zero every value (tests/bench isolation); registered metrics survive so
// cached counter pointers inside hot paths stay valid.
HTPU_API void htpu_metrics_reset() { htpu::Metrics::Get().Reset(); }

// ----------------------------------------------------- flight recorder

// Record one event into the process-wide ring (flight_recorder.h).  Lets
// the Python run loop leave breadcrumbs — pending tensor names, op
// timeouts — next to the native control/transport events.
HTPU_API void htpu_flight_record(const char* kind, const char* detail,
                                 long long bytes, int a, int b) {
  htpu::FlightRecorder::Get().Record(kind, detail, bytes, a, b);
}

// Resize the ring to `events` slots (drops recorded history; tests).
HTPU_API void htpu_flight_set_capacity(long long events) {
  htpu::FlightRecorder::Get().SetCapacityEvents(events);
}

HTPU_API void htpu_flight_set_rank(int rank) {
  htpu::FlightRecorder::Get().SetRank(rank);
}

// Dump the ring to the per-rank JSON file; writes the path into *out
// (htpu_free it) and returns its length, 0 when the write failed.
HTPU_API int htpu_flight_dump(const char* why, void** out) {
  return CopyOut(
      htpu::FlightRecorder::Get().Dump(why ? why : "manual"), out);
}

// The ring as a JSON object without touching the filesystem (tests).
HTPU_API int htpu_flight_snapshot(const char* why, void** out) {
  return CopyOut(
      htpu::FlightRecorder::Get().SnapshotJson(why ? why : "snapshot"),
      out);
}

// ---------------------------------------------------------------- scheduler

// Full per-tick policy (fusion + first-ready issue order); same wire
// contract as htpu_plan_fusion, which remains for compatibility.
HTPU_API int htpu_plan_tick(const void* responses_bytes, int len,
                            const char** names, const int64_t* nbytes,
                            const char** dtypes, int n_entries,
                            int64_t threshold, void** out) {
  htpu::ResponseList in;
  if (!htpu::ParseResponseList(static_cast<const uint8_t*>(responses_bytes),
                               size_t(len), &in)) {
    return -1;
  }
  std::unordered_map<std::string, int64_t> size_map;
  std::unordered_map<std::string, std::string> dtype_map;
  for (int i = 0; i < n_entries; ++i) {
    size_map[names[i]] = nbytes[i];
    dtype_map[names[i]] = dtypes[i];
  }
  htpu::ResponseList result;
  result.shutdown = in.shutdown;
  result.responses = htpu::PlanTick(
      in.responses,
      [&](const std::string& n) {
        auto it = size_map.find(n);
        return it == size_map.end() ? int64_t{0} : it->second;
      },
      [&](const std::string& n) {
        auto it = dtype_map.find(n);
        return it == dtype_map.end() ? std::string() : it->second;
      },
      threshold);
  std::string buf;
  htpu::SerializeResponseList(result, &buf);
  return CopyOut(buf, out);
}

// Algorithm selection for a payload; writes the resolved algo name into
// *out (htpu_free it) and returns its length ("" = flat ring).
HTPU_API int htpu_resolve_algo(const char* pref, int64_t nbytes,
                               int num_hosts, int num_procs,
                               int64_t crossover_bytes, void** out) {
  return CopyOut(htpu::ResolveAlgo(pref ? pref : "", nbytes, num_hosts,
                                   num_procs, crossover_bytes),
                 out);
}

HTPU_API void* htpu_sched_create(int64_t bucket_bytes) {
  return new htpu::BucketPlanner(bucket_bytes);
}

HTPU_API void htpu_sched_destroy(void* sched) {
  delete static_cast<htpu::BucketPlanner*>(sched);
}

HTPU_API int htpu_sched_register(void* sched, const char* name,
                                 int64_t nbytes, const char* dtype) {
  return static_cast<htpu::BucketPlanner*>(sched)->RegisterLeaf(
      name ? name : "", nbytes, dtype ? dtype : "");
}

HTPU_API int htpu_sched_seal(void* sched) {
  return static_cast<htpu::BucketPlanner*>(sched)->Seal();
}

HTPU_API int htpu_sched_bucket_of(void* sched, int leaf) {
  return static_cast<htpu::BucketPlanner*>(sched)->BucketOf(leaf);
}

HTPU_API int64_t htpu_sched_bucket_bytes(void* sched, int bucket) {
  return static_cast<htpu::BucketPlanner*>(sched)->BucketBytes(bucket);
}

HTPU_API int htpu_sched_note_ready(void* sched, int leaf) {
  return static_cast<htpu::BucketPlanner*>(sched)->NoteReady(leaf);
}

HTPU_API int htpu_sched_next_issue(void* sched) {
  return static_cast<htpu::BucketPlanner*>(sched)->NextIssue();
}

HTPU_API void htpu_sched_note_complete(void* sched, int bucket) {
  static_cast<htpu::BucketPlanner*>(sched)->NoteComplete(bucket);
}

HTPU_API int htpu_sched_all_complete(void* sched) {
  return static_cast<htpu::BucketPlanner*>(sched)->AllComplete() ? 1 : 0;
}

HTPU_API void htpu_sched_reset(void* sched) {
  static_cast<htpu::BucketPlanner*>(sched)->Reset();
}

// ------------------------------------------------------------ fleet policy

// Standalone handle over htpu::FleetPolicy (policy.h) so the Python
// mirror (horovod_tpu/policy.py) can defer decisions to the native
// engine and the parity tests can replay identical wait streams through
// both.  The knobs are read from the environment at create time, same
// as the coordinator's embedded instance.

HTPU_API void* htpu_policy_create(void) { return new htpu::FleetPolicy(); }

HTPU_API void htpu_policy_destroy(void* policy) {
  delete static_cast<htpu::FleetPolicy*>(policy);
}

HTPU_API int htpu_policy_active(void* policy) {
  return static_cast<htpu::FleetPolicy*>(policy)->active() ? 1 : 0;
}

HTPU_API void htpu_policy_observe(void* policy, int64_t tick,
                                  const double* wait_s, int n) {
  std::vector<double> w(wait_s, wait_s + (n > 0 ? n : 0));
  static_cast<htpu::FleetPolicy*>(policy)->ObserveTick(uint64_t(tick), w);
}

HTPU_API int htpu_policy_next_eviction(void* policy, int process_count,
                                       int seat_available) {
  return static_cast<htpu::FleetPolicy*>(policy)->NextEviction(
      process_count, seat_available != 0);
}

// Writes the reordered process indices over `pidx` in place (n entries).
HTPU_API void htpu_policy_rerank(void* policy, int* pidx, int n) {
  std::vector<int> in(pidx, pidx + (n > 0 ? n : 0));
  std::vector<int> out =
      static_cast<htpu::FleetPolicy*>(policy)->RerankOrder(in);
  for (size_t i = 0; i < out.size(); ++i) pidx[i] = out[i];
}

HTPU_API int htpu_policy_autoscale_target(void* policy, int64_t tick) {
  return static_cast<htpu::FleetPolicy*>(policy)->AutoscaleTarget(
      uint64_t(tick));
}

HTPU_API double htpu_policy_ewma(void* policy, int proc) {
  return static_cast<htpu::FleetPolicy*>(policy)->ewma(proc);
}

HTPU_API int htpu_policy_consecutive_slow(void* policy, int proc) {
  return static_cast<htpu::FleetPolicy*>(policy)->consecutive_slow(proc);
}

// Per-set straggler state (policy.h): the same wait streams bucketed by
// process set, so one tenant's slowness never nominates a rank for
// eviction from another's.  The unsuffixed endpoints above read set 0.

HTPU_API void htpu_policy_observe_set(void* policy, int set,
                                      const double* wait_s, int n) {
  std::vector<double> w(wait_s, wait_s + (n > 0 ? n : 0));
  static_cast<htpu::FleetPolicy*>(policy)->ObserveTickSet(set, w);
}

HTPU_API double htpu_policy_ewma_set(void* policy, int set, int proc) {
  return static_cast<htpu::FleetPolicy*>(policy)->ewma_set(set, proc);
}

HTPU_API int htpu_policy_consecutive_slow_set(void* policy, int set,
                                              int proc) {
  return static_cast<htpu::FleetPolicy*>(policy)->consecutive_slow_set(set,
                                                                       proc);
}

HTPU_API int htpu_policy_next_eviction_set(void* policy, int set,
                                           int process_count,
                                           int seat_available) {
  return static_cast<htpu::FleetPolicy*>(policy)->NextEvictionSet(
      set, process_count, seat_available != 0);
}

// Precision controller (policy.h): the per-bucket wire-dtype ladder —
// the third actuator on the same engine, exposed for the Python mirror
// and the native-parity trace in tests/test_precision.py.

HTPU_API int htpu_policy_precision_auto(void* policy) {
  return static_cast<htpu::FleetPolicy*>(policy)->precision_auto() ? 1 : 0;
}

HTPU_API void htpu_policy_precision_observe(void* policy, const char* name,
                                            double residual_norm) {
  static_cast<htpu::FleetPolicy*>(policy)->ObservePrecision(
      name ? name : "", residual_norm);
}

HTPU_API void htpu_policy_precision_bandwidth(void* policy,
                                              double min_leg_bps) {
  static_cast<htpu::FleetPolicy*>(policy)->NotePrecisionBandwidth(
      min_leg_bps);
}

HTPU_API int htpu_policy_precision_level(void* policy, const char* name) {
  return static_cast<htpu::FleetPolicy*>(policy)->PrecisionLevel(
      name ? name : "");
}

HTPU_API double htpu_policy_precision_ewma(void* policy, const char* name) {
  return static_cast<htpu::FleetPolicy*>(policy)->PrecisionEwma(
      name ? name : "");
}

// counts[0] = promotions, counts[1] = demotions (lifetime).
HTPU_API void htpu_policy_precision_counts(void* policy, long long* counts) {
  auto* p = static_cast<htpu::FleetPolicy*>(policy);
  counts[0] = p->precision_promotions();
  counts[1] = p->precision_demotions();
}

HTPU_API int htpu_policy_precision_dirty(void* policy) {
  return static_cast<htpu::FleetPolicy*>(policy)->TakePrecisionDirty() ? 1
                                                                       : 0;
}

// ------------------------------------------------------------- process sets

// Standalone handle over htpu::ProcessSetTable (process_set.h) for the
// Python mirror and the parity tests.  The coordinator's embedded
// instance (HOROVOD_TPU_PROCESS_SETS) is driven through htpu_control_tick
// via set-tagged request frames, not through these endpoints.

HTPU_API void* htpu_process_sets_create(long long cache_capacity) {
  return new htpu::ProcessSetTable(cache_capacity);
}

HTPU_API void htpu_process_sets_destroy(void* ps) {
  delete static_cast<htpu::ProcessSetTable*>(ps);
}

// 1 on success, 0 on a malformed spec (earlier sets stay registered).
HTPU_API int htpu_process_sets_parse_spec(void* ps, const char* spec) {
  return static_cast<htpu::ProcessSetTable*>(ps)->ParseSpec(spec ? spec : "")
             ? 1
             : 0;
}

// New set id (>= 1), or -1 on invalid input.
HTPU_API int htpu_process_sets_add(void* ps, const char* name,
                                   const int* ranks, int n) {
  std::vector<int32_t> r(ranks, ranks + (n > 0 ? n : 0));
  return static_cast<htpu::ProcessSetTable*>(ps)->Add(name ? name : "", r);
}

HTPU_API int htpu_process_sets_remove(void* ps, int id) {
  return static_cast<htpu::ProcessSetTable*>(ps)->Remove(id) ? 1 : 0;
}

HTPU_API int htpu_process_sets_id_of(void* ps, const char* name) {
  return static_cast<htpu::ProcessSetTable*>(ps)->IdOf(name ? name : "");
}

HTPU_API int htpu_process_sets_count(void* ps) {
  return static_cast<htpu::ProcessSetTable*>(ps)->Count();
}

HTPU_API int htpu_process_sets_size(void* ps, int id) {
  return static_cast<htpu::ProcessSetTable*>(ps)->SizeOf(id);
}

HTPU_API int htpu_process_sets_local_rank(void* ps, int id, int global_rank) {
  return static_cast<htpu::ProcessSetTable*>(ps)->LocalRank(id, global_rank);
}

HTPU_API int htpu_process_sets_generation(void* ps, int id) {
  return static_cast<htpu::ProcessSetTable*>(ps)->Generation(id);
}

// Per-set elastic shrink: new generation, or -1 on unknown set/rank.
HTPU_API int htpu_process_sets_reconfigure(void* ps, int id,
                                           int lost_global_rank) {
  return static_cast<htpu::ProcessSetTable*>(ps)->Reconfigure(
      id, lost_global_rank);
}

// 1 = set ready to construct, 0 = waiting, -1 = parse error, unknown set,
// or set-local rank out of range.  Same single-message boundary format as
// htpu_table_increment (always with_algo; the set id is the explicit arg,
// never re-read from the frame).
HTPU_API int htpu_process_sets_increment(void* ps, int id,
                                         const void* req_bytes, int len) {
  htpu::Request req;
  size_t pos = 0;
  if (!htpu::ParseRequest(static_cast<const uint8_t*>(req_bytes), size_t(len),
                          &pos, &req, /*with_algo=*/true) ||
      pos != size_t(len)) {
    return -1;
  }
  req.process_set = id;
  return static_cast<htpu::ProcessSetTable*>(ps)->Increment(id, req);
}

// Serialized Response into *out; returns its length (>=0) or -1.
HTPU_API int htpu_process_sets_construct(void* ps, int id, const char* name,
                                         void** out) {
  htpu::Response resp;
  if (!static_cast<htpu::ProcessSetTable*>(ps)->Construct(id, name, &resp)) {
    return -1;
  }
  std::string buf;
  htpu::SerializeResponse(resp, &buf, /*with_algo=*/true);
  return CopyOut(buf, out);
}

// ------------------------------------------------- fleet observatory

// HOROVOD_TPU_OBSERVE state: 1 armed, 0 off.  Runtime-toggleable (the
// bench A/B measures both states in one process).
HTPU_API int htpu_observe_enabled(void) {
  return htpu::ObserveEnabled() ? 1 : 0;
}

HTPU_API void htpu_observe_set_enabled(int on) {
  htpu::ObserveSetEnabled(on != 0);
}

// One training step's decomposition from the Python layer (seconds).
HTPU_API void htpu_observe_note_step(double step_s, double compute_s,
                                     double hidden_s, double exposed_s,
                                     double stall_s) {
  htpu::NoteStep(step_s, compute_s, hidden_s, exposed_s, stall_s);
}

// Test seam: record one completed transfer on leg 0..3 (classic, shm,
// uring, ctrl) without driving a real job.
HTPU_API void htpu_observe_record_xfer(int leg, long long sent_bytes,
                                       long long recv_bytes,
                                       double seconds) {
  if (leg < 0 || leg > 3) return;
  htpu::RecordXfer(htpu::Leg(leg), size_t(sent_bytes < 0 ? 0 : sent_bytes),
                   size_t(recv_bytes < 0 ? 0 : recv_bytes), seconds);
}

// Compact local telemetry digest as JSON into *out; returns its length.
HTPU_API int htpu_observe_snapshot(void** out) {
  return CopyOut(htpu::ObserveSnapshotJson(), out);
}

HTPU_API void htpu_observe_reset(void) { htpu::ObserveReset(); }

// The telemetry trailer this process would append to its next tick
// frame: kObserveTrailerBytes when the observatory is armed, 0 bytes
// when it is off (the golden-frame contract — nothing is appended).
HTPU_API int htpu_observe_trailer_encode(void** out) {
  std::string t;
  if (htpu::ObserveEnabled()) htpu::AppendObserveTrailer(&t);
  return CopyOut(t, out);
}

// Probe `len` bytes the way the coordinator does: strip a telemetry
// trailer if one is present.  JSON {"stripped":bool,"payload_len":N,
// "sample":{...}} into *out; returns its length.  A frame from an
// observe-off peer reports stripped=false with the payload untouched.
HTPU_API int htpu_observe_trailer_probe(const void* buf, int len,
                                        void** out) {
  std::string blob(static_cast<const char*>(buf), size_t(len < 0 ? 0 : len));
  htpu::ObserveSample s;
  const bool stripped = htpu::StripObserveTrailer(&blob, &s);
  char js[512];
  snprintf(js, sizeof(js),
           "{\"stripped\":%s,\"payload_len\":%zu,\"sample\":{"
           "\"step_s\":%.9g,\"compute_s\":%.9g,\"exposed_s\":%.9g,"
           "\"stall_s\":%.9g,\"steps\":%u,\"bw_bps\":[%.9g,%.9g,%.9g,"
           "%.9g]}}",
           stripped ? "true" : "false", blob.size(), double(s.step_s),
           double(s.compute_s), double(s.exposed_s), double(s.stall_s),
           s.steps, double(s.bw_bps[0]), double(s.bw_bps[1]),
           double(s.bw_bps[2]), double(s.bw_bps[3]));
  return CopyOut(std::string(js), out);
}

// ---- aggregation tier (hierarchical control topology) ----------------
//
// Native seam for the Python mirror (horovod_tpu/aggregate.py): the
// parity tests drive the SAME merge through both implementations and
// pin the bytes equal.

// Fold container `b` into container `a` (both serialized AggFrames) and
// write the canonical merged container into *out; returns its length,
// or -1 if either input fails to parse.
HTPU_API int htpu_agg_merge(const void* a, int a_len, const void* b,
                            int b_len, void** out) {
  htpu::AggFrame acc;
  if (!htpu::ParseAggFrame(static_cast<const uint8_t*>(a),
                           size_t(a_len < 0 ? 0 : a_len), &acc)) {
    return -1;
  }
  htpu::AggFrame in;
  if (!htpu::ParseAggFrame(static_cast<const uint8_t*>(b),
                           size_t(b_len < 0 ? 0 : b_len), &in)) {
    return -1;
  }
  htpu::AggregateRequests(in, &acc);
  std::string buf;
  htpu::SerializeAggFrame(acc, &buf);
  return CopyOut(buf, out);
}

// Parse + re-serialize one container: the canonicalization round-trip
// (members sorted, duplicates merged, template re-elected).  Returns the
// canonical length into *out, or -1 on a corrupt container — the seam
// the property tests use to pin Python serialization byte-equal to
// native.
HTPU_API int htpu_agg_roundtrip(const void* buf, int len, void** out) {
  htpu::AggFrame f;
  if (!htpu::ParseAggFrame(static_cast<const uint8_t*>(buf),
                           size_t(len < 0 ? 0 : len), &f)) {
    return -1;
  }
  std::string s;
  htpu::SerializeAggFrame(f, &s);
  return CopyOut(s, out);
}

}  // extern "C"
