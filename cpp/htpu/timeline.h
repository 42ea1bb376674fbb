// Chrome-tracing timeline writer.
//
// Native equivalent of the reference's Timeline
// (horovod/common/timeline.{h,cc}): each named tensor is a trace "process"
// (metadata event), with spans for negotiation (begin/instant-per-rank/end),
// the top-level operation, and nested activities. Output format matches the
// Python fallback in horovod_tpu/timeline.py byte-for-byte in structure so
// either can be loaded in chrome://tracing / Perfetto.
#ifndef HTPU_TIMELINE_H_
#define HTPU_TIMELINE_H_

#include <chrono>
#include <cstdio>
#include <mutex>
#include <string>
#include <unordered_map>

#include "htpu/wire.h"

namespace htpu {

class Timeline {
 public:
  // `rank` tags the trace with the recording rank: every trace opens
  // with a "trace_t0" instant carrying {rank, t0_wall_us} so
  // tools/trace_merge.py can map each file to its rank and anchor the
  // monotonic timestamps to wall clock.
  explicit Timeline(const std::string& path, int rank = 0);
  ~Timeline();

  bool ok() const { return file_ != nullptr; }

  void NegotiateStart(const std::string& tensor_name, RequestType type);
  void NegotiateRankReady(const std::string& tensor_name, int rank);
  void NegotiateEnd(const std::string& tensor_name);
  void Start(const std::string& tensor_name, ResponseType type);
  void End(const std::string& tensor_name);
  void ActivityStart(const std::string& tensor_name,
                     const std::string& activity);
  void ActivityEnd(const std::string& tensor_name);
  // Complete-event span ("ph": "X") on the tensor's lane for an activity
  // the caller timed itself: `dur_us` long, ended `ended_ago_us` ago.
  void ActivitySpan(const std::string& tensor_name,
                    const std::string& activity, int64_t dur_us,
                    int64_t ended_ago_us);
  // Chrome-trace counter track ("ph": "C") — plotted by Perfetto as a
  // rate graph alongside the spans (queue depth, bytes in flight).
  void Counter(const std::string& name, int64_t value);
  // Complete-event span ("ph": "X") on the control track marking a
  // negotiation tick served entirely from the response cache: visually
  // distinct from NEGOTIATE_* spans, dur = full Tick latency.
  void CacheHitTick(int64_t dur_us);
  // Complete-event span on the control track covering one negotiation
  // tick (worker: request send -> response received; coordinator:
  // gather start -> broadcast done).  Emitted on EVERY rank so merged
  // traces line the tick stream up across processes by args.tick.
  void TickSpan(uint64_t tick, int64_t dur_us);
  // Global instant on the control track with a raw JSON args object
  // (caller-built, e.g. "{\"rank\": 1, \"offset_us\": 12.5}").
  void Instant(const std::string& name, const std::string& args_json);
  // Coordinator clock-sync metadata: the estimated wall-clock offset of
  // `rank` relative to this process (positive = rank's clock is ahead).
  void ClockOffset(int rank, double offset_us, double uncertainty_us);
  void Flush();
  void Close();

 private:
  int64_t TsUs() const;
  int Pid(const std::string& tensor_name);  // registers metadata on first use
  void Emit(const std::string& json_line);

  FILE* file_ = nullptr;
  std::mutex mu_;
  std::chrono::steady_clock::time_point t0_;
  std::chrono::steady_clock::time_point last_flush_;
  std::unordered_map<std::string, int> tensor_pids_;
  int next_pid_ = 1;
  bool closed_ = false;
  bool first_event_ = true;   // comma bookkeeping: ",\n" BEFORE each
                              // event after the first, so a killed
                              // process leaves a trace missing only the
                              // final "]" (trivially repairable) while
                              // Close() writes strictly valid JSON.
};

}  // namespace htpu

#endif  // HTPU_TIMELINE_H_
