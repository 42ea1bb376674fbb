#include "htpu/timeline.h"

#include <sstream>

#include "htpu/flight_recorder.h"  // WallClockUs

namespace htpu {

namespace {

constexpr double kFlushEverySeconds = 1.0;  // reference timeline.h:32

// Minimal JSON string escaping for tensor names.
std::string JsonEscape(const std::string& s) {
  std::string out;
  out.reserve(s.size() + 2);
  for (char c : s) {
    switch (c) {
      case '"': out += "\\\""; break;
      case '\\': out += "\\\\"; break;
      case '\n': out += "\\n"; break;
      case '\t': out += "\\t"; break;
      case '\r': out += "\\r"; break;
      default:
        if (static_cast<unsigned char>(c) < 0x20) {
          char buf[8];
          snprintf(buf, sizeof(buf), "\\u%04x", c);
          out += buf;
        } else {
          out += c;
        }
    }
  }
  return out;
}

const char* ResponseTypeTraceName(ResponseType t) {
  switch (t) {
    case ResponseType::ALLREDUCE: return "ALLREDUCE";
    case ResponseType::ALLGATHER: return "ALLGATHER";
    case ResponseType::BROADCAST: return "BROADCAST";
    case ResponseType::ERROR: return "ERROR";
  }
  return "UNKNOWN";
}

}  // namespace

Timeline::Timeline(const std::string& path, int rank) {
  file_ = fopen(path.c_str(), "w");
  if (file_) fputs("[", file_);
  t0_ = std::chrono::steady_clock::now();
  last_flush_ = t0_;
  // Absolute anchor: ts 0 of this trace corresponds to t0_wall_us on
  // this process's wall clock.  trace_merge.py keys per-rank alignment
  // off this event.
  std::ostringstream os;
  os << "{\"name\": \"trace_t0\", \"ph\": \"i\", \"s\": \"g\", \"pid\": 0, "
     << "\"ts\": 0, \"args\": {\"rank\": " << rank << ", \"t0_wall_us\": "
     << WallClockUs() << "}}";
  Emit(os.str());
}

Timeline::~Timeline() { Close(); }

int64_t Timeline::TsUs() const {
  return std::chrono::duration_cast<std::chrono::microseconds>(
             std::chrono::steady_clock::now() - t0_)
      .count();
}

void Timeline::Emit(const std::string& json_line) {
  std::lock_guard<std::mutex> l(mu_);
  if (closed_ || !file_) return;
  fputs(first_event_ ? "\n" : ",\n", file_);
  first_event_ = false;
  fputs(json_line.c_str(), file_);
  auto now = std::chrono::steady_clock::now();
  if (std::chrono::duration<double>(now - last_flush_).count() >
      kFlushEverySeconds) {
    fflush(file_);
    last_flush_ = now;
  }
}

int Timeline::Pid(const std::string& tensor_name) {
  int pid;
  bool created = false;
  {
    std::lock_guard<std::mutex> l(mu_);
    auto it = tensor_pids_.find(tensor_name);
    if (it == tensor_pids_.end()) {
      pid = next_pid_++;
      tensor_pids_.emplace(tensor_name, pid);
      created = true;
    } else {
      pid = it->second;
    }
  }
  if (created) {
    // Metadata event registering the tensor as a trace process
    // (reference timeline.cc:51-68).
    std::ostringstream os;
    os << "{\"name\": \"process_name\", \"ph\": \"M\", \"pid\": " << pid
       << ", \"args\": {\"name\": \"" << JsonEscape(tensor_name) << "\"}}";
    Emit(os.str());
    std::ostringstream os2;
    os2 << "{\"name\": \"process_sort_index\", \"ph\": \"M\", \"pid\": " << pid
        << ", \"args\": {\"sort_index\": " << pid << "}}";
    Emit(os2.str());
  }
  return pid;
}

void Timeline::NegotiateStart(const std::string& tensor_name,
                              RequestType type) {
  std::ostringstream os;
  os << "{\"ph\": \"B\", \"pid\": " << Pid(tensor_name)
     << ", \"ts\": " << TsUs() << ", \"name\": \"NEGOTIATE_"
     << RequestTypeName(type) << "\"}";
  Emit(os.str());
}

void Timeline::NegotiateRankReady(const std::string& tensor_name, int rank) {
  std::ostringstream os;
  os << "{\"ph\": \"i\", \"pid\": " << Pid(tensor_name)
     << ", \"ts\": " << TsUs() << ", \"s\": \"p\", \"name\": \"" << rank
     << "\"}";
  Emit(os.str());
}

void Timeline::NegotiateEnd(const std::string& tensor_name) {
  std::ostringstream os;
  os << "{\"ph\": \"E\", \"pid\": " << Pid(tensor_name)
     << ", \"ts\": " << TsUs() << "}";
  Emit(os.str());
}

void Timeline::Start(const std::string& tensor_name, ResponseType type) {
  std::ostringstream os;
  os << "{\"ph\": \"B\", \"pid\": " << Pid(tensor_name)
     << ", \"ts\": " << TsUs() << ", \"name\": \""
     << ResponseTypeTraceName(type) << "\"}";
  Emit(os.str());
}

void Timeline::End(const std::string& tensor_name) { NegotiateEnd(tensor_name); }

void Timeline::ActivityStart(const std::string& tensor_name,
                             const std::string& activity) {
  std::ostringstream os;
  os << "{\"ph\": \"B\", \"pid\": " << Pid(tensor_name)
     << ", \"ts\": " << TsUs() << ", \"name\": \"" << JsonEscape(activity)
     << "\"}";
  Emit(os.str());
}

void Timeline::ActivityEnd(const std::string& tensor_name) {
  NegotiateEnd(tensor_name);
}

void Timeline::ActivitySpan(const std::string& tensor_name,
                            const std::string& activity, int64_t dur_us,
                            int64_t ended_ago_us) {
  if (dur_us < 0) dur_us = 0;
  std::ostringstream os;
  os << "{\"ph\": \"X\", \"pid\": " << Pid(tensor_name)
     << ", \"ts\": " << TsUs() - ended_ago_us - dur_us
     << ", \"dur\": " << dur_us << ", \"name\": \""
     << JsonEscape(activity) << "\"}";
  Emit(os.str());
}

void Timeline::CacheHitTick(int64_t dur_us) {
  std::ostringstream os;
  os << "{\"ph\": \"X\", \"pid\": 0, \"ts\": " << TsUs() - dur_us
     << ", \"dur\": " << dur_us << ", \"name\": \"CACHED_TICK\"}";
  Emit(os.str());
}

void Timeline::TickSpan(uint64_t tick, int64_t dur_us) {
  if (dur_us < 0) dur_us = 0;
  std::ostringstream os;
  os << "{\"ph\": \"X\", \"pid\": 0, \"ts\": " << TsUs() - dur_us
     << ", \"dur\": " << dur_us << ", \"name\": \"TICK\", \"args\": "
     << "{\"tick\": " << tick << "}}";
  Emit(os.str());
}

void Timeline::Instant(const std::string& name,
                       const std::string& args_json) {
  std::ostringstream os;
  os << "{\"name\": \"" << JsonEscape(name)
     << "\", \"ph\": \"i\", \"s\": \"g\", \"pid\": 0, \"ts\": " << TsUs()
     << ", \"args\": " << (args_json.empty() ? "{}" : args_json) << "}";
  Emit(os.str());
}

void Timeline::ClockOffset(int rank, double offset_us,
                           double uncertainty_us) {
  std::ostringstream os;
  os << "{\"rank\": " << rank << ", \"offset_us\": " << offset_us
     << ", \"uncertainty_us\": " << uncertainty_us << "}";
  Instant("clock_offset", os.str());
}

void Timeline::Counter(const std::string& name, int64_t value) {
  std::ostringstream os;
  os << "{\"ph\": \"C\", \"pid\": 0, \"ts\": " << TsUs() << ", \"name\": \""
     << JsonEscape(name) << "\", \"args\": {\"value\": " << value << "}}";
  Emit(os.str());
}

void Timeline::Flush() {
  std::lock_guard<std::mutex> l(mu_);
  if (!closed_ && file_) fflush(file_);
}

void Timeline::Close() {
  std::lock_guard<std::mutex> l(mu_);
  if (!closed_ && file_) {
    fputs("\n]\n", file_);
    fclose(file_);
    file_ = nullptr;
    closed_ = true;
  }
}

}  // namespace htpu
