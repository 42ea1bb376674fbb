"""Roofline attribution of a captured step trace: per-op time, FLOP/s
and bytes/s against the published peaks of the chip that ran it, grouped
by (name-stem, source).
Usage: python tools/roofline.py TRACE_DIR DEVICE_KIND
  e.g. python tools/roofline.py chiprun_out/stepprof "TPU v5 lite"
(The trace does not record the device kind; the script that captured it
prints it.)"""
import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
import sys

from horovod_tpu import profiling

log_dir, device_kind = sys.argv[1], sys.argv[2]
rows = profiling.per_op_rooflines(log_dir, profiling.device_peaks(device_kind))
print(f"device module span: {profiling.device_time_ms(log_dir)} ms; "
      f"XLA-op time: {sum(r['ms'] for r in rows):.2f} ms")
profiling.print_rooflines(rows, top=35)
