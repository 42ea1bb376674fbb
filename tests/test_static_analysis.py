"""tools/analyze: the cross-language contract checkers (PR: static
analysis).

Two halves: the shipped tree must be clean (the checkers run here as
tier-1 gates), and each checker must actually fail on a planted defect
— an undocumented knob, a mismatched ctypes signature, a renamed
metric, and a printf on the SIGUSR2 dump path.  The fixtures are
minimal trees in tmp_path, not copies of the repo, so they stay fast
and pin down exactly what each checker keys on.
"""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         os.pardir))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tools.analyze import contract, knobs, metric_names, signal_safety  # noqa: E402
from tools.analyze.__main__ import run_all  # noqa: E402

import pathlib  # noqa: E402

ROOT = pathlib.Path(REPO_ROOT)


def _write(root, rel, text):
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)


# ---------------------------------------------------------------------------
# The shipped tree is clean and the counts match the hand-audited
# contract surface.
# ---------------------------------------------------------------------------

class TestShippedTree:
    def test_all_checkers_clean(self):
        findings, stats = run_all(ROOT, native=True)
        native_unavailable = [f for f in findings
                             if "native library unavailable" in f.message]
        if native_unavailable and len(findings) == len(native_unavailable):
            pytest.skip("no native toolchain; dynamic contract check "
                        "covered elsewhere")
        assert not findings, "\n".join(str(f) for f in findings)
        # The audited contract surface; update these alongside a
        # deliberate knob/symbol addition.
        assert stats["knobs_total"] == 69
        assert stats["symbols_total"] == 117

    def test_every_knob_has_a_read_site_count(self):
        _, stats = knobs.check(ROOT)
        assert stats["knobs_cpp"] >= 8
        assert stats["knobs_python"] >= 30

    def test_signal_walk_covers_the_dump_helpers(self):
        findings, stats = signal_safety.check(ROOT)
        assert not findings, "\n".join(str(f) for f in findings)
        walked = stats["signal_functions_walked"]
        assert "SignalDump" in walked and "Sigusr2Handler" in walked
        assert "FormatEvent" in walked  # helpers re-walked, not trusted

    def test_cli_json_ok(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.analyze", "--json",
             "--no-native"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert report["ok"] is True
        assert report["findings"] == []
        assert report["stats"]["symbols_total"] == 117


# ---------------------------------------------------------------------------
# Planted defects: each checker must go red on its fixture.
# ---------------------------------------------------------------------------

class TestPlantedKnob:
    def test_undocumented_knob_fails(self, tmp_path):
        _write(tmp_path, "horovod_tpu/foo.py",
               'import os\n'
               'X = os.environ.get("HOROVOD_TPU_PLANTED_KNOB", "1")\n')
        _write(tmp_path, "docs/running.md",
               "| Variable | Default | Effect |\n|---|---|---|\n"
               "| `HOROVOD_TPU_OTHER` | `0` | something else |\n")
        findings, _ = knobs.check(tmp_path)
        msgs = [f.message for f in findings if f.checker == "knobs"]
        assert any("HOROVOD_TPU_PLANTED_KNOB" in m and "not documented" in m
                   for m in msgs), msgs
        # The stale docs row is the dual failure mode.
        assert any("HOROVOD_TPU_OTHER" in m and "nothing reads" in m
                   for m in msgs), msgs

    def test_divergent_default_fails(self, tmp_path):
        _write(tmp_path, "horovod_tpu/foo.py",
               'import os\n'
               'X = os.environ.get("HOROVOD_TPU_PLANTED_KNOB", "64")\n')
        _write(tmp_path, "docs/running.md",
               "| Variable | Default | Effect |\n|---|---|---|\n"
               "| `HOROVOD_TPU_PLANTED_KNOB` | `128` | planted |\n")
        findings, _ = knobs.check(tmp_path)
        assert any("default diverges" in f.message for f in findings), \
            [str(f) for f in findings]


class TestPlantedContract:
    def _tree(self, tmp_path, binding):
        _write(tmp_path, "cpp/htpu/c_api.cc",
               '#define HTPU_API extern "C"\n'
               "HTPU_API int htpu_planted(void* h, int n);\n")
        _write(tmp_path, "cpp/htpu.lds",
               "{ global: htpu_*; local: *; };\n")
        _write(tmp_path, "horovod_tpu/cpp_core.py",
               "import ctypes\n" + binding)

    def test_mismatched_signature_fails(self, tmp_path):
        # Native (void*, int) bound as (c_void_p, c_double): wrong width.
        self._tree(tmp_path,
                   "lib.htpu_planted.argtypes = "
                   "[ctypes.c_void_p, ctypes.c_double]\n")
        findings, _ = contract.check(tmp_path, native=False)
        assert any("argument 1 is c_double" in f.message
                   for f in findings), [str(f) for f in findings]

    def test_arity_mismatch_fails(self, tmp_path):
        self._tree(tmp_path,
                   "lib.htpu_planted.argtypes = [ctypes.c_void_p]\n")
        findings, _ = contract.check(tmp_path, native=False)
        assert any("arity 1 != native arity 2" in f.message
                   for f in findings), [str(f) for f in findings]

    def test_unbound_and_stale_symbols_fail(self, tmp_path):
        self._tree(tmp_path,
                   "lib.htpu_gone.argtypes = [ctypes.c_void_p]\n")
        findings, _ = contract.check(tmp_path, native=False)
        msgs = [f.message for f in findings]
        assert any("htpu_planted" in m and "no ctypes binding" in m
                   for m in msgs), msgs
        assert any("htpu_gone" in m and "stale binding" in m
                   for m in msgs), msgs


class TestPlantedMetric:
    def test_renamed_consumer_reference_fails(self, tmp_path):
        _write(tmp_path, "cpp/htpu/control.cc",
               'void f() {\n'
               '  Metrics::Get().Counter("ring.allreduce.bytes_sent")\n'
               '      ->fetch_add(1);\n'
               '}\n')
        _write(tmp_path, "tools/metrics_watch.py",
               'x = snap.get("ring.allreduce.bytes_total")\n')
        findings, _ = metric_names.check(tmp_path)
        assert any("ring.allreduce.bytes_total" in f.message
                   and "no emitter" in f.message for f in findings), \
            [str(f) for f in findings]

    def test_matching_reference_passes(self, tmp_path):
        _write(tmp_path, "cpp/htpu/control.cc",
               'void f() {\n'
               '  Metrics::Get().Counter("ring.allreduce.bytes_sent")\n'
               '      ->fetch_add(1);\n'
               '}\n')
        _write(tmp_path, "tools/metrics_watch.py",
               'x = snap.get("ring.allreduce.bytes_sent")\n')
        findings, _ = metric_names.check(tmp_path)
        assert not findings, [str(f) for f in findings]


class TestPlantedSignalUnsafety:
    def test_printf_on_dump_path_fails(self, tmp_path):
        _write(tmp_path, "cpp/htpu/flight_recorder.cc",
               "#include <cstdio>\n"
               "void SignalDump(const char* why) {\n"
               '  printf("dump %s\\n", why);\n'
               "}\n"
               "void Sigusr2Handler(int) {\n"
               '  SignalDump("sigusr2");\n'
               "}\n")
        findings, _ = signal_safety.check(tmp_path)
        assert any("printf" in f.message and "SIGUSR2" in f.message
                   for f in findings), [str(f) for f in findings]

    def test_transitive_helper_is_walked(self, tmp_path):
        # The deny token hides one call deep; the walk must follow it.
        _write(tmp_path, "cpp/htpu/flight_recorder.cc",
               "void Helper(char* p) {\n"
               "  std::lock_guard<std::mutex> g(mu);\n"
               "}\n"
               "void SignalDump(const char* why) {\n"
               "  char buf[64];\n"
               "  Helper(buf);\n"
               "}\n"
               "void Sigusr2Handler(int) {\n"
               '  SignalDump("sigusr2");\n'
               "}\n")
        findings, _ = signal_safety.check(tmp_path)
        assert any("lock_guard" in f.message for f in findings), \
            [str(f) for f in findings]

    def test_clean_dump_path_passes(self, tmp_path):
        _write(tmp_path, "cpp/htpu/flight_recorder.cc",
               "void SignalDump(const char* why) {\n"
               "  char buf[64];\n"
               "  int n = snprintf(buf, sizeof(buf), \"%s\", why);\n"
               "  write(2, buf, n);\n"
               "}\n"
               "void Sigusr2Handler(int) {\n"
               '  SignalDump("sigusr2");\n'
               "}\n")
        findings, _ = signal_safety.check(tmp_path)
        assert not findings, [str(f) for f in findings]


class TestCliOnFixture:
    def test_cli_exits_nonzero_on_planted_tree(self, tmp_path):
        _write(tmp_path, "horovod_tpu/foo.py",
               'import os\n'
               'X = os.environ.get("HOROVOD_TPU_PLANTED_KNOB", "1")\n')
        proc = subprocess.run(
            [sys.executable, "-m", "tools.analyze", "--root",
             str(tmp_path), "--no-native"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "HOROVOD_TPU_PLANTED_KNOB" in proc.stdout
