# Top-level convenience targets.  The native core itself builds via
# cpp/Makefile (invoked automatically by horovod_tpu/cpp_core.py on
# first import); this file bundles the repo-wide hygiene gates.

PYTHON ?= python

# Everything a reviewer runs before trusting a change, minus the slow
# multi-process tests: the cross-language contract checkers (knob parity,
# C API/ctypes signatures, metric names, signal safety) plus both
# sanitizer smoke binaries built AND executed.  Fails on any finding,
# any sanitizer report, or any build warning-turned-error.
check: analyze asan tsan
	ASAN_OPTIONS=detect_leaks=0 ./cpp/htpu_smoke_asan
	TSAN_OPTIONS=halt_on_error=1 ./cpp/htpu_smoke_tsan

# The static-analysis suite alone (fast, no toolchain needed).
# See docs/static-analysis.md for what each checker enforces.
analyze:
	$(PYTHON) -m tools.analyze

asan:
	$(MAKE) -C cpp asan

tsan:
	$(MAKE) -C cpp tsan

# Tier-1 test suite, as the driver runs it (six xdist workers, a file to a
# worker, 1470 s for the whole; tests/conftest.py limits each test), then
# where its time went: seconds a file, the long cases, the cap's share.
T1_XML ?= /tmp/_t1.xml
test:
	rm -f $(T1_XML); \
	timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
		$(PYTHON) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider -p no:randomly \
		-p xdist -n 6 --dist loadfile --junitxml=$(T1_XML); rc=$$?; \
	$(PYTHON) tools/tier1_times.py $(T1_XML); exit $$rc

clean:
	$(MAKE) -C cpp clean
	rm -rf horovod_tpu/lib
	find . -name __pycache__ -type d -prune -exec rm -rf {} +

.PHONY: check analyze asan tsan test clean
