# elbencho-tpu top-level targets (reference: hand-written Makefile driving
# the C++ build; here the Python package needs no build and the native
# engine lives in csrc/)

.PHONY: all native native-tsan native-asan tsan asan check check-schema \
	lint test test-fast test-chaos test-scale test-mesh test-obs \
	test-scenario test-tune test-examples fuzz docs clean deb rpm \
	docker

all: native

native:
	$(MAKE) -C csrc

# ThreadSanitizer build of the native engine (SURVEY.md section 5.2: the
# reference has no sanitizer targets; we add one since the engine is new).
# Always rebuilds — the sanitized .so replaces the normal one until the
# next `make native`.
native-tsan:
	$(MAKE) -C csrc clean
	$(MAKE) -C csrc CXXFLAGS="-O1 -g -fsanitize=thread -fPIC -std=c++17"
	@touch csrc/ioengine.cpp  # so the next `make native` rebuilds normally
	@echo "tsan build done; run tests with:" \
		"LD_PRELOAD=\$$(gcc -print-file-name=libtsan.so) pytest ..."

# AddressSanitizer build (same replace-then-restore dance as tsan)
native-asan:
	$(MAKE) -C csrc clean
	$(MAKE) -C csrc CXXFLAGS="-O1 -g -fsanitize=address -fPIC -std=c++17"
	@touch csrc/ioengine.cpp
	@echo "asan build done; run tests with:" \
		"LD_PRELOAD=\$$(gcc -print-file-name=libasan.so)" \
		"ASAN_OPTIONS=detect_leaks=0 pytest ..."

# sanitizer gates: build the sanitized engine AND run the native test
# file against it (covers the raw-ctypes stream/slot-reuse tests plus
# the ABI-10 cancel + fault-injection + deadline tests), then restore
# the normal build
tsan: native-tsan
	LD_PRELOAD=$$(gcc -print-file-name=libtsan.so) \
		python -m pytest tests/test_native_engine.py -q
	$(MAKE) native

asan: native-asan
	LD_PRELOAD=$$(gcc -print-file-name=libasan.so) \
		ASAN_OPTIONS=detect_leaks=0 \
		python -m pytest tests/test_native_engine.py -q
	$(MAKE) native

# the single green command (SURVEY.md section 5.2 sanitizer/robustness
# gate): static analysis + pytest + seeded fuzz sweeps + the lockgraph-
# armed chaos suite (runtime lock-order detector beside the native
# sanitizers) + asan/tsan engine builds each re-running the native test
# file + the end-to-end example suite. Exits nonzero on the first
# failing stage; ends by restoring the normal (unsanitized) engine
# build.
check: native
	tools/elbencho-tpu-lint
	python -m pytest tests/ -q
	tools/fuzz-sweep
	env ELBENCHO_TPU_TESTING=1 ELBENCHO_TPU_LOCKGRAPH=1 \
		python -m pytest tests/test_fault_tolerance.py \
		tests/test_io_fault_tolerance.py tests/test_run_lifecycle.py \
		tests/test_svc_stream.py -q -m chaos
	env JAX_PLATFORMS=cpu ELBENCHO_TPU_TESTING=1 \
		ELBENCHO_TPU_LOCKGRAPH=1 \
		python -m pytest tests/test_autotune.py -q -m tune
	$(MAKE) native-asan
	LD_PRELOAD=$$(gcc -print-file-name=libasan.so) \
		ASAN_OPTIONS=detect_leaks=0 \
		python -m pytest tests/test_native_engine.py -q
	$(MAKE) native-tsan
	LD_PRELOAD=$$(gcc -print-file-name=libtsan.so) \
		python -m pytest tests/test_native_engine.py -q
	$(MAKE) native
	tools/test-examples $${BASEDIR:-/tmp}
	@echo "make check: ALL GREEN"

# fuzz sweeps alone (fixed default seed; see tools/fuzz-sweep --help)
fuzz:
	tools/fuzz-sweep

# project-invariant static analysis (elbencho_tpu/analysis/, rule
# catalog: docs/static-analysis.md): merge-rule completeness, append-
# only schemas, route_lock/WorkersSharedData lock discipline, off-path
# telemetry guards, to_service_dict/FINGERPRINT_EXCLUDE wire hygiene,
# flags-parity drift — the conventions every "review-hardened"
# paragraph since PR 10 re-fixed by hand, as a machine gate. Audited
# exceptions: tools/lint-allowlist. `--fix` rewrites the generated
# files the two mechanical rules check.
lint:
	tools/elbencho-tpu-lint

# append-only schema tier alone (PATH_AUDIT / CONTROL_AUDIT lists, CSV
# columns, summarize-json column tail) against the previous commit —
# kept as its own entrypoint; since the rule engine landed this is
# `elbencho-tpu-lint --schema` behind the historical shim
check-schema:
	tools/check-schema

test: native lint
	python -m pytest tests/ -q

test-fast: native
	python -m pytest tests/ -q -x --ignore=tests/test_service_mode.py \
		--ignore=tests/test_netbench.py

# chaos gates alone: the fault-injection suites that drive control-plane
# retry/watchdog/degradation, data-plane I/O faults, and the crash-safe
# run lifecycle (lease orphaning, journal/resume, signal shutdown)
# through real master/service processes (pytest marker `chaos`) — armed
# with the runtime lock-order detector (testing/lockgraph.py): the
# session fails on any lock-order cycle or route_lock-across-RPC in the
# union of every fleet process's lock graph
test-chaos: native
	env ELBENCHO_TPU_TESTING=1 ELBENCHO_TPU_LOCKGRAPH=1 \
		python -m pytest tests/test_fault_tolerance.py \
		tests/test_io_fault_tolerance.py tests/test_run_lifecycle.py \
		tests/test_svc_stream.py -q -m chaos

# pod-slice gate: the --tpuslice mesh suite on an 8-device virtual CPU
# mesh (mesh factory edge cases, fingerprint-exact ingest/redistribute
# equivalence, interrupt/chip-loss behavior, Ici counter merge rules,
# service-wire merge, MULTICHIP capture; pytest marker `mesh`;
# docs/pod-slice.md)
test-mesh: native
	env JAX_PLATFORMS=cpu python -m pytest tests/test_pod_slice.py \
		-q -m mesh

# control-plane scale gate: a simulated 64-host in-process loopback
# fleet proving --svcstream --svcfanout holds O(fanout) master
# connections and cuts request count / per-tick control-plane bytes
# >= 10x vs polling (pytest marker `scale`; docs/control-plane.md)
test-scale:
	env JAX_PLATFORMS=cpu ELBENCHO_TPU_NO_NATIVE=1 \
		ELBENCHO_TPU_TESTING=1 ELBENCHO_TPU_LOCKGRAPH=1 \
		python -m pytest tests/test_stream_scale.py -q -m scale

# observability gate: the telemetry + flight-recorder + run-doctor +
# fleet-tracing + slow-op-forensics suites (/metrics scrape-under-load,
# trace schema, flightrec codec round-trip/torn-tail/merge properties,
# doctor verdicts incl. straggler + tail attribution, clock-skew
# estimator units, fleet trace merge properties, the 8-host
# cross-host-flow e2e, the --slowops chaos e2e naming an injected slow
# host/file/offset, the no-op overhead guards; pytest marker `obs`;
# docs/telemetry.md)
test-obs: check-schema
	env JAX_PLATFORMS=cpu python -m pytest tests/test_telemetry.py \
		tests/test_flightrec.py tests/test_tracefleet.py \
		tests/test_slowops.py -q -m obs

# training-ingest scenario gate: the --scenario suite (plan expansion
# units, shuffle-window generator properties, dataloader pacing, e2e
# local runs of all five scenarios with scenario-level doctor verdicts,
# the in-process master-mode fleet run, summarize/chart column checks;
# pytest marker `scenario`; docs/scenarios.md). Also part of the default
# `make test` pytest sweep.
test-scenario: native check-schema
	env JAX_PLATFORMS=cpu ELBENCHO_TPU_TESTING=1 \
		ELBENCHO_TPU_LOCKGRAPH=1 \
		python -m pytest tests/test_scenarios.py \
		-q -m scenario

# closed-loop autotuning gate: fake-doctor convergence units (each
# verdict moves the axis it names, plateau/budget/probe-cap stops,
# repeat-median noise rejection), knob-space config validation
# (tpudirect clamp, service-mode-only axes), tuned-profile round-trip,
# and the chaos e2e where an injected per-op delay on an in-process
# 2-host fleet makes the tuner provably beat the defaults (pytest
# marker `tune`; docs/autotuning.md). Lockgraph-armed — the probe loop
# exercises repeated master-mode rebuilds, exactly where lock-order
# bugs hide — and part of the chaos stage of `make check`.
test-tune: native
	env JAX_PLATFORMS=cpu ELBENCHO_TPU_TESTING=1 \
		ELBENCHO_TPU_LOCKGRAPH=1 \
		python -m pytest tests/test_autotune.py -q -m tune

# end-to-end example suite against real resources (loopdevs, services)
test-examples: native
	tools/test-examples $${BASEDIR:-/tmp}

docs:
	python tools/generate-usage-docs

# packaging (reference: make deb / make rpm / Docker images)
deb: native
	bash packaging/make-deb.sh

rpm: native
	@command -v rpmbuild >/dev/null || \
		{ echo "rpmbuild not installed"; exit 1; }
	rpmbuild -bb --define "_sourcedir $(CURDIR)" \
		--define "pkg_version $$(sed -n 's/^version = \"\(.*\)\"/\1/p' \
		pyproject.toml)" packaging/elbencho-tpu.spec

docker:
	@command -v docker >/dev/null || { echo "docker not installed"; exit 1; }
	docker build -t elbencho-tpu .

clean:
	$(MAKE) -C csrc clean
	rm -rf build dist/*.egg-info
