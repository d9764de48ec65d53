# Build orchestration for client_tpu: proto codegen + native libraries.
#
# Quality gates:
#   make lint        tpu-lint static analysis (client_tpu/analysis):
#                    per-file concurrency & numpy-semantics rules PLUS the
#                    whole-program pass (call-graph lock summaries:
#                    LOCK-INV, BLOCK-UNDER-LOCK, CALLBACK-UNDER-LOCK,
#                    PEER-CALL-UNDER-LOCK, and Eraser-style lockset
#                    inference: LOCKSET-RACE).  Runs over client_tpu/ AND
#                    tests/; exits non-zero on any finding not
#                    grandfathered in analysis/baseline.json.  Incremental
#                    (mtime+rules-hash per-file cache + a fileset-digest
#                    cache for the program pass — a warm repeat run is
#                    ~1s); `--no-cache` to force cold.  Suppressions
#                    require a reason (`# tpulint: disable=RULE -- why`)
#                    and are audited: a waiver whose rule no longer fires
#                    is itself a finding (STALE-SUPPRESS).
#   make lint-sarif  lint, emitting SARIF 2.1.0 to build/lint.sarif for
#                    CI annotators and editors (same gate semantics).
#   make lint-strict lint, plus examples/ in the scanned program.
#   make test        ASAN native tests + the python suite.
#   make check       the PR gate, reproduced locally: make lint + the
#                    tier-1 pytest command (ROADMAP.md "Tier-1 verify").
#   make smoke       chip_smoke.py: the served path, once, on the TPU
#                    (Server <- gRPC <- TPU-shm -> fused batcher at
#                    resnet50 @ 224, LmEngine streams, the Pallas
#                    kernels compiled).  Needs the chip and refuses
#                    anything else; one process, because a chip belongs
#                    to one process at a time.
#   make prof        continuous-profiler demo: spin an in-process
#                    engine, run the cnn headline workload, print the
#                    time-attribution table (python -m client_tpu.profview
#                    --live; serve/prof.py is the instrument).
#   make chaos       the fast chaos-matrix subset (tests/test_chaos.py:
#                    deterministic fault schedules + invariant checkers)
#                    under the dynamic lock-order, race AND resource
#                    witnesses (TPULINT_LOCK_WITNESS=1
#                    TPULINT_RACE_WITNESS=1 TPULINT_RESOURCE_WITNESS=1)
#                    — the quick failure-domain gate.
#   make soak        slow-tier chaos repetition, run under the DYNAMIC
#                    witnesses: every lock built under client_tpu/
#                    records the real acquisition DAG (a cycle fails the
#                    round), @witness_shared classes run the Eraser
#                    lockset algorithm per field access (an unguarded
#                    shared write fails with both stacks + a flight
#                    dump), and every registered acquire/release pair is
#                    tracked in a live-handle table (a leaked KV block /
#                    lease / span fails the round with its stack).

PROTO_DIR := proto
PB_OUT := client_tpu/_proto
CXX ?= g++
CXXFLAGS ?= -O2 -fPIC -Wall -std=c++17
NATIVE_OUT := client_tpu/utils/shared_memory
TPUSHM_OUT := client_tpu/utils/tpu_shared_memory

.PHONY: all protos native cpp clean test asan java java-bindings lint \
        lint-sarif lint-strict check soak chaos prof smoke

lint:
	python -m client_tpu.analysis client_tpu tests

# Same gate, SARIF 2.1.0 artifact for CI annotation / editor import.
# The redirect preserves the exit code: findings still fail the target,
# but the .sarif lands either way so the annotator can show them.
lint-sarif:
	@mkdir -p build
	python -m client_tpu.analysis client_tpu tests --format sarif \
	    > build/lint.sarif

lint-strict:
	python -m client_tpu.analysis client_tpu tests examples

# One command = the PR gate: static analysis, then the tier-1 suite with
# the exact flags ROADMAP.md's "Tier-1 verify" runs.
check: lint
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
	    --continue-on-collection-errors -p no:cacheprovider \
	    -p no:xdist -p no:randomly

# The quickest proof that the system still starts on the chip.  No
# JAX_PLATFORMS here: the script must see whatever the machine has.
smoke:
	python chip_smoke.py

# Where the engine's time goes, in one command: an in-process engine
# runs the cnn headline workload and profview renders the
# dispatch/compute/host/idle attribution + MFU table from its own
# /v2/debug/prof-shaped report.
prof:
	JAX_PLATFORMS=cpu python -m client_tpu.profview --live

# Fast chaos-matrix gate: the deterministic fault schedules + invariant
# checkers (SIGKILL-with-active-sequences, anti-entropy convergence,
# harness units) under the dynamic lock-order witness.  TPU_FLIGHT_DIR
# routes flight-recorder dumps (an invariant failure dumps every
# replica's ring automatically) into build/flight/ so a red run ships
# its own postmortem artifacts.
chaos:
	@mkdir -p build/flight/chaos
	@JAX_PLATFORMS=cpu TPULINT_LOCK_WITNESS=1 TPULINT_RACE_WITNESS=1 \
	    TPULINT_RESOURCE_WITNESS=1 \
	    TPU_FLIGHT_DIR=build/flight/chaos \
	    python -m pytest tests/test_chaos.py -q -m 'not slow' \
	    -p no:cacheprovider -p no:xdist -p no:randomly || { \
	  echo "chaos FAILED — flight-recorder dumps archived:"; \
	  ls -l build/flight/chaos 2>/dev/null; exit 1; }

# Churn + isolation soak: the slow tier tier-1 excludes — repeats the
# replica-churn chaos acceptance (discovery add/retire, stream-pinned
# kill, resolver flap), the multi-tenant noisy-neighbor/hot-key
# scenario, the continuous-batching LM 128-stream submit/cancel churn,
# the three-replica fleet kill-mid-stream chaos, and the scaled
# chaos-matrix scenarios (randomized-timing SIGKILL with durable
# sequences, anti-entropy convergence) SOAK_N times; churn and
# isolation bugs are timing bugs, repetition finds them.
SOAK_N ?= 3
soak:
	@mkdir -p build/flight/soak
	@for i in $$(seq 1 $(SOAK_N)); do \
	  echo "== soak round $$i/$(SOAK_N) (lock-order + race + resource witness armed) =="; \
	  JAX_PLATFORMS=cpu TPULINT_LOCK_WITNESS=1 TPULINT_RACE_WITNESS=1 \
	      TPULINT_RESOURCE_WITNESS=1 \
	      TPU_FLIGHT_DIR=build/flight/soak \
	      python -m pytest tests/test_discovery.py \
	      tests/test_balance.py tests/test_frontdoor.py \
	      tests/test_lm.py tests/test_fleet.py tests/test_chaos.py \
	      -q -m slow \
	      -p no:cacheprovider -p no:xdist -p no:randomly || { \
	    echo "soak round $$i FAILED — flight-recorder dumps archived:"; \
	    ls -l build/flight/soak 2>/dev/null; exit 1; }; \
	done

all: protos native cpp

# ---- Java client (compiled when a JDK is present; skipped otherwise) ------
JAVA_SRC := $(shell find src/java -name '*.java' 2>/dev/null)
JAVA_BUILD := build/java/classes

java:
	@if command -v javac >/dev/null 2>&1; then \
	  mkdir -p $(JAVA_BUILD) && \
	  javac -d $(JAVA_BUILD) $(JAVA_SRC) && \
	  echo "java client compiled to $(JAVA_BUILD)"; \
	else \
	  echo "javac not found: skipping java client build"; \
	fi

# ---- Java FFM bindings over the C shm ABI (needs JDK >= 22) ---------------
JAVA_BINDINGS_SRC := $(shell find src/java-api-bindings/java -name '*.java' 2>/dev/null)
JAVA_BINDINGS_BUILD := build/java-bindings/classes

java-bindings:
	@if command -v javac >/dev/null 2>&1 && \
	    [ "$$(javac --version | sed 's/[^0-9]*\([0-9]*\).*/\1/')" -ge 22 ]; then \
	  mkdir -p $(JAVA_BINDINGS_BUILD) && \
	  javac -d $(JAVA_BINDINGS_BUILD) $(JAVA_BINDINGS_SRC) && \
	  echo "java ffm bindings compiled to $(JAVA_BINDINGS_BUILD)"; \
	else \
	  echo "javac >= 22 not found: skipping java ffm bindings"; \
	fi

# ---- native C++ client library + examples + integration test -------------
CPP_DIR := src/cpp
CPP_BUILD := build/cpp
CLIENT_SRCS := $(CPP_DIR)/client/json.cc $(CPP_DIR)/client/http_client.cc \
               $(CPP_DIR)/client/http_reactor.cc \
               $(CPP_DIR)/client/shm_utils.cc $(CPP_DIR)/client/transport.cc
CLIENT_HDRS := $(wildcard $(CPP_DIR)/client/*.h)
# Each client TU compiled once; every example/test links the objects.
CLIENT_OBJS := $(CPP_BUILD)/json.o $(CPP_BUILD)/http_client.o \
               $(CPP_BUILD)/http_reactor.o $(CPP_BUILD)/shm_utils.o \
               $(CPP_BUILD)/transport.o

# gRPC client: protoc-generated KServe protos + the h2/hpack transport.
PB_CPP := build/proto_cpp
GRPC_SRCS := $(CPP_DIR)/grpc/hpack.cc $(CPP_DIR)/grpc/h2.cc \
             $(CPP_DIR)/client/grpc_client.cc
GRPC_HDRS := $(wildcard $(CPP_DIR)/grpc/*.h)
GRPC_OBJS := $(CPP_BUILD)/hpack.o $(CPP_BUILD)/h2.o $(CPP_BUILD)/transport.o \
             $(CPP_BUILD)/grpc_client.o $(CPP_BUILD)/inference.pb.o \
             $(CPP_BUILD)/model_config.pb.o $(CPP_BUILD)/shm_utils.o
GRPC_LINK := -lprotobuf -lrt -lpthread -lz
GRPC_INC := -I$(PB_CPP) -I$(CPP_DIR)/client -I$(CPP_DIR)/grpc

HTTP_EXAMPLES := simple_http_infer_client \
                 simple_http_health_metadata \
                 simple_http_async_infer_client \
                 simple_http_string_infer_client \
                 simple_http_shm_client \
                 simple_http_sequence_sync_infer_client \
                 simple_http_ensemble_client \
                 simple_http_infer_multi_client \
                 reuse_infer_objects_http_client \
                 simple_http_model_control

cpp: $(addprefix $(CPP_BUILD)/,$(HTTP_EXAMPLES)) $(CPP_BUILD)/cc_client_test \
     $(CPP_BUILD)/libhttpclient_tpu.so grpc_cpp

GRPC_EXAMPLES := simple_grpc_infer_client \
                 simple_grpc_sequence_stream_infer_client \
                 simple_grpc_sequence_sync_infer_client \
                 simple_grpc_async_infer_client \
                 simple_grpc_health_metadata \
                 simple_grpc_model_control \
                 simple_grpc_shm_client \
                 simple_grpc_string_infer_client \
                 simple_grpc_ensemble_client \
                 simple_grpc_decoupled_repeat_client \
                 simple_grpc_custom_args_client \
                 simple_grpc_timeout_client \
                 image_client \
                 reuse_infer_objects_grpc_client

grpc_cpp: $(addprefix $(CPP_BUILD)/,$(GRPC_EXAMPLES)) \
          $(CPP_BUILD)/simple_grpc_tpushm_client \
          $(CPP_BUILD)/cc_grpc_client_test $(CPP_BUILD)/hpack_unit_test \
          $(CPP_BUILD)/client_timeout_test $(CPP_BUILD)/memory_leak_test \
          $(CPP_BUILD)/perf_worker

# native load-generation worker (the perf harness's C++ engine)
$(CPP_BUILD)/perf_worker: $(CPP_DIR)/perf/perf_worker.cc $(GRPC_OBJS)
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -o $@ $< $(GRPC_OBJS) $(GRPC_INC) $(GRPC_LINK)

# Dual-protocol test binaries link both client stacks (shared objects
# appear once: GRPC_OBJS already carries shm_utils.o and transport.o).
MIXED_OBJS := $(GRPC_OBJS) $(CPP_BUILD)/json.o $(CPP_BUILD)/http_client.o \
              $(CPP_BUILD)/http_reactor.o

$(CPP_BUILD)/client_timeout_test: $(CPP_DIR)/tests/client_timeout_test.cc $(GRPC_OBJS) $(CLIENT_OBJS)
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -o $@ $< $(MIXED_OBJS) $(GRPC_INC) $(GRPC_LINK)

$(CPP_BUILD)/memory_leak_test: $(CPP_DIR)/tests/memory_leak_test.cc $(GRPC_OBJS) $(CLIENT_OBJS)
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -o $@ $< $(MIXED_OBJS) $(GRPC_INC) $(GRPC_LINK)

$(PB_CPP)/inference.pb.cc: $(PROTO_DIR)/inference.proto $(PROTO_DIR)/model_config.proto
	mkdir -p $(PB_CPP)
	protoc -I$(PROTO_DIR) --cpp_out=$(PB_CPP) \
	    $(PROTO_DIR)/inference.proto $(PROTO_DIR)/model_config.proto

$(CPP_BUILD)/inference.pb.o: $(PB_CPP)/inference.pb.cc
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -w -c -o $@ $< -I$(PB_CPP)

$(CPP_BUILD)/model_config.pb.o: $(PB_CPP)/inference.pb.cc
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -w -c -o $@ $(PB_CPP)/model_config.pb.cc -I$(PB_CPP)

$(CPP_BUILD)/hpack.o: $(CPP_DIR)/grpc/hpack.cc $(GRPC_HDRS)
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -c -o $@ $< $(GRPC_INC)

$(CPP_BUILD)/h2.o: $(CPP_DIR)/grpc/h2.cc $(GRPC_HDRS) $(CLIENT_HDRS)
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -c -o $@ $< $(GRPC_INC)

$(CPP_BUILD)/grpc_client.o: $(CPP_DIR)/client/grpc_client.cc $(CPP_DIR)/client/grpc_client.h $(GRPC_HDRS) $(CLIENT_HDRS) $(PB_CPP)/inference.pb.cc
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -c -o $@ $< $(GRPC_INC)

$(CPP_BUILD)/hpack_unit_test: $(CPP_DIR)/tests/hpack_unit_test.cc $(CPP_BUILD)/hpack.o
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -o $@ $< $(CPP_BUILD)/hpack.o $(GRPC_INC)

$(addprefix $(CPP_BUILD)/,$(GRPC_EXAMPLES)): $(CPP_BUILD)/%: $(CPP_DIR)/examples/%.cc $(GRPC_OBJS)
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -o $@ $< $(GRPC_OBJS) $(GRPC_INC) $(GRPC_LINK)

$(CPP_BUILD)/ctpushm.o: $(CPP_DIR)/shm/ctpushm.cc
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -c -o $@ $<

# TPU-shm example links the libctpushm code directly (same TU the wheel
# ships as libctpushm.so)
$(CPP_BUILD)/simple_grpc_tpushm_client: $(CPP_DIR)/examples/simple_grpc_tpushm_client.cc $(GRPC_OBJS) $(CPP_BUILD)/ctpushm.o
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -o $@ $< $(GRPC_OBJS) $(CPP_BUILD)/ctpushm.o $(GRPC_INC) $(GRPC_LINK)

$(CPP_BUILD)/cc_grpc_client_test: $(CPP_DIR)/tests/cc_grpc_client_test.cc $(GRPC_OBJS)
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -o $@ $< $(GRPC_OBJS) $(GRPC_INC) $(GRPC_LINK)

$(CPP_BUILD)/libhttpclient_tpu.so: $(CLIENT_SRCS) $(CLIENT_HDRS)
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -shared -o $@ $(CLIENT_SRCS) -lrt -lpthread -lz

$(CLIENT_OBJS): $(CPP_BUILD)/%.o: $(CPP_DIR)/client/%.cc $(CLIENT_HDRS)
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -c -o $@ $< -I$(CPP_DIR)/client

$(addprefix $(CPP_BUILD)/,$(HTTP_EXAMPLES)): $(CPP_BUILD)/%: $(CPP_DIR)/examples/%.cc $(CLIENT_OBJS)
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -o $@ $< $(CLIENT_OBJS) -I$(CPP_DIR)/client -lrt -lpthread -lz

$(CPP_BUILD)/cc_client_test: $(CPP_DIR)/tests/cc_client_test.cc $(CLIENT_OBJS)
	mkdir -p $(CPP_BUILD)
	$(CXX) $(CXXFLAGS) -o $@ $< $(CLIENT_OBJS) -I$(CPP_DIR)/client -lrt -lpthread -lz

protos: $(PB_OUT)/inference_pb2.py $(PB_OUT)/tfserve_pb2.py

$(PB_OUT)/inference_pb2.py: $(PROTO_DIR)/inference.proto $(PROTO_DIR)/model_config.proto
	mkdir -p $(PB_OUT)
	protoc -I$(PROTO_DIR) --python_out=$(PB_OUT) \
	    $(PROTO_DIR)/inference.proto $(PROTO_DIR)/model_config.proto
	# protoc emits absolute imports; rewrite to package-relative.
	sed -i 's/^import model_config_pb2 as/from . import model_config_pb2 as/' \
	    $(PB_OUT)/inference_pb2.py

$(PB_OUT)/tfserve_pb2.py: $(PROTO_DIR)/tfserve.proto
	mkdir -p $(PB_OUT)
	protoc -I$(PROTO_DIR) --python_out=$(PB_OUT) $(PROTO_DIR)/tfserve.proto

native: $(NATIVE_OUT)/libcshm_tpu.so $(TPUSHM_OUT)/libctpushm.so

$(NATIVE_OUT)/libcshm_tpu.so: src/cpp/shm/cshm.cc
	mkdir -p $(NATIVE_OUT)
	$(CXX) $(CXXFLAGS) -shared -o $@ $< -lrt

$(TPUSHM_OUT)/libctpushm.so: src/cpp/shm/ctpushm.cc
	mkdir -p $(TPUSHM_OUT)
	$(CXX) $(CXXFLAGS) -shared -o $@ $< -lrt

# ---- sanitizer run (SURVEY §5.2): native shm libs + HPACK under ASAN ------
ASAN_FLAGS := -fsanitize=address -fno-omit-frame-pointer -g -O1

asan: $(CPP_BUILD)/shm_asan_test $(CPP_BUILD)/hpack_asan_test
	$(CPP_BUILD)/shm_asan_test
	$(CPP_BUILD)/hpack_asan_test

$(CPP_BUILD)/shm_asan_test: $(CPP_DIR)/tests/shm_sanitizer_test.cc src/cpp/shm/cshm.cc src/cpp/shm/ctpushm.cc
	mkdir -p $(CPP_BUILD)
	$(CXX) -std=c++17 -Wall $(ASAN_FLAGS) -o $@ $< \
	    src/cpp/shm/cshm.cc src/cpp/shm/ctpushm.cc -lrt

$(CPP_BUILD)/hpack_asan_test: $(CPP_DIR)/tests/hpack_unit_test.cc $(CPP_DIR)/grpc/hpack.cc
	mkdir -p $(CPP_BUILD)
	$(CXX) -std=c++17 -Wall $(ASAN_FLAGS) -o $@ $< \
	    $(CPP_DIR)/grpc/hpack.cc -I$(CPP_DIR)/grpc

clean:
	rm -f $(PB_OUT)/*_pb2.py $(NATIVE_OUT)/libcshm_tpu.so \
	    $(TPUSHM_OUT)/libctpushm.so
	rm -rf $(CPP_BUILD)

test: asan
	python -m pytest tests/ -x -q
