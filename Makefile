# Build the native runtime library (engine + storage + recordio + C API +
# embedded-CPython real-runtime binding).  `make` → mxnet_tpu/lib/libmxtpu_rt.so
# The python binding (src/py_runtime.cc) links libpython so C/C++ callers run
# the SAME jnp/XLA ops as python; build with PYBACKEND=0 for a python-less lib
# (the NDArray tier then uses the self-contained host fallback).
CXX ?= g++
CXXFLAGS ?= -O2 -fPIC -std=c++17 -Wall -Wextra -pthread
INCLUDES := -Iinclude
SRCS := src/engine.cc src/storage.cc src/recordio.cc src/ndarray.cc src/ffi.cc
SRCS += src/dataio.cc
SRCS += src/telemetry.cc
LIB := mxnet_tpu/lib/libmxtpu_rt.so

# native no-GIL image decode tier (src/dataio.cc) needs OpenCV; built as a
# stub that errors at runtime when the headers are absent
OPENCV_CFLAGS := $(shell pkg-config --cflags opencv4 2>/dev/null)
ifneq ($(OPENCV_CFLAGS),)
CXXFLAGS += -DMXTPU_WITH_OPENCV $(OPENCV_CFLAGS)
LDLIBS += -lopencv_imgcodecs -lopencv_imgproc -lopencv_core

# scaled-decode fast path (libjpeg-turbo classic API): probe with an
# actual compile+link of jpeg_mem_src so a header-only or stub install
# never produces a lib that fails at load time.  Only meaningful with
# OpenCV present (the loader's fallback decoder).
PROBE_DIR := $(or $(TMPDIR),/tmp)
LIBJPEG_OK := $(shell printf '#include <stdio.h>\n#include <jpeglib.h>\nint main(){struct jpeg_decompress_struct c;(void)c;(void)jpeg_mem_src;return 0;}\n' \
	      > $(PROBE_DIR)/_mxtpu_jpeg_probe.c && \
	      $(CXX) -x c $(PROBE_DIR)/_mxtpu_jpeg_probe.c -ljpeg -o $(PROBE_DIR)/_mxtpu_jpeg_probe 2>/dev/null \
	      && echo 1)
ifeq ($(LIBJPEG_OK),1)
CXXFLAGS += -DMXTPU_WITH_LIBJPEG
LDLIBS += -ljpeg
endif
endif

# snapshot the python-less source/lib lists before the PYBACKEND block
# appends the embedded-CPython binding: the TSAN build must not link
# libpython (TSAN's interceptors drown in the interpreter's allocator)
TSAN_SRCS := $(SRCS)
TSAN_LDLIBS := $(LDLIBS)

PYBACKEND ?= 1
PY_INCLUDES := $(shell python3-config --includes 2>/dev/null)
PY_LDLIB := $(shell python3-config --ldflags --embed 2>/dev/null || \
	      python3-config --ldflags 2>/dev/null)
ifeq ($(PYBACKEND),1)
ifneq ($(PY_INCLUDES),)
SRCS += src/py_runtime.cc
INCLUDES += $(PY_INCLUDES)
LDLIBS += $(PY_LDLIB) -ldl
else
CXXFLAGS += -DMXTPU_NO_PYBACKEND
endif
else
CXXFLAGS += -DMXTPU_NO_PYBACKEND
endif

all: $(LIB)

$(LIB): $(SRCS) include/mxtpu/c_api.h src/telemetry.h
	@mkdir -p mxnet_tpu/lib
	$(CXX) $(CXXFLAGS) $(INCLUDES) -shared -o $@ $(SRCS) $(LDLIBS)

# address-sanitizer build of the native runtime + its C++ test, ≙ the
# reference's ASAN CI job (SURVEY §5.2); run: make asan
ASAN_LIB := mxnet_tpu/lib/libmxtpu_rt_asan.so
asan:
	@mkdir -p mxnet_tpu/lib
	$(CXX) $(CXXFLAGS) -fsanitize=address -fno-omit-frame-pointer \
	    $(INCLUDES) -shared -o $(ASAN_LIB) $(SRCS) $(LDLIBS)
	$(CXX) -O1 -g -std=c++17 -fsanitize=address -fno-omit-frame-pointer \
	    -Iinclude -Icpp-package/include \
	    cpp-package/tests/test_train_xor.cc $(abspath $(ASAN_LIB)) \
	    -o /tmp/mxtpu_asan_xor -pthread
	@echo "ASAN build OK: LD_LIBRARY_PATH=mxnet_tpu/lib" \
	      "MXTPU_BACKEND=host /tmp/mxtpu_asan_xor"

# thread-sanitizer build of the native runtime + a pthread smoke that
# hammers engine/storage/telemetry/recordio/thread-pool locking, ≙ the
# reference's TSAN CI job; run: make tsan  (docs/static_analysis.md)
TSAN_LIB := mxnet_tpu/lib/libmxtpu_rt_tsan.so
tsan:
	@mkdir -p mxnet_tpu/lib
	$(CXX) $(CXXFLAGS) -DMXTPU_NO_PYBACKEND -O1 -g -fsanitize=thread \
	    -fno-omit-frame-pointer $(INCLUDES) -shared -o $(TSAN_LIB) \
	    $(TSAN_SRCS) $(TSAN_LDLIBS)
	$(CXX) -O1 -g -std=c++17 -fsanitize=thread -fno-omit-frame-pointer \
	    -Iinclude cpp-package/tests/test_tsan_smoke.cc \
	    $(abspath $(TSAN_LIB)) -o /tmp/mxtpu_tsan_smoke -pthread
	LD_LIBRARY_PATH=mxnet_tpu/lib TSAN_OPTIONS="halt_on_error=1" \
	    /tmp/mxtpu_tsan_smoke

# static-analysis gate: mxlint (tools/analyze/) over the whole tree —
# env/telemetry doc drift, lock discipline, trace purity, fault-spec
# grammar, span hygiene.  Stdlib-only (no JAX import), a few seconds;
# exits non-zero on any unsuppressed finding (docs/static_analysis.md).
analyze-check:
	python tools/analyze/mxlint.py

# the quickest proof that the system still starts on the chip: ResNet-50
# fused training then serving on one TPU (fails at once without one);
# `python chip_smoke.py --chips 4` is the dp=2 x tp=2 phase alone
chip-smoke:
	python chip_smoke.py

clean:
	rm -f $(LIB) $(LIB).inputs.sha256 $(ASAN_LIB) $(TSAN_LIB)

# multi-process parameter-server tests (pytest -m dist): excluded from
# quick selections by marker, run here explicitly.  Each test carries a
# SIGALRM per-test timeout (tests/conftest.py) so a hung socket bounds
# its own cost.  Needs a backend that supports multi-process collectives
# (the pure-CPU container does not — expect failures there).
test-dist:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m dist \
	    -p no:cacheprovider

# telemetry smoke: exercise engine/storage/kvstore/datafeed, then assert
# mx.telemetry.snapshot() has every section populated and the Prometheus
# exposition renders (docs/telemetry.md).  `--check` exits non-zero on a
# missing section.
telemetry-check:
	JAX_PLATFORMS=cpu python -m mxnet_tpu.telemetry --check

# Eager-dispatch regression gate: fails when framework_overhead_us
# exceeds the 60 µs budget or the steady-state executable-cache hit
# rate drops below 99% (see docs/eager_dispatch.md).
dispatch-check:
	JAX_PLATFORMS=cpu python benchmark/opperf/opperf.py \
		--dispatch-overhead --check

# Fused-step regression gate: one compiled executable per
# (block, optimizer) identity, zero steady-state retraces/rebuilds,
# exactly one host dispatch per step, zero eager dispatch-cache traffic
# (see docs/fused_step.md).  Imported (not -m) to avoid runpy's
# already-in-sys.modules warning for a package submodule.
fused-check:
	JAX_PLATFORMS=cpu python -c "from mxnet_tpu.parallel import train; \
		raise SystemExit(train._selfcheck())"

# Durable-checkpoint regression gate: save the fused trainer, inject
# every MXNET_CKPT_FAULT mode, and assert restore falls back to the
# newest intact checkpoint bit-for-bit, retention GC holds keep-K, and
# an async save returns in step-loop time (see docs/checkpoint.md).
ckpt-check:
	JAX_PLATFORMS=cpu python -c "from mxnet_tpu import checkpoint; \
		raise SystemExit(checkpoint._selfcheck())"

# Fused residual-block regression gate: interpret-mode parity of the
# Pallas conv+BN+ReLU(+add) pipeline (fwd/dgrad/wgrad/dgamma) on all
# three ResNet stage shapes, train and frozen BN, the routing rule over
# {stage} x {one TPU, CPU, four devices}, a fuse_step run with 0
# retraces / 0 rebuilds / 1 dispatch per step, and the lone conv through
# ops/nn.py (see docs/pallas.md).
pallas-check:
	JAX_PLATFORMS=cpu python -m pytest tests/test_pallas_block.py \
		tests/test_pallas_conv.py -q -p no:cacheprovider

# Data-feed regression gate: build a synthetic .rec, assert the turbo
# scaled-decode backend is selected when available, pixel parity vs the
# OpenCV fallback (exact at 8/8, bounded at DCT scales), stats-reset
# correctness, and ≥1.5× 4-worker-vs-1-worker scaling (relative; only
# enforced when the host has ≥4 cores — see docs/datafeed.md).
feed-check:
	JAX_PLATFORMS=cpu python -c "from mxnet_tpu.io import feedcheck; \
		raise SystemExit(feedcheck._selfcheck())"

# Sharding regression gate: plan inference on resnet50 + a 2-layer
# transformer (rule table of docs/sharding.md), plan JSON round-trip +
# fingerprint re-key on edit, and a fused SHARDED step over tp=2 ×
# hierarchical dp (dp_out×dp_in) on 8 forced host devices with
# 0 retraces / 0 rebuilds / 1 dispatch per step, bit-for-bit replay
# equality vs the replicated step at the same dp grouping (tolerance vs
# single-device), and per-device parameter bytes = 1/tp.
shard-check:
	JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	python -c "from mxnet_tpu.parallel import sharding; \
		raise SystemExit(sharding._selfcheck())"

# INT8 quantization regression gate: int8 Pallas kernel parity vs the
# XLA int8 route (interpret mode), quantize a small seeded net through
# the fused residual-block route and hold it within tolerance of the
# float reference with argmax agreement + live Pallas-stage hit
# counters, serve it at precision=int8 with ZERO post-warmup retraces,
# and re-register under MXNET_SERVE_PRECISION=int8 to see the engine
# rebuild at int8 (see docs/quantization.md).
int8-check:
	JAX_PLATFORMS=cpu python -c "from mxnet_tpu import quantization; \
		raise SystemExit(quantization._selfcheck())"

# Serving-tier regression gate: warm an engine over the bucket ladder,
# fire a concurrent single-item burst, and assert it was served via
# coalesced bucketed batches (≥1 fill > 1), bit-for-bit equal to the
# unbatched forward, with 0 retraces after warm-up, a reportable p99,
# and a clean shutdown with no leaked serve threads (docs/serving.md).
serve-check:
	JAX_PLATFORMS=cpu python -c "from mxnet_tpu import serve; \
		raise SystemExit(serve._selfcheck())"

# Resilient-serving chaos gate: router + 2 real replica subprocesses
# under supervise_respawn; asserts 2-replica QPS ≥ 1.5× one replica,
# then SIGKILLs a replica under load and requires ZERO client-visible
# failures for admitted requests plus a full breaker
# open → half-open → closed cycle and an ejection/reinstatement pair in
# router telemetry (docs/serving.md §resilience).  Slow (~1 min) —
# spawns subprocess fleets; not part of tier-1 pytest.
chaos-check:
	JAX_PLATFORMS=cpu python -m mxnet_tpu.serve.chaos --check

# Distributed-data-service functional gate: 2 real decode-worker
# subprocesses; asserts global-shuffle determinism (two fresh clients
# produce the bitwise-identical stream, equal to local decode), a
# seeded epoch permutation that actually permutes and varies by epoch,
# a counted fallback-to-local leg when every worker is unroutable, and
# ≥1.5× 2-worker aggregate throughput (sleep-bound synthetic service
# time, so it holds on 1-core rigs — docs/datafeed.md §data service).
feed-service-check:
	JAX_PLATFORMS=cpu python -m mxnet_tpu.io.feed_chaos --service

# Feed-plane chaos gate: a 2-worker fed loop under supervise_respawn;
# SIGKILLs one decode worker mid-epoch and requires ZERO lost or
# duplicated samples (bitwise batch-stream parity vs an uninterrupted
# reference), a counted ejection → reinstatement cycle in the
# feed_service telemetry section, and a counted bitwise-correct
# fallback-to-local leg with all workers down.  Slow (~1 min) — spawns
# subprocess fleets; not part of tier-1 pytest.
feed-chaos-check:
	JAX_PLATFORMS=cpu python -m mxnet_tpu.io.feed_chaos --check

# Distributed-tracing gate: spawn a real replica subprocess behind an
# in-process router AND a real decode worker feeding a fused train
# step; each must yield one trace id whose spans cross ≥2 OS processes
# and nest (child ⊆ parent), the coalesced serve.execute span must
# link all member request spans, and tools/trace.py merge over the
# SIGUSR2-collected shards must emit valid Chrome trace-event JSON
# (docs/tracing.md).
trace-check:
	JAX_PLATFORMS=cpu python -m mxnet_tpu.tracecheck

# Observability gate: a real mini fleet (replica + feed decode worker
# subprocesses, in-process router + fused-step trainer) with the obs
# recorder sampling at 100 ms and the seeded SLO watchdog armed.
# Injects a 250 ms feed-fetch delay fault and requires the
# input_starved alert to FIRE and then CLEAR through hysteresis once
# the fault is removed; tools/obs.py scrape must merge /metrics from
# every role with the trainer's recorder shard into one report showing
# non-zero rates per role and finite input-stall / goodput / MFU
# signals (docs/observability.md).  Slow (~1 min) — spawns subprocess
# fleets; not part of tier-1 pytest.
obs-check:
	JAX_PLATFORMS=cpu python -m mxnet_tpu.obs --check

# Autoregressive decode gate (docs/generate.md): continuous-batched
# decode bit-for-bit vs unbatched greedy, ring wraparound + seek
# (snapshot/restore) replay parity down to the cache bits, 0 retraces
# after warmup, and join-at-iteration-boundary observed through the
# DecodeBatcher.
decode-check:
	JAX_PLATFORMS=cpu python -m mxnet_tpu.generate

# Tensor-parallel serving gate (docs/serving.md §sharded serving): on 2
# forced host devices, a tp=2 model through the full router tier is
# bit-for-bit equal to the unsharded engine (bucket ladder AND streamed
# decode), per-device param/KV bytes are exactly 1/tp, 0 post-warmup
# retraces, a plan edit re-keys the programs as a counted rebuild, and
# a model over MXNET_SERVE_HBM_BUDGET refuses unsharded but serves
# sharded — including params restored straight into their 1/tp
# placement from a sharded checkpoint.
tp-serve-check:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=2" \
		python -c "from mxnet_tpu.serve import tpcheck; raise SystemExit(tpcheck._selfcheck())"

.PHONY: all clean asan tsan analyze-check test-dist telemetry-check \
	dispatch-check fused-check ckpt-check serve-check chaos-check \
	pallas-check feed-check shard-check feed-service-check \
	feed-chaos-check trace-check int8-check obs-check decode-check \
	tp-serve-check chip-smoke
