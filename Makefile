# polykey_tpu build/test/run targets.
# Mirrors the reference Makefile's target families (/root/reference/Makefile:
# build/run/test/compose lifecycle/help) adapted to the Python+C++ toolchain.

PYTHON ?= python3
CXX ?= g++
CXXFLAGS ?= -O2 -std=c++17 -Wall -Wextra
BUILD_DIR := build

.PHONY: help run run-client test test-models native protos clean perfbench-tiny dryrun \
	kernel-check chip-smoke metrics-smoke \
	obs-smoke chaos-smoke print-chaos occupancy-smoke occupancy-soak \
	failover-smoke failover-soak timeline-capture \
	flightwatch spec-smoke \
	disagg-smoke disagg-soak hostkv-smoke hostkv-soak \
	autopilot-smoke autopilot-soak \
	postmortem postmortem-smoke

help: ## Show available targets
	@grep -E '^[a-zA-Z_-]+:.*?## .*$$' $(MAKEFILE_LIST) | \
	  awk 'BEGIN {FS = ":.*?## "}; {printf "  \033[36m%-14s\033[0m %s\n", $$1, $$2}'

run: ## Start the gRPC gateway (mock backend; POLYKEY_BACKEND=tpu for engine)
	$(PYTHON) -m polykey_tpu.gateway.server

run-client: ## Run the dev client smoke test against a running server
	$(PYTHON) -m polykey_tpu.gateway.client

test: ## Run the full test suite (CPU, simulated 8-device mesh)
	$(PYTHON) -m pytest tests/ -x -q

test-report: ## Tests with the Jest-style report renderer
	$(PYTHON) -m pytest tests/ -q --report-log=/tmp/pytest-report.jsonl; \
	  $(PYTHON) -c "import sys; sys.path.insert(0,'.'); \
	    from polykey_tpu.gateway.beautify import print_jest_report; \
	    print_jest_report(open('/tmp/pytest-report.jsonl'))"

native: $(BUILD_DIR)/log-beautifier ## Build the native log beautifier

$(BUILD_DIR)/log-beautifier: native/log_beautifier.cc
	@mkdir -p $(BUILD_DIR)
	$(CXX) $(CXXFLAGS) -o $@ $<

protos: ## Regenerate protobuf stubs from protos/
	./scripts/gen_protos.sh

# As tests/perfbench/test_perfbench_rehearsal.py runs it; on the chip,
# BENCHMARK.json's `command` with perfbench/run.py's arguments, no --tiny.
perfbench-tiny: ## One benchmark cell (BENCHMARK.json + perfbench/) on the CPU at toy size
	$(PYTHON) perfbench/run.py --workload mistral-7b.decode-saturated --seed 2147483659 --seconds 3 --trace 0 --tiny

# Observability acceptance probe (ISSUE 10; grown from PR 1's
# metrics-smoke): families, OpenMetrics exemplars, the gated /debug
# surface (incl. a 2-replica pool), and a CPU profiler-capture
# round-trip with the single-flight guarantee.
obs-smoke: ## Boot the stack on CPU; assert families, exemplars, debug endpoints, profiler
	JAX_PLATFORMS=cpu $(PYTHON) scripts/obs_smoke.py

metrics-smoke: obs-smoke ## Legacy alias for obs-smoke

# Operator triage console (ISSUE 11): top-style live view over /metrics
# + /debug/slo (set POLYKEY_DEBUG_ENDPOINTS=1 on the server for the
# windowed + SLO sections). PORT=9464 by default.
flightwatch: ## Live console over a running server's /metrics + /debug/slo
	$(PYTHON) scripts/flightwatch.py $(if $(PORT),--port $(PORT),)

# Flight-deck timeline capture (ISSUE 10): a short CPU occupancy soak
# exporting the engine timeline as Perfetto JSON. The committed
# perf/timeline_*.json artifacts come from this target (open them at
# https://ui.perfetto.dev); tests/test_timeline.py validates structure.
timeline-capture: ## Capture a CPU soak timeline to perf/ (Perfetto JSON)
	JAX_PLATFORMS=cpu POLYKEY_DISPATCH_LOOKAHEAD=2 \
	  $(PYTHON) scripts/occupancy_soak.py \
	  --slots 8 --duration 12 --min-occupancy 0.7 \
	  --out /tmp/timeline_soak.json \
	  --timeline perf/timeline_$$(date -u +%Y-%m-%d).json

# Deterministic fault-injection suite (ISSUE 3 + ISSUE 9): deadline
# drops, load shedding, watchdog trip → supervised restart, client
# retries, health transitions, replica-pool failover/resume — all on
# CPU with test-scaled timeouts.
CHAOS_TESTS := tests/test_chaos.py tests/test_faults.py tests/test_health.py \
	tests/test_client_retry.py tests/test_replica_pool.py \
	tests/test_disagg.py tests/test_kv_wire.py

chaos-smoke: ## Run the fault-injection/resilience test suite on CPU
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest $(CHAOS_TESTS) -q

# Occupancy discipline (ISSUE 4): Poisson soak at CI scale — 8 slots,
# 10 s window, measured lanes >= 0.7 x slots (the 48-slot acceptance
# run measured 0.82+; see perf/occupancy_soak_*.json). Artifact goes to
# /tmp so CI runs never dirty the repo.
occupancy-smoke: ## Poisson-load occupancy soak at CI scale (gated >= 0.7 + sched-witness zero-starvation gate)
	rm -rf /tmp/polykey-sched-witness-occupancy
	JAX_PLATFORMS=cpu POLYKEY_SCHED_WITNESS=1 \
	  POLYKEY_SCHED_WITNESS_OUT=/tmp/polykey-sched-witness-occupancy \
	  $(PYTHON) scripts/occupancy_soak.py \
	  --slots 8 --duration 10 --min-occupancy 0.7 \
	  --out /tmp/occupancy_smoke.json
	$(PYTHON) -m polykey_tpu.analysis sched --only SL006 \
	  --witness /tmp/polykey-sched-witness-occupancy

# Speculative rounds (ISSUE 19): the fused accept/merge core's
# jit-vs-eager parity plus engine greedy bit-identity between the plain
# and the speculative engine at lookahead depths 1 and 2.
spec-smoke: ## Accept/merge interpret parity + spec bit-identity vs plain
	JAX_PLATFORMS=cpu $(PYTHON) scripts/spec_smoke.py

# Host-memory KV tier (ISSUE 15): sticky multi-turn sessions at 1.5x
# the device pool — gates zero failed RPCs, greedy streams bit-identical
# to an all-device run, and a supervised restart mid-soak recovering
# warm TTFT from the durable prefix store. Smoke scale for CI; the
# committed acceptance artifact comes from hostkv-soak.
hostkv-smoke: ## Host-KV tier drill at CI scale (spill/fault/restart, bit-identity gate + heap-witness zero-growth gate)
	rm -rf /tmp/polykey-heap-witness-hostkv
	JAX_PLATFORMS=cpu POLYKEY_HEAP_WITNESS=1 \
	  POLYKEY_HEAP_WITNESS_OUT=/tmp/polykey-heap-witness-hostkv \
	  $(PYTHON) scripts/occupancy_soak.py --host-kv \
	  --slots 8 --hk-sessions 6 --hk-turns 3 --hk-base 64 \
	  --hk-turn-tokens 32 --out /tmp/hostkv_smoke.json
	$(PYTHON) -m polykey_tpu.analysis mem --only ML006 \
	  --witness /tmp/polykey-heap-witness-hostkv

hostkv-soak: ## The 12-session / 4-turn acceptance drill (writes perf/)
	JAX_PLATFORMS=cpu $(PYTHON) scripts/occupancy_soak.py --host-kv \
	  --slots 8 \
	  --out perf/hostkv_soak_$$(date -u +%Y%m%d_%H%M%S).json

# Timestamped output so a rerun never clobbers a committed, cited
# acceptance artifact (the script's date-only default would).
occupancy-soak: ## The full 48-slot / 60 s acceptance soak (writes perf/)
	JAX_PLATFORMS=cpu $(PYTHON) scripts/occupancy_soak.py \
	  --slots 48 --duration 60 --min-occupancy 0.8 \
	  --out perf/occupancy_soak_$$(date -u +%Y%m%d_%H%M%S).json

# Replica failover drill (ISSUE 9): Poisson load at 2 replicas, one
# replica killed mid-run via targeted fault injection — gates zero
# failed RPCs, token-complete streams, bounded p95 TTFT inflation, and
# recovery to full SERVING capacity. Artifact to /tmp so CI runs never
# dirty the repo.
failover-smoke: ## Kill-one-replica drill at CI scale (2 replicas, 10 s)
	JAX_PLATFORMS=cpu $(PYTHON) scripts/failover_soak.py \
	  --replicas 2 --duration 10 --out /tmp/failover_smoke.json

failover-soak: ## The 3-replica / 30 s acceptance drill (writes perf/)
	JAX_PLATFORMS=cpu $(PYTHON) scripts/failover_soak.py \
	  --replicas 3 --duration 30 \
	  --out perf/failover_soak_$$(date -u +%Y%m%d_%H%M%S).json

# Disaggregated-tier drill (ISSUE 13): real worker PROCESSES over
# localhost, a prefill worker killed mid-handoff + a decode worker
# killed mid-stream — gates zero failed RPCs, token-complete streams,
# and greedy streams bit-identical to a single-process reference run.
# Smoke scale (2 prefill + 1 decode) for CI; the acceptance artifact
# comes from disagg-soak (2x2, both kills, longer window).
# ISSUE 14 rides along twice: the drill itself runs the CL005
# protocol-conformance check before spawning, and the whole run executes
# under the runtime lock witness (POLYKEY_LOCK_WITNESS=1) — the observed
# acquisition-order edges from the coordinator + every worker process
# then merge into racelint's static lock graph, which must stay
# cycle-free (the zero-deadlock gate with real evidence).
disagg-smoke: ## Kill-workers drill at CI scale + lock-witness zero-cycle gate + heap-witness zero-growth gate + sched-witness zero-starvation gate
	rm -rf /tmp/polykey-lock-witness /tmp/polykey-heap-witness-disagg \
	  /tmp/polykey-sched-witness-disagg
	JAX_PLATFORMS=cpu POLYKEY_LOCK_WITNESS=1 \
	  POLYKEY_LOCK_WITNESS_OUT=/tmp/polykey-lock-witness \
	  POLYKEY_HEAP_WITNESS=1 \
	  POLYKEY_HEAP_WITNESS_OUT=/tmp/polykey-heap-witness-disagg \
	  POLYKEY_SCHED_WITNESS=1 \
	  POLYKEY_SCHED_WITNESS_OUT=/tmp/polykey-sched-witness-disagg \
	  $(PYTHON) scripts/failover_soak.py --disagg \
	  --prefill 2 --decode 1 --duration 10 \
	  --out /tmp/disagg_smoke.json
	$(PYTHON) -m polykey_tpu.analysis race --only CL001 \
	  --witness /tmp/polykey-lock-witness
	$(PYTHON) -m polykey_tpu.analysis mem --only ML006 \
	  --witness /tmp/polykey-heap-witness-disagg
	$(PYTHON) -m polykey_tpu.analysis sched --only SL006 \
	  --witness /tmp/polykey-sched-witness-disagg

# Cross-process black boxes (ISSUE 16): reconstruct the last seconds
# before any member death from the checkpoints in a disagg state dir —
# triage report + ONE merged clock-aligned Perfetto file.
#   make postmortem STATE_DIR=/tmp/polykey-disagg-xyz
postmortem: ## Triage a disagg state dir's black boxes (STATE_DIR=...)
	@test -n "$(STATE_DIR)" || { \
	  echo "usage: make postmortem STATE_DIR=<disagg state dir>"; exit 2; }
	$(PYTHON) -m polykey_tpu.obs.postmortem $(STATE_DIR)

# The crash-durability drill: SIGKILL a decode worker PROCESS
# mid-stream (os._exit flushes nothing), then require the surviving
# black boxes to reconstruct the death — fatal trace id in the dead
# incarnation's ring, triage report names it, merged Perfetto rows for
# every member. The victim stream itself must still finish (respawn +
# re-route), so the drill also re-pins the recovery path.
postmortem-smoke: ## Kill a decode worker mid-stream; black boxes must reconstruct the death
	JAX_PLATFORMS=cpu $(PYTHON) scripts/postmortem_smoke.py

# Autopilot drill (ISSUE 18): the closed control loop armed over a
# disaggregated pool, a 4x mid-run arrival ramp AND a decode-worker
# SIGKILL — the controller (tier scale-up + knob actuations, every one
# a typed autopilot_decision timeline event) plus the pool's own
# supervision must recover p95 TTFT to within tolerance of the
# pre-ramp baseline with zero failed RPCs and ZERO human intervention.
# Smoke scale runs under the heap + starvation witnesses and finishes
# with the five-tier `analysis all` gate (zero blocking findings).
autopilot-smoke: ## Ramp+SIGKILL drill at CI scale, controller-only recovery + analysis-all gate + heap-witness gate + sched-witness gate
	rm -rf /tmp/polykey-heap-witness-autopilot \
	  /tmp/polykey-sched-witness-autopilot
	JAX_PLATFORMS=cpu \
	  POLYKEY_HEAP_WITNESS=1 \
	  POLYKEY_HEAP_WITNESS_OUT=/tmp/polykey-heap-witness-autopilot \
	  POLYKEY_SCHED_WITNESS=1 \
	  POLYKEY_SCHED_WITNESS_OUT=/tmp/polykey-sched-witness-autopilot \
	  $(PYTHON) scripts/autopilot_soak.py \
	  --prefill 1 --decode 1 --baseline-s 12 --ramp-s 35 --tail-s 10 \
	  --max-p95-added-ms 45000 \
	  --out /tmp/autopilot_smoke.json
	$(PYTHON) -m polykey_tpu.analysis all
	$(PYTHON) -m polykey_tpu.analysis mem --only ML006 \
	  --witness /tmp/polykey-heap-witness-autopilot
	$(PYTHON) -m polykey_tpu.analysis sched --only SL006 \
	  --witness /tmp/polykey-sched-witness-autopilot

autopilot-soak: ## The 1+1 -> scaled / 65 s acceptance drill (writes perf/)
	JAX_PLATFORMS=cpu $(PYTHON) scripts/autopilot_soak.py \
	  --prefill 1 --decode 1 \
	  --out perf/autopilot_soak_$$(date -u +%Y%m%d_%H%M%S).json

disagg-soak: ## The 2x2-worker / 30 s acceptance drill (writes perf/)
	rm -rf /tmp/polykey-lock-witness
	JAX_PLATFORMS=cpu POLYKEY_LOCK_WITNESS=1 \
	  POLYKEY_LOCK_WITNESS_OUT=/tmp/polykey-lock-witness \
	  $(PYTHON) scripts/failover_soak.py --disagg \
	  --prefill 2 --decode 2 --duration 30 \
	  --out perf/disagg_soak_$$(date -u +%Y%m%d_%H%M%S).json
	$(PYTHON) -m polykey_tpu.analysis race --only CL001 \
	  --witness /tmp/polykey-lock-witness \
	  --dump-graph perf/lock_witness_$$(date -u +%Y-%m-%d).json

print-chaos: ## Print the chaos test file list (CI's single source of truth)
	@echo $(CHAOS_TESTS)

kernel-check: ## Compile + compare every Pallas kernel on the attached TPU
	$(PYTHON) scripts/tpu_kernel_check.py

chip-smoke: ## Gateway -> engine on the attached TPU, once (CPU rehearsal: chip_smoke.py --tiny)
	$(PYTHON) chip_smoke.py

dryrun: ## Compile-check the multi-chip sharded step on a virtual mesh
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  $(PYTHON) scripts/dryrun_multichip.py

multiproc-demo: ## 2-process jax.distributed train+serve on localhost CPU
	bash scripts/run_multiproc_demo.sh

# -- local CI reproduction (reference Makefile:217-308 scan/ci-check family) --
.PHONY: lint polylint graphlint racelint memlint schedlint native-asan scan ci-check

lint: ## Lint: ruff (pinned ruff.toml, same config as CI) + polylint
	@if command -v ruff >/dev/null 2>&1; then \
	  ruff check polykey_tpu/ tests/ scripts/; \
	else \
	  echo "ruff not installed (CI pins ruff==0.12.5); falling back to a syntax gate"; \
	  $(PYTHON) -m compileall -q polykey_tpu/ tests/ scripts/; \
	fi
	@$(MAKE) polylint

polylint: ## Project-invariant static analysis (stdlib-only, always runs)
	$(PYTHON) -m polykey_tpu.analysis

# The third analysis tier (ISSUE 14): concurrency & cross-process
# protocol contracts — interprocedural lock-order cycles (CL001),
# unguarded shared state (CL002), lock-scope escapes (CL003),
# blocking-under-lock across call boundaries (CL004), and the disagg
# coordinator/worker + KV-wire protocol conformance (CL005). Stdlib-only
# AST like polylint; the runtime lock witness rides disagg-smoke.
racelint: ## Concurrency & protocol contract analysis (stdlib-only)
	$(PYTHON) -m polykey_tpu.analysis race

# The second analysis tier (ISSUE 5): traces the real engine/model step
# functions on a CPU backend and verifies compiled-graph contracts —
# recompile stability (GL001), donation aliasing (GL002), dtype policy
# (GL003), host-transfer discipline (GL004), kernel block/sharding
# layout (GL005). ~1-2 min: it compile-warms two tiny engines.
graphlint: ## Compiled-graph contract analysis (CPU-backed; ~1-2 min)
	JAX_PLATFORMS=cpu $(PYTHON) -m polykey_tpu.analysis graph

# The fourth analysis tier (ISSUE 17): memory & capacity contracts —
# the analytic byte ledger vs ChipSpec.hbm_bytes across the served
# matrix (ML001), unbounded container growth (ML002), and the
# POLYKEY_* knob contracts: documented (ML003), single parse site
# (ML004), shipped to disagg workers (ML005). Stdlib-only AST + pure
# arithmetic; the runtime heap witness (ML006) rides hostkv-smoke and
# disagg-smoke.
memlint: ## Memory & capacity contract analysis (stdlib-only)
	$(PYTHON) -m polykey_tpu.analysis mem

# The fifth analysis tier (ISSUE 20): scheduler liveness & fairness
# contracts — progress floors on budget-bounded dispatch loops (SL001),
# round-robin cursor discipline with starved-first re-anchoring
# (SL002), restore→prefill→decode frontier ordering (SL003), and
# bounded-wait queues (SL004).
# Stdlib-only AST; the runtime starvation witness (SL006) rides
# occupancy-smoke, disagg-smoke, and autopilot-smoke.
schedlint: ## Scheduler liveness & fairness contract analysis (stdlib-only)
	$(PYTHON) -m polykey_tpu.analysis sched

ASAN_FLAGS := -g -O1 -fsanitize=address,undefined -fno-omit-frame-pointer

native-asan: ## Build the log beautifier under ASan/UBSan and smoke-run it
	@mkdir -p $(BUILD_DIR)/asan
	$(CXX) -std=c++17 -Wall -Wextra $(ASAN_FLAGS) \
	  -o $(BUILD_DIR)/asan/log-beautifier native/log_beautifier.cc
	@printf '%s\n' \
	  '{"time":"2026-08-03T00:00:00Z","level":"INFO","msg":"gRPC call received","method":"/polykey.v2.PolykeyService/ExecuteTool","trace_id":"smoke1"}' \
	  '{"time":"2026-08-03T00:00:01Z","level":"INFO","msg":"gRPC call finished","method":"/polykey.v2.PolykeyService/ExecuteTool","duration":"12.3ms","code":"OK","trace_id":"smoke1"}' \
	  'compose-prefix | {"time":"2026-08-03T00:00:02Z","level":"ERROR","msg":"gRPC call finished","method":"/x/Y","duration":"1ms","code":"Internal"}' \
	  'not json at all' \
	  '{"broken":' \
	  | $(BUILD_DIR)/asan/log-beautifier >/dev/null
	@echo "native-asan OK"

scan: ## Security scan (Trivy fs over the tree + lockfile, CRITICAL/HIGH gate)
	@if ! command -v trivy >/dev/null 2>&1; then \
	  echo "Trivy not found. Install: https://aquasecurity.github.io/trivy"; \
	  echo "(CI additionally image-scans the published container in .github/workflows/ci.yml)"; \
	  exit 2; \
	fi
	@mkdir -p .trivy-cache
	TRIVY_CACHE_DIR=.trivy-cache trivy fs . \
	  --format table \
	  --exit-code 1 \
	  --skip-dirs .trivy-cache \
	  --scanners vuln,secret \
	  --severity CRITICAL,HIGH

ci-check: ## Run the CI pipeline locally: lint+polylint+racelint+graphlint+memlint+schedlint, chaos, failover, disagg(+lock/heap/sched-witness gates), postmortem, occupancy(+sched-witness gate), spec, hostkv(+heap-witness gate), autopilot(+analysis-all gate), obs, tests, native(+asan), scan
	@$(MAKE) lint
	@$(MAKE) racelint
	@$(MAKE) graphlint
	@$(MAKE) memlint
	@$(MAKE) schedlint
	@$(MAKE) chaos-smoke
	@$(MAKE) failover-smoke
	@$(MAKE) disagg-smoke
	@$(MAKE) postmortem-smoke
	@$(MAKE) occupancy-smoke
	@$(MAKE) spec-smoke
	@$(MAKE) hostkv-smoke
	@$(MAKE) autopilot-smoke
	@$(MAKE) obs-smoke
	@$(MAKE) test
	@$(MAKE) native
	@$(MAKE) native-asan
	@# Probe trivy here, not via scan's exit code: make launders any
	@# recipe failure to exit 2, so findings and tool-missing would be
	@# indistinguishable through $(MAKE) scan's status.
	@if command -v trivy >/dev/null 2>&1; then \
	  $(MAKE) scan || { echo "scan FAILED: Trivy reported CRITICAL/HIGH findings"; exit 1; }; \
	else \
	  echo "scan SKIPPED: Trivy not installed locally (CI's image-scan gate still applies)"; \
	fi
	@echo "ci-check done"

clean: ## Remove build artifacts and caches
	rm -rf $(BUILD_DIR) .pytest_cache .trivy-cache .jax_cache chiprun_out
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true

# -- container lifecycle (reference Makefile:126-172 compose family) ---------
.PHONY: docker-build docker-test compose-up compose-down compose-logs compose-client health-probe

docker-build: ## Build the production image
	docker build --target production -t polykey-tpu:latest .

docker-test: ## Run the test suite inside the tester image
	docker build --target tester -t polykey-tpu-tester . && docker run --rm polykey-tpu-tester

compose-up: ## Start the server stack (POLYKEY_BACKEND=tpu for the engine)
	docker compose up -d polykey-server

compose-down: ## Stop and remove the stack
	docker compose down -v

compose-logs: $(if $(filter true,$(b)),$(BUILD_DIR)/log-beautifier,) ## Tail server logs through the C++ beautifier (b=true)
	docker compose logs -f polykey-server $(if $(filter true,$(b)),| $(BUILD_DIR)/log-beautifier,)

compose-client: ## Run the containerized dev client against the server
	docker compose run --rm polykey-dev-client

health-probe: ## Probe a running server's gRPC health (ADDR=localhost:50051)
	$(PYTHON) -m polykey_tpu.gateway.health $(or $(ADDR),localhost:50051)
