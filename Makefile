# The runnable test matrix (ref Makefile:3-27 build/test vs sbuild/stest;
# .github/workflows/ci.yml encodes the same legs for CI).
#
#   make test          sim suite, compiled C executor core (the default)
#   make test-nonative sim suite again with MADSIM_NO_NATIVE=1 (pure-Python
#                      loop; schedules must be byte-identical)
#   make test-real     real-mode legs only (asyncio + real sockets + grpcio
#                      wire + real fs/signal/process)
#   make test-procs    forked-process sweep smoke (fail-fast, jax guard)
#   make explore-smoke the explore pipeline end to end on a tiny budget
#                      (CPU backend, fixed campaign seed: find -> triage
#                      -> shrink against the amnesia raft target)
#   make oracle-smoke  the history-oracle pipeline end to end (seeded
#                      etcd bug -> linearizability checker -> triage ->
#                      shrink -> cross-path history byte identity)
#   make differential-smoke
#                      host<->device differential gate: matched
#                      (spec, seed) grids incl. every gray-failure
#                      family, outcome distributions within tolerances,
#                      both tiers' histories checked by one spec
#   make wire-smoke    heavy-traffic wire gate, both tiers: the sim-tier
#                      Kafka leg (concurrent genuine-protocol clients
#                      against the sim broker under a latency burst,
#                      LogSpec-checked history, live-vs-replay byte
#                      identity, differential-fuzz sweep) plus the async
#                      serving core's load rig at small scale (worker
#                      processes, kafka+s3+etcd wires, chaos mid-run,
#                      oracle-checked histories, async-vs-legacy
#                      transcript parity — docs/wire.md)
#   make multichip-smoke
#                      sharded checked-sweep pipeline on the CPU host
#                      mesh: device-count curve + a small sharded
#                      campaign, summary/report bytes asserted
#                      identical across mesh sizes
#   make stream-smoke  persistent streaming sweep service
#                      (docs/streaming.md): stream == chunked report
#                      bytes, refill-schedule invariance, v9
#                      interrupt/resume, zero-compile warmed stream
#   make obs-smoke     fleet telemetry (docs/observability.md): reports
#                      byte-equal with telemetry on/off, Perfetto trace
#                      with visible device/host overlap + stream refill
#                      cadence, run journal, live /metrics endpoint,
#                      device-side event-mix plane
#   make fleet-smoke   crash-safe fleet orchestrator (docs/fleet.md):
#                      shared corpus store across two processes ==
#                      solo bytes, strictly more fingerprints than
#                      either worker alone, kill -9 mid-append + lease
#                      reclaim, regression-replay gate
#   make steer-smoke   self-steering scheduler (docs/steering.md):
#                      bandit campaign report + decision trace replayed
#                      byte-identical (telemetry on/off), journaled
#                      steer_round mirror, and the adaptive-vs-uniform
#                      A/B at a matched device-event budget (>= 1.5x
#                      distinct fingerprints)
#   make stest         sim suite + determinism smoke gate (a fault-campaign
#                      sweep twice in two processes, traces byte-diffed;
#                      plus two campaign runs, JSONL reports byte-diffed;
#                      plus two history decodes, bytes diffed; plus the
#                      pipelined checked-sweep report across two
#                      processes x two worker-pool sizes AND two mesh
#                      sizes, byte-diffed)
#                      + explore-smoke + oracle-smoke + multichip-smoke
#                      + stream-smoke
#   make dryrun        multi-chip gate: 8-device mesh, sharded==unsharded
#                      and chunked==unsharded per-seed equality
#   make bench-smoke   the whole bench pipeline on tiny shapes (~1 min)
#   make test-all      every leg above, in order
#
# PYTEST_ARGS passes extra pytest flags to the suite legs, e.g.
#   make test PYTEST_ARGS="-k unix -v"

PY ?= python
PYTEST ?= $(PY) -m pytest
PYTEST_ARGS ?=

.PHONY: test test-nonative test-real test-procs stest determinism \
	explore-smoke oracle-smoke differential-smoke wire-smoke \
	multichip-smoke stream-smoke obs-smoke fleet-smoke steer-smoke \
	dryrun bench-smoke test-all

test:
	$(PYTEST) tests/ -q $(PYTEST_ARGS)

determinism:
	PY=$(PY) bash scripts/check_determinism.sh

# campaign seed 5 on purpose: tests/test_explore.py already runs the
# seed-1 campaign, so the gate explores a second mutation path instead
# of paying ~70 s to repeat the same deterministic computation
explore-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/explore_demo.py \
	  --rounds 6 --seeds-per-round 128 --campaign-seed 5 \
	  --assert-zero-recompile

# the history-oracle pipeline end to end (docs/oracle.md): seeded etcd
# stale-read bug -> WGL checker rejects -> history-flavor triage ->
# checker-verified shrink -> sweep/traced byte identity -> clean control;
# then the checked sweep once more through the on-device decode kernel
# (docs/oracle.md "Device-side checking")
oracle-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/oracle_demo.py
	JAX_PLATFORMS=cpu $(PY) scripts/checked_sweep_demo.py --seeds 96 \
		--chunk-size 32 --device-decode --report /dev/null

# host<->device differential gate (docs/faults.md "Gray failures"): a
# 200-seed matched-(spec, seed) grid per fault family — crash storm +
# asymmetric partitions + fsync-stall/power-fail + clock skew — outcome
# distributions within tolerances, election histories checked against
# one sequential spec on both tiers
differential-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/differential_demo.py

# the kafka wire under concurrent genuine-protocol load + fuzz
# (scripts/wire_load_demo.py docstring has the three determinism claims),
# then the async serving core's rig at small scale: worker processes x
# kafka+s3+etcd wires, gray failure mid-run, oracle-checked histories,
# replay identity, async-vs-legacy parity (scripts/wire_load.py --smoke)
wire-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/wire_load_demo.py
	$(PY) scripts/wire_load_demo.py --fuzz 12
	JAX_PLATFORMS=cpu $(PY) scripts/wire_load.py --smoke

# the sharded checked-sweep pipeline on the CPU host mesh
# (docs/multichip.md): device-count curve + small campaign, bytes
# asserted identical across mesh sizes
multichip-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/multichip_campaign.py --smoke

# the persistent streaming sweep service (docs/streaming.md): stream ==
# chunked bytes, refill-schedule invariance, v9 interrupt/resume,
# zero-compile warmed stream
stream-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/stream_smoke.py

# the fleet telemetry subsystem (docs/observability.md): out-of-band
# reports, Perfetto trace artifact, journal, exposition, event mix
obs-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/obs_smoke.py

# the crash-safe fleet orchestrator (docs/fleet.md): solo-vs-shared-store
# merged-report byte identity, two workers strictly beating either alone,
# kill -9 mid-append + lease reclaim, regression-replay gate
fleet-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/fleet_smoke.py

# the self-steering scheduler (docs/steering.md): replayed bandit
# campaign byte-identity (report + decision trace, telemetry on/off),
# the journal's steer_round mirror, and the matched-budget
# adaptive-vs-uniform fingerprint A/B
steer-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/steer_demo.py

stest: test determinism explore-smoke oracle-smoke differential-smoke \
	wire-smoke multichip-smoke stream-smoke obs-smoke fleet-smoke \
	steer-smoke

test-nonative:
	MADSIM_NO_NATIVE=1 $(PYTEST) tests/ -q $(PYTEST_ARGS)

test-real:
	$(PYTEST) tests/test_real.py tests/test_real_grpc.py \
	  tests/test_real_grpcio.py tests/test_real_etcd.py \
	  tests/test_real_kafka_s3.py tests/test_real_fs_signal.py \
	  tests/test_etcd_wire.py tests/test_s3_wire.py \
	  tests/test_kafka_wire.py tests/test_wire_differential.py \
	  -q $(PYTEST_ARGS)

test-procs:
	$(PYTEST) tests/test_builder.py -q -k procs $(PYTEST_ARGS)

dryrun:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

bench-smoke:
	$(PY) bench.py --smoke

test-all: test test-nonative test-real test-procs dryrun bench-smoke
	@echo "test matrix: ALL LEGS GREEN"
