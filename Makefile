# Developer entry points (the reference drives everything through
# per-component Makefiles; here one root Makefile covers the repo).

.PHONY: test test-slow test-all e2e smoke conformance bench bench-gate chip-smoke dryrun native verify-all obs-check profile-check serving-check fleet-check kernels-check tenancy-check chaos-check train-check train-obs-check disagg-check cache-check cache-tier-check control-check rollout-check scenario-check

verify-all:  ## the full evidence sweep, one command
	python -m pytest tests -q -m "slow or not slow"
	python e2e/run_e2e.py
	python deploy/smoke.py standalone
	python conformance/conformance.py
	python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
	python loadtest/loadtest.py --notebooks 200 --tpu 0
	python loadtest/serving_loadtest.py

test:        ## fast tier: compile-heavy tests deselected (<5 min)
	python -m pytest tests -q

test-slow:   ## the compile-heavy tier only (CI runs it on main)
	python -m pytest tests -q -m slow

test-all:    ## both tiers
	python -m pytest tests -q -m "slow or not slow"

e2e:         ## out-of-process platform lifecycle suite
	python e2e/run_e2e.py

smoke:       ## boot the platform from the shipped overlay + e2e
	python deploy/smoke.py standalone

conformance: ## capability certification checks
	python conformance/conformance.py

obs-check:   ## strict /metrics parse + /debug/traces gate on a live app
	python -m ci.obs_check

profile-check: ## step-anatomy gate: /debug/profile + zero-seeded phase/recompile families
	JAX_PLATFORMS=cpu python -m ci.obs_check profile

serving-check: ## CPU dense-oracle parity gate for the paged-KV serving path
	JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py \
	  tests/test_continuous.py tests/test_paged_kv.py \
	  tests/test_speculative.py tests/test_chunked_prefill.py \
	  tests/test_spec_paged.py -q -m "slow or not slow"

kernels-check: ## Pallas kernels vs XLA oracles, interpret mode, both tiers
	JAX_PLATFORMS=cpu python -m pytest tests/test_flash.py \
	  tests/test_decode_attention.py \
	  tests/test_paged_attention_kernel.py \
	  tests/test_prefill_append_kernel.py -q -m "slow or not slow"

fleet-check: ## fleet router gate: unit + migration suites + 2-replica routed loadtest
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py \
	  tests/test_migration.py -q -m "slow or not slow"
	JAX_PLATFORMS=cpu python loadtest/serving_loadtest.py --mode fleet \
	  --fleet-replicas 2 --clients 4 --requests 12 --max-new 8

chaos-check: ## fault-injection gate: migration parity suite + seeded chaos loadtest
	JAX_PLATFORMS=cpu python -m pytest tests/test_migration.py \
	  tests/test_fleet.py -q -m "slow or not slow"
	JAX_PLATFORMS=cpu python loadtest/serving_loadtest.py --mode chaos \
	  --clients 8 --requests 48 --max-new 16

train-check: ## elastic-training gate: resize/ZeRO/commit-marker suites + metric zero-seed check + trainer chaos loadtest
	JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py \
	  tests/test_checkpoint.py -q -m "slow or not slow"
	JAX_PLATFORMS=cpu python -m ci.obs_check train
	JAX_PLATFORMS=cpu python loadtest/serving_loadtest.py --mode train-chaos \
	  --train-replicas 2 --train-steps 8 --train-save-every 2

train-obs-check: ## training observatory gate: goodput ledger suite + federated /elastic/metrics conservation contract
	JAX_PLATFORMS=cpu python -m pytest tests/test_train_obs.py -q \
	  -m "slow or not slow"
	JAX_PLATFORMS=cpu python -m ci.obs_check train-obs

disagg-check: ## disaggregated prefill/decode gate: unit suite + pool metrics contract + A/B loadtest
	JAX_PLATFORMS=cpu python -m pytest tests/test_disagg.py \
	  tests/test_fleet.py -q -m "slow or not slow"
	JAX_PLATFORMS=cpu python -m ci.obs_check disagg
	JAX_PLATFORMS=cpu python loadtest/serving_loadtest.py --mode disagg \
	  --clients 12 --requests 48 --max-new 16

cache-check: ## KV-cache observatory gate: ledger/heat/counterfactual suite + cache metrics contract
	JAX_PLATFORMS=cpu python -m pytest tests/test_cachestats.py -q \
	  -m "slow or not slow"
	JAX_PLATFORMS=cpu python -m ci.obs_check cache

cache-tier-check: ## fleet cache-tier gate: spill/restore + peer-fetch suite + tier metrics contract
	JAX_PLATFORMS=cpu python -m pytest tests/test_cache_tier.py -q \
	  -m "slow or not slow"
	JAX_PLATFORMS=cpu python -m ci.obs_check cache-tier

control-check: ## closed-loop control gate: hysteresis/ledger/actuator suite + decision-plane metrics contract
	JAX_PLATFORMS=cpu python -m pytest tests/test_control.py -q \
	  -m "slow or not slow"
	JAX_PLATFORMS=cpu python -m ci.obs_check control

rollout-check: ## live-deployment gate: rollout suite + rollout-plane metrics contract + mid-flood roll/rollback loadtest
	JAX_PLATFORMS=cpu python -m pytest tests/test_rollout.py -q \
	  -m "slow or not slow"
	JAX_PLATFORMS=cpu python -m ci.obs_check rollout
	JAX_PLATFORMS=cpu python loadtest/serving_loadtest.py --mode rollout \
	  --clients 8 --requests 24 --max-new 8

scenario-check: ## scenario engine gate: trace/replay suite + record-replay contract + pathological scenarios vs the live fleet + recorded-replay fidelity
	JAX_PLATFORMS=cpu python -m pytest tests/test_scenarios.py -q \
	  -m "slow or not slow"
	JAX_PLATFORMS=cpu python -m ci.obs_check scenario
	JAX_PLATFORMS=cpu python loadtest/serving_loadtest.py --mode scenario \
	  --scenario loadtest/scenarios/flash_crowd.jsonl --scenario-target fleet
	JAX_PLATFORMS=cpu python loadtest/serving_loadtest.py --mode scenario \
	  --scenario loadtest/scenarios/abandon_retry.jsonl --scenario-target fleet
	JAX_PLATFORMS=cpu python loadtest/serving_loadtest.py --mode scenario \
	  --scenario loadtest/scenarios/tenant_flood.jsonl \
	  --scenario-max-batch 1 --scenario-fidelity-pct 10

tenancy-check: ## multi-tenant QoS gate: unit suite + noisy-neighbor A/B loadtest
	JAX_PLATFORMS=cpu python -m pytest tests/test_tenancy.py -q \
	  -m "slow or not slow"
	JAX_PLATFORMS=cpu python loadtest/serving_loadtest.py --mode tenants \
	  --tenant-bulk-clients 8 --tenant-live-requests 6

bench:       ## perf sweep on the device JAX attaches (one process; no fallback)
	python bench.py

chip-smoke:  ## TPU host only: kernels + serve + train at llama3-1b widths
	python chip_smoke.py

bench-gate:  ## perf sweep + regression compare vs ci/bench_baseline.json
	python bench.py --json-out /tmp/bench_run.json
	python -m ci.bench_gate /tmp/bench_run.json

dryrun:      ## multi-chip sharding compile gate (8 virtual devices)
	python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

native:      ## C++ data loader
	$(MAKE) -C native
