PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: test test-sched audit docs-check deprecated-check gateway-smoke \
  check

test:
	$(PYTHON) -m pytest -q

# Scheduler tier: the suites that are green and need only numpy/scipy
# (kernel tests additionally need the jax/pallas toolchain).
test-sched:
	$(PYTHON) -m pytest -q tests/test_executor.py tests/test_solvers.py \
	  tests/test_workflowbench.py tests/test_score_matrix_parity.py \
	  tests/test_delta_rescoring.py tests/test_shared_frontier.py \
	  tests/test_admission.py tests/test_preemption.py \
	  tests/test_scheduler_api.py tests/test_faults.py \
	  tests/test_recovery.py tests/test_pool_partition.py \
	  tests/test_batched_probe.py tests/test_scan_index.py \
	  tests/test_scale_stress.py tests/test_multiclass.py \
	  tests/test_routing.py tests/test_gateway.py \
	  tests/test_arrival_queue.py tests/test_pools_auto.py \
	  tests/test_event_stream_live.py tests/test_calibration.py

# Invariant auditor smoke: build a journaled chaos run in a temp dir,
# kill it mid-flight, restore from snapshot + journal replay, and
# assert the cross-structure invariants hold (tools/invariant_audit.py
# also audits archived SNAPSHOT.json / journal artifacts directly).
audit:
	$(PYTHON) tools/invariant_audit.py --self-test

# Docs gate: markdown link check over README.md/docs/ plus a
# pydocstyle-equivalent docstring lint on the documented-surface
# modules (offline container: no pydocstyle wheel, tools/docs_check.py
# implements the same checks on ast).
docs-check:
	$(PYTHON) tools/docs_check.py

# Gateway smoke: boot the asyncio HTTP gateway on an ephemeral port,
# submit one workflow over real HTTP, drain its NDJSON event stream,
# and exit nonzero if any event was dropped or the workflow never
# reached its terminal event (see serving/gateway.py --smoke).
gateway-smoke:
	$(PYTHON) -m repro.serving.gateway --smoke

# Deprecated-surface gate: fails if any in-repo caller still uses the
# policy_kwargs path outside the back-compat wrappers / parity tests
# (the typed SchedulerConfig is the supported surface).
deprecated-check:
	$(PYTHON) tools/check_deprecated.py

# CI gate: the scheduler tests, the docs gate and the
# deprecated-surface gate.  Speed is measured on the chip by bench/.
check: test-sched docs-check deprecated-check
