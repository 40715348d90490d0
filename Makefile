# Developer/CI entry points. Tier-1 itself is driven by ROADMAP.md's
# pytest line; these targets cover the static-analysis side.

.PHONY: lint lint-sarif lint-dot lint-errorflow-dot lint-fix-baseline \
	test trace-demo chaos

# Full graftlint: every per-file rule plus BOTH interprocedural
# passes — concurrency (lock-order cycles, blocking-under-lock,
# unlocked collective dispatch) and errorflow (unchecked RPC replies,
# budgets minted in flight, unbounded blocking on ingress paths). Both
# models are cached on source mtimes
# (tools/graftlint/.{concurrency,errorflow}_cache.json, one shared
# invalidation path); per-phase wall time is recorded in
# summary.timings of the JSON so tier-1 budget creep is visible in CI
# artifacts (tests/test_lint_clean.py pins the warm run under 15s).
lint:
	@python -m tools.graftlint weaviate_tpu/ --format json

# SARIF 2.1.0 of the NEW violations — renders as code annotations in CI.
lint-sarif:
	@python -m tools.graftlint weaviate_tpu/ --format sarif

# The whole-program lock-order graph (graphviz); cycle edges are red.
# Recipes are @-silenced so the output pipes cleanly:
#   make lint-dot | dot -Tsvg > lock-order.svg
lint-dot:
	@python -m tools.graftlint weaviate_tpu/ --format dot

# The whole-program reply-taint graph (graphviz): RPC/blob/queue taint
# sources, the functions whose returns launder them, and the
# sanitizers that clear them (docs/lint.md "Error-path contracts"):
#   make lint-errorflow-dot | dot -Tsvg > reply-taint.svg
lint-errorflow-dot:
	@python -m tools.graftlint weaviate_tpu/ --format errorflow-dot

lint-fix-baseline:
	python -m tools.graftlint weaviate_tpu/ --fix-baseline

test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
		-p no:cacheprovider

# The chaos suite, slow soaks included: replica coordination under
# seeded drop/latency/partition faults, the elastic scale-out
# scenario (3->5 nodes under live ingest+search, donor killed
# mid-migration, crash-resume via the rebalance ledger), and the cold
# tier / cluster backup scenarios (kill mid-offload and mid-backup,
# bucket outages, 3-node backup restored into 5 nodes with zero lost
# acked writes), and the closed-loop autoscaling diurnal ramp (3->6->3
# under seeded faults with a leader killed between decision-journal
# and actuation). Runs under both runtime witnesses (conftest default):
# the session FAILS if any lock-order inversion or any serving-scope
# RPC with no live deadline is observed — zero violations is an
# asserted invariant of the chaos suite, not a hope.
chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_chaos_replication.py \
		tests/test_rebalance.py tests/test_coldtier_chaos.py \
		tests/test_autoscale.py \
		-q -p no:cacheprovider

# Boot a node on a loopback port, run a mixed search/ingest burst, and
# pretty-print the assembled trace tree from /v1/debug/traces — the
# quickest way to SEE what docs/tracing.md describes. Smoke-tested in
# tier-1 (tests/test_observability.py::test_trace_demo_smoke).
trace-demo:
	JAX_PLATFORMS=cpu python -m tools.trace_demo
