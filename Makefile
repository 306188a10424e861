# One-command verify recipes (CI + local).  .github/workflows/ci.yml runs
# exactly these targets, so CI and local invocations stay identical.
#
#   make test            docs-check + tier-1 suite (the ROADMAP verify command)
#   make docs-check      public-API docstring lint (tools/check_docstrings.py)
#   make test-interpret  kernel/engine suites with every op forced through
#                        the Pallas interpreter (REPRO_PALLAS_INTERPRET=1)
#   make test-dist       distributed plan-engine suite directly on 8 forced
#                        host devices (the tier-1 run covers the same thing
#                        through a subprocess launcher test)
#   make test-train      gradient-correctness tier (flash backward vs the
#                        naive oracle, grad accumulation, blockwise-parallel
#                        blocks vs monolithic)
#   make lint            byte-compile + import sanity (no external linters
#                        are installed in the container) + fails if any
#                        __pycache__/.pyc path is git-tracked
#
# `test` deliberately does NOT set REPRO_PALLAS_INTERPRET globally: model
# smoke tests validate the default dispatch (jnp oracle on CPU), and the
# kernel suites opt into interpret mode per-test via the pallas_interpret
# fixture.  `test-interpret` covers the force-everything configuration on
# the suites designed for it.
#
# The benchmark is not a make target: it runs on a TPU chip, one cell a
# process (`python3 bench/run.py --workload <cell> --seed <n> --seconds 51`,
# cells in BENCHMARK.json).

PYTHONPATH := src

.PHONY: test test-interpret test-dist test-serve test-train lint check docs-check

docs-check:
	python tools/check_docstrings.py

test: docs-check
	PYTHONPATH=$(PYTHONPATH) python -m pytest -x -q

test-interpret:
	PYTHONPATH=$(PYTHONPATH) REPRO_PALLAS_INTERPRET=1 python -m pytest -x -q \
		tests/test_kernels.py tests/test_plan_engine.py tests/test_substrate.py \
		tests/test_properties.py tests/test_stencil_engine.py

test-dist:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 REPRO_DIST_CHILD=1 \
		PYTHONPATH=$(PYTHONPATH) python -m pytest -x -q tests/test_dist_plan.py

test-serve:
	PYTHONPATH=$(PYTHONPATH) python -m pytest -x -q tests/test_serve_engine.py

test-train:
	PYTHONPATH=$(PYTHONPATH) python -m pytest -x -q tests/test_train_engine.py

lint:
	python -m compileall -q src tests examples
	PYTHONPATH=$(PYTHONPATH) python -c "import repro.core.rearrange, repro.core.plan, repro.core.tune, repro.kernels.ops, repro.tune"
	@tracked="$$(git ls-files | grep -E '(^|/)__pycache__/|\.pyc$$' || true)"; \
	if [ -n "$$tracked" ]; then \
		echo "lint: git-tracked bytecode (commit .gitignore'd files?):"; \
		echo "$$tracked"; exit 1; \
	fi

check: lint test
