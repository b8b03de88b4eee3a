# Single gate every PR runs. `make test` is the tier-1 command from
# ROADMAP.md (pytest.ini deselects `slow`-marked fuzz phases by default);
# `make test-all` runs everything including the slow phases;
# `make test-property` runs only the hypothesis property suites (their
# dedicated lane); `make test-churn` runs the membership/fault-injection
# conformance suite (pinned fast schedules + the slow hypothesis phase);
# `make test-read` runs the batched read-plane + read-repair suite
# (including its slow kernel/fuzz phases); `test-serving` runs the
# coalescing serving-plane suite (conformance + the slow scheduled-churn
# phase); `test-geo` runs the geo-replication tier (DC topology, HLC
# walls, causal snapshot plane, incl. its slow DC-partition fuzz phase);
# `test-faults` runs the fault-injection matrix + self-driving membership
# suite (pinned conformance lanes + the slow hypothesis phase);
# `test-durable` runs the segment-log durability suite (codec/segment
# units, warm-restart conformance, the crash-point fuzz incl. its slow
# every-extent sweep); `lint` is a dependency-free syntax/bytecode pass
# (the container has no flake8/ruff baked in).  The benchmark lives in
# `benchmarks/chip/` and runs on a TPU (README.md, "On a TPU").

PY := python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-all test-property test-churn test-read test-shard \
	test-serving test-geo test-faults test-durable lint check

test:
	$(PY) -m pytest -x -q

test-all:
	$(PY) -m pytest -q -m ""

test-property:
	$(PY) -m pytest -q -m property

test-churn:
	$(PY) -m pytest -q -m churn

test-read:
	$(PY) -m pytest -q -m read

test-shard:
	$(PY) -m pytest -q -m shard

test-serving:
	$(PY) -m pytest -q -m serving

test-geo:
	$(PY) -m pytest -q -m geo

test-faults:
	$(PY) -m pytest -q -m faults

test-durable:
	$(PY) -m pytest -q -m durable

lint:
	$(PY) -m compileall -q src tests benchmarks/chip examples
	$(PY) -m pyflakes src tests benchmarks/chip 2>/dev/null || true

check: lint test
