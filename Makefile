# Developer entry points; the canonical pre-push gate is
# scripts/static_check.sh (lint + lockcheck-armed suites) and the
# tier-1 command in ROADMAP.md.

.PHONY: lint lint-locks lint-buf lint-fx test chaos chaos-concurrent chaos-fleet \
	chaos-restore chaos-scrub chaos-ec scrub-smoke static-check \
	clean-lint

# Cached SARIF lint over the whole tree (package + scripts/):
# all rule families, VL001-VL005 + VL105/VL106 + VL301 per-file + VL101-VL104
# interprocedural + VL201-VL205 shape/dtype abstract interpretation +
# VL401-VL404 static concurrency + VL501-VL505 buffer provenance +
# VL601-VL605 fault paths, no baseline. Warm runs re-analyze zero
# files; see docs/development.md.
lint:
	python -m volsync_tpu.analysis volsync_tpu/ scripts/ \
	    --no-baseline --format sarif --out lint.sarif --cache .lint-cache

# Just the static concurrency family (VL401-VL404), with the lock
# acquisition-order graph exported for inspection.
lint-locks:
	python -m volsync_tpu.analysis volsync_tpu/ scripts/ \
	    --no-baseline --select VL4 --dump-lock-graph lock-graph.json

# Just the buffer-provenance family (VL501-VL505), with the provenance
# graph (sanctioned sites, function summaries, arg->param flow edges)
# exported for inspection.
lint-buf:
	python -m volsync_tpu.analysis volsync_tpu/ scripts/ \
	    --no-baseline --select VL5 --dump-provenance provenance.json

# Just the fault-path family (VL601-VL605), with the effect graph
# (resolved laws, per-function effect/raise summaries, retry-policy
# edges) exported for inspection.
lint-fx:
	python -m volsync_tpu.analysis volsync_tpu/ scripts/ \
	    --no-baseline --select VL6 --dump-effects effects.json

test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
	    -p no:cacheprovider

# Chaos soak: backup -> restore over seeded fault schedules through the
# resilience layer, plus the fault-injected crash-at-op-N recovery
# scenarios (docs/robustness.md).
chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py \
	    tests/test_resilience.py tests/test_crash_recovery.py \
	    -q -m 'not slow' -p no:cacheprovider

# Multi-writer chaos acceptance (docs/robustness.md): 4 concurrent
# fenced writers + a two-phase pruner under the MW_SCHEDULES seeded
# fault/crash matrix in tests/test_chaos.py (crash at every new prune
# step boundary plus a forced double-takeover), ending in a clean
# check(read_data=True) and byte-identical restores, plus the
# single-writer two-phase manifest-boundary crashes and the
# multi-writer protocol unit suite.
chaos-concurrent:
	JAX_PLATFORMS=cpu python -m pytest \
	    "tests/test_chaos.py::test_chaos_multiwriter_prune" \
	    "tests/test_crash_recovery.py::test_two_phase_prune_crash_at_manifest_boundaries" \
	    tests/test_multiwriter.py \
	    -q -m 'not slow' -p no:cacheprovider

# Fleet replica drill (docs/service.md "Fleet operations"): 3 fenced
# mover replicas on one repository plus a CONTINUOUS GC service under
# the FLEET_SCHEDULES seeded fault matrix — kill-a-replica-mid-stream,
# a store partition, GC-writer crash — asserting failover completes
# every admitted job, the dead writer is fenced (StaleWriterError on
# its late publish), no live pack is swept, and the ending
# check(read_data=True) is clean; plus the fleet/GC/deadline unit
# suite.
chaos-fleet:
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet_chaos.py \
	    tests/test_fleet.py -q -m 'not slow' -p no:cacheprovider

# Restore-storm chaos drill (docs/robustness.md): N concurrent
# pipelined restores sharing one PackCache over seeded read-path fault
# schedules (transient, truncated reads, a store partition) — every
# destination byte-identical, each pack crossing the wire ~once for the
# whole storm (single-flight), and a crash mid-fetch leaving no partial
# file; plus the golden serial≡pipelined byte-identity suite.
chaos-restore:
	JAX_PLATFORMS=cpu python -m pytest tests/test_restore_chaos.py \
	    tests/test_restorepipe.py -q -m 'not slow' -p no:cacheprovider

# Silent-corruption defense, deterministic half (docs/robustness.md,
# "Silent corruption & scrub"): ScrubService heal/quarantine/backfill
# units, the serial≡device check(read_data=True) golden, and the
# `volsync scrub` exit-code contract — no seeded storms.
scrub-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_scrub_chaos.py \
	    -q -m 'not slow' -k "not chaos_" -p no:cacheprovider

# Bit-rot chaos drill (docs/robustness.md, "Silent corruption &
# scrub"): seeded bitflip schedules corrupt pack GET payloads under a
# live restore storm + scrub service + ContinuousGC + concurrent
# backup traffic — every drill ends quarantine-empty, check-clean and
# byte-identical (no single-copy corruption ever reaches a restored
# file); plus the read-repair suite riding test_restorepipe.py.
chaos-scrub:
	JAX_PLATFORMS=cpu python -m pytest tests/test_scrub_chaos.py \
	    tests/test_restorepipe.py -q -m 'not slow' -p no:cacheprovider

# Erasure-coded durability drill (docs/robustness.md, "Erasure coding
# & online repack"): the GF(2^8) Reed-Solomon kernel goldens
# (device ≡ NumPy oracle), EC-armed seal layout + any-k restores,
# heal-arm priority (mirror-first with exactly one GET, then stripe
# reconstruction, then quarantine below k), RepackService
# crash-at-every-boundary safety, and seeded vanish+bitflip storms
# under live backup/restore/repack/GC traffic — every drill ends
# quarantine-empty, check-clean, byte-identical.
chaos-ec:
	JAX_PLATFORMS=cpu python -m pytest tests/test_ec_chaos.py \
	    tests/test_rs.py -q -m 'not slow' -p no:cacheprovider

static-check:
	scripts/static_check.sh

clean-lint:
	rm -f lint.sarif .lint-cache lock-graph.json provenance.json \
	    effects.json
