#!/usr/bin/env bash
# Local static-analysis + concurrency gate (docs/development.md).
#
#   1. `volsync lint` over the whole tree — the package and scripts/
#      — must be clean with no baseline, with every rule
#      family enabled: the per-file VL001-VL005 checks plus VL105
#      (ad-hoc retry sleeps outside resilience.py), VL106 (hot-path
#      byte copies outside the sanctioned copy-ledger sites) and VL301
#      (span names must be literal dotted lowercase), the interprocedural
#      VL101-VL104 family, the VL201-VL205
#      shape/dtype abstract interpreter, the VL401-VL404 static
#      concurrency family (lock-order cycle proofs, guarded-field race
#      inference, check-then-act, unsynchronized publication), and the
#      VL501-VL505 buffer-provenance family (implicit device->host
#      syncs, per-item dispatch loops, unledgered pooled copies,
#      use-after-donate, copy-ledger sanction drift), and the
#      VL601-VL605 fault-path family (unprotected network effects,
#      retry stacking, exception-taxonomy drift, fence-before-publish
#      dominance, declared crash orderings)
#      (tests/test_analysis.py enforces the same in tier-1). Emits a
#      SARIF 2.1.0 report to lint.sarif for CI upload — asserted to
#      carry the VL601-VL605 rule catalogue with its severity tiers —
#      and uses the content-hash incremental cache (.lint-cache): an
#      immediate second run ASSERTS the warm cache re-analyzes zero
#      files AND that the cache rows carry the "buf" provenance and
#      "fx" fault-path fact kinds, so the cached
#      lock/shape/provenance/effect summary plumbing can't silently
#      regress. `volsync lint --stats` then asserts the committed
#      suppression budget: the tree-wide count of `# lint: ignore`
#      pragmas may only grow with review (bump the budget here).
#   2. The pipeline + crash-recovery suites with the lock-order/race
#      detector armed at process start (VOLSYNC_TPU_LOCKCHECK=1), so
#      module-level locks are instrumented too.
#   3. The multi-writer chaos acceptance (`make chaos-concurrent`):
#      4 fenced concurrent writers + a two-phase pruner under the
#      seeded MW_SCHEDULES fault/crash matrix — crash at every prune
#      step boundary, forced double-takeover — always ending in a
#      clean check(read_data=True) with byte-identical restores
#      (docs/robustness.md, "Multi-writer protocol").
#   4. The fleet replica drill (`make chaos-fleet`): 3 fenced mover
#      replicas + a continuous GC service under the FLEET_SCHEDULES
#      seeded matrix — kill-a-replica-mid-stream, store partition,
#      GC-writer crash — failover completes every admitted job, the
#      dead writer's late publish is fenced, no live pack is swept
#      (docs/service.md, "Fleet operations").
#   5. The restore-storm chaos drill (`make chaos-restore`): the golden
#      serial≡pipelined byte-identity suite plus N concurrent restores
#      sharing one PackCache under seeded read-path faults — identical
#      trees, single-flight pack fetches, no partial file on a crashed
#      restore (docs/robustness.md, "Restore storms").
#   6. The scrub smoke (`make scrub-smoke`): ScrubService
#      heal/quarantine/backfill units, the serial≡device
#      check(read_data=True) golden, and the `volsync scrub` exit-code
#      contract (docs/robustness.md, "Silent corruption & scrub").
#   7. The bit-rot chaos drill (`make chaos-scrub`): seeded bitflip
#      schedules under a live restore storm + scrub + ContinuousGC +
#      concurrent backup — quarantine-empty, check-clean,
#      byte-identical restores, plus the read-repair suite
#      (docs/robustness.md, "Silent corruption & scrub").
#   8. The erasure-coding drill (`make chaos-ec`): RS kernel goldens,
#      EC-armed seal layout + any-k restores, heal-arm priority
#      (mirror-first, then stripe reconstruction, then quarantine),
#      RepackService crash-at-every-boundary safety, seeded
#      vanish+bitflip storms under live traffic (docs/robustness.md,
#      "Erasure coding & online repack").
#
# Run from the repo root before pushing data-plane changes.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== volsync lint =="
python -m volsync_tpu.analysis volsync_tpu/ scripts/ \
    --no-baseline --format sarif --out lint.sarif --cache .lint-cache

echo "== volsync lint (warm cache must re-analyze zero files) =="
warm=$(python -m volsync_tpu.analysis volsync_tpu/ scripts/ \
    --no-baseline --cache .lint-cache)
echo "$warm" | grep -q "cache: analyzed 0 of" || {
    echo "warm lint cache re-analyzed files on an unchanged tree:" >&2
    echo "$warm" >&2
    exit 1
}
python - <<'EOF'
import json, sys
rows = json.load(open(".lint-cache"))["files"]
if not any(row.get("buf") for row in rows.values()):
    sys.exit('lint cache rows carry no "buf" provenance facts — the '
             'VL5xx summary cache plumbing regressed')
if not any(row.get("fx") for row in rows.values()):
    sys.exit('lint cache rows carry no "fx" fault-path facts — the '
             'VL6xx summary cache plumbing regressed')
sarif = json.load(open("lint.sarif"))
rules = {r["id"]: r for r in
         sarif["runs"][0]["tool"]["driver"]["rules"]}
want = {"VL601": "error", "VL602": "error", "VL603": "warning",
        "VL604": "error", "VL605": "error"}
for code, level in want.items():
    got = rules.get(code, {}).get(
        "defaultConfiguration", {}).get("level")
    if got != level:
        sys.exit(f"lint.sarif rule {code}: level {got!r}, "
                 f"want {level!r} — the VL6xx severity tiers drifted")
EOF

echo "== volsync lint --stats (committed suppression budget) =="
stats=$(python -m volsync_tpu.analysis volsync_tpu/ scripts/ \
    --no-baseline --stats)
python - "$stats" <<'EOF'
import json, sys
stats = json.loads(sys.argv[1])
# The committed suppression budget: every `# lint: ignore` pragma in
# the tree is a reviewed one-off. New suppressions need review — bump
# this number in the same change that adds the pragma.
BUDGET = 75
total = stats["total_suppressions"]
if total > BUDGET:
    sys.exit(f"suppression budget exceeded: {total} `# lint: ignore` "
             f"pragmas in the tree, budget {BUDGET} — review the new "
             f"suppressions and bump BUDGET here if they stand")
if stats["total_findings"] or stats["errors"]:
    sys.exit(f"lint --stats reports {stats['total_findings']} "
             f"finding(s), {stats['errors']} error(s)")
EOF

echo "== lockcheck-armed pipeline suites =="
JAX_PLATFORMS=cpu VOLSYNC_TPU_LOCKCHECK=1 \
    python -m pytest tests/test_lockcheck.py tests/test_pipeline.py \
        tests/test_crash_recovery.py -q -p no:cacheprovider

echo "== chaos-concurrent =="
make --no-print-directory chaos-concurrent

echo "== chaos-fleet =="
make --no-print-directory chaos-fleet

echo "== chaos-restore =="
make --no-print-directory chaos-restore

echo "== scrub-smoke =="
make --no-print-directory scrub-smoke

echo "== chaos-scrub =="
make --no-print-directory chaos-scrub

echo "== chaos-ec =="
make --no-print-directory chaos-ec

echo "static_check: OK"
