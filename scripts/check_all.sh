#!/usr/bin/env bash
# One-command validation of every robustness tier, in cost order:
#   unit/property/integration suite -> multichip dryrun -> fuzz
#   campaigns -> multi-process smoke (incl. leader failover) -> soaks.
# Roughly 20 minutes on one core. Any failing tier stops the run.
# Usage: bash scripts/check_all.sh [--quick]   (--quick trims campaign
# rounds and soak seconds for a ~6-minute pass)
set -euo pipefail
cd "$(dirname "$0")/.."

QUICK=${1:-}
ROUNDS=200; IROUNDS=500; DROUNDS=200; CROUNDS=3
export SOAK_SECONDS=${SOAK_SECONDS:-30}
if [ "$QUICK" = "--quick" ]; then
  # campaigns trim, but the soak floor stays 30s: the aggregator soak
  # needs enough wall time to close whole windows (it asserts so)
  ROUNDS=40; IROUNDS=100; DROUNDS=40; CROUNDS=1
fi

echo "== static analysis =="
# m3lint (m3_tpu/analysis): cache-key safety, JAX trace purity,
# whole-program lock discipline (cross-module ABBA), resource-lifecycle
# balance, batch-loop exception safety. Zero non-suppressed findings is
# the contract (also gated in-tree by tests/test_static_analysis.py).
# Process-parallel with a content-hash findings cache: warm runs are
# <0.5s, cold ~5s (--stats for the per-rule breakdown).
python -m m3_tpu.analysis --jobs 0 m3_tpu/

echo "== index microbench smoke (<5s; bitmap-vs-ref + cache hit-rate asserted) =="
# Array-native inverted index: bitmap kernels must agree with the
# set-algebra reference and the postings cache must serve the warm pass
# (full matrix: tests/test_index_property.py).
python scripts/index_smoke.py

echo "== block-cache smoke (<5s; warm hit-rate, eviction under tiny budget, zero residency after close) =="
# HBM-resident block cache: warm reads must hit, results must be
# bit-identical to the uncached decode, a tiny budget must evict, and
# namespace close must drop every cached byte. Full matrix:
# tests/test_block_cache.py. Wall budget via
# CACHE_SMOKE_BUDGET_S.
JAX_PLATFORMS=cpu python scripts/cache_smoke.py

echo "== codec-kernel smoke (<10s; Pallas route counters prove dispatch, pack/decode/hash bit-identical to XLA + ref_codec, kill switch routes back) =="
# The Pallas bitstream kernels (ops/pallas_codec.py) behind the
# M3_TPU_PALLAS gate: every kernel must actually dispatch (the
# telemetry.codec.pallas_* route counters move — a silent fallback
# passes parity while benchmarking the wrong code), outputs must be
# bit-identical to the XLA/numpy twins and the scalar reference codec,
# and =0 must route back to XLA. Full matrix: tests/test_codec_pallas.py;
# campaign: fuzz_codec under M3_TPU_PALLAS=1 adds the pallas packer to
# its parity set. Wall budget via CODEC_SMOKE_BUDGET_S (interpret-mode
# compiles dominate the cold run).
JAX_PLATFORMS=cpu python scripts/codec_smoke.py

echo "== chaos smoke (seeded faultnet, one scenario per layer) =="
# Resilience regressions (retry/breaker/deadline/dedup) fail HERE in
# seconds, not twenty minutes in; the full matrix is tests/test_resilience.py.
JAX_PLATFORMS=cpu python scripts/chaos_smoke.py --seed 7

echo "== overload smoke (<5s; seeded 3x overload, shed-by-priority asserted) =="
# Overload-protection regressions (query limits / admission control /
# typed ResourceExhausted / budget leaks) fail here in seconds; the full
# matrix is tests/test_overload.py. Wall budget via OVERLOAD_SMOKE_BUDGET_S.
JAX_PLATFORMS=cpu python scripts/overload_smoke.py --seed 7

echo "== write-path smoke (~5s; queue drain on shutdown, zero lost writes, mesh encode bit-equality) =="
# Insert-queue regressions (stranded queued writes, lost writes racing
# tick/seal, mesh-vs-single-device flush encode divergence) fail here in
# seconds; the full matrix is tests/test_write_path.py. Wall budget via
# WRITE_SMOKE_BUDGET_S.
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python scripts/write_smoke.py

echo "== churn smoke (SLO-under-churn: chaos + placement churn + concurrent repair, hard SLOs asserted) =="
# The composed production story (ROADMAP item 3): RF=3 cluster behind
# seeded faultnet proxies under seeded open-loop mixed-priority load
# WHILE add/remove/replace-node churn and a repair sweep run — zero lost
# acked writes, zero shed CRITICAL, bounded p99/queues, replica-
# consistent convergence. Full matrix: tests/test_dtest_scenarios.py +
# tests/test_bootstrap_repair.py. Wall budget via
# CHURN_SMOKE_BUDGET_S (first cold run pays one-time kernel compiles,
# persisted to .jax_cache for later runs).
JAX_PLATFORMS=cpu python scripts/churn_smoke.py --seed 7

echo "== lockdep witness (write+churn smoke under M3_TPU_LOCKDEP=1; zero cycles, witnessed edges ⊆ static graph ∪ reconciliation) =="
# Runtime lock-order witness (utils/lockdep.py): re-run the two most
# lock-contended smokes with every m3_tpu lock wrapped, record the
# process-wide acquisition-order graph + held-while-blocking edges,
# then assert (1) zero witnessed cycles and (2) every witnessed edge is
# derivable from the static cross-module lock graph
# (analysis/callgraph.py) or listed with a reason in
# m3_tpu/analysis/lockdep_reconcile.txt. Closes the loop between the
# analyzer's model and what the code actually does. Wall budget via
# LOCKDEP_SMOKE_BUDGET_S (feeds both smokes' own budgets).
( LOCKDEP_OUT=$(mktemp -d)
  trap 'rm -rf "$LOCKDEP_OUT"' EXIT  # cleanup on failure too (set -e)
  if [ -n "${LOCKDEP_SMOKE_BUDGET_S:-}" ]; then
    export WRITE_SMOKE_BUDGET_S="$LOCKDEP_SMOKE_BUDGET_S"
    export CHURN_SMOKE_BUDGET_S="$LOCKDEP_SMOKE_BUDGET_S"
  fi
  export M3_TPU_LOCKDEP=1 M3_TPU_LOCKDEP_OUT="$LOCKDEP_OUT"
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python scripts/write_smoke.py
  JAX_PLATFORMS=cpu python scripts/churn_smoke.py --seed 7
  unset M3_TPU_LOCKDEP
  python scripts/lockdep_check.py "$LOCKDEP_OUT" )

echo "== restart smoke (<10s; kill -9 a real dbnode mid-flush, restart, zero acked loss + bounded serving-ready) =="
# Crash-safe columnar recovery: a REAL dbnode child under seeded load
# is SIGKILLed mid-window (mediator flushing/snapshotting every 100ms),
# torn WAL tail + checkpoint-less fileset injected, restarted — every
# acked write must be served, nothing fabricated, restart bounded. Full
# matrix: tests/test_durability.py (+ migration/backfill variants);
# campaign: scripts/fuzz_durability.py. Wall
# budget via RESTART_SMOKE_BUDGET_S.
JAX_PLATFORMS=cpu python scripts/restart_smoke.py --seed 7

echo "== rules smoke (<5s; batch matcher ≡ per-metric oracle, 100% warm match-cache hits, standing recording+alert pipelines across two windows) =="
# The compiled streaming rules engine: seeded rule-set x metric-batch
# corpus through Downsampler.write_batch vs the retained write_ref
# oracle (bit-identical counters + flushed rows), warm (generation, id)
# match-memo hit rate with KV-update invalidation, and one recording +
# one alert rule evaluated incrementally on a live embedded coordinator
# with the firing transition asserted and recorded output queried back
# over the PromQL HTTP API. Full matrix: tests/test_batch_matcher.py +
# tests/test_rules_engine.py. Wall budget via
# RULES_SMOKE_BUDGET_S.
JAX_PLATFORMS=cpu python scripts/rules_smoke.py

echo "== diskfault smoke (<10s; seeded I/O faults on one replica: quarantine, scrub repair from peers, ENOSPC read-only + recovery, zero acked loss) =="
# The disk-fault plane: one RF=3 drill with the victim's persist tier
# behind a seeded testing/faultfs plan — serve-time row-checksum
# verification must quarantine every rotten fileset, the scrubber must
# repair from healthy peers and un-quarantine, ENOSPC must trip
# DiskHealth read-only (NORMAL sheds, CRITICAL + reads flow) and
# auto-recover, with zero acked-write loss and zero fabrication. Full
# matrix: tests/test_diskfault.py (4+ seeds); region-targeted bit-flip
# corpus: scripts/fuzz_durability.py. Wall budget via
# DISKFAULT_SMOKE_BUDGET_S (first cold run pays one-time kernel
# compiles, persisted to .jax_cache for later runs — override the
# budget on a cold tree).
JAX_PLATFORMS=cpu python scripts/diskfault_smoke.py --seed 7

echo "== computefault smoke (<10s; seeded device/kernel faults on the guarded routes: oracle equality, typed DEVICE_FAULT + quarantine, breaker trip + half-open recovery) =="
# The compute-fault plane: one seeded pass arms the testing/faultcomp
# dispatch seam over the real guarded routes (plan, agg-flush, codec)
# — every answer must stay oracle-equal under raises/OOMs/corrupt
# planes, the plan fallback must be typed DEVICE_FAULT scope=runtime
# with the shape bucket quarantined (no recompile crash-loop), a
# crash-looping route must trip its breaker OPEN and read as
# compute-degraded (never shedding) then recover through the half-open
# probe, and the decision log must replay from the pure seeded
# schedule. Full matrix: tests/test_compute_faults.py; per-kernel kill
# switches: tests/test_codec_pallas.py. Wall budget via
# COMPUTEFAULT_SMOKE_BUDGET_S.
JAX_PLATFORMS=cpu python scripts/computefault_smoke.py --seed 7

echo "== observability smoke (<10s; cross-process span tree, slow-query log, self-scrape PromQL round trip, jit telemetry) =="
# The tracing / /debug / self-scrape plane: one 2-node clustered run
# asserting a client->coordinator->dbnode span tree (>=3 hops, grafted
# server spans, per-span QueryScope costs), a slow-query entry with cost
# attribution, instrument counters queryable back via PromQL against the
# platform's own dbnodes, and non-empty jit-compile counters. Full
# matrix: tests/test_observability.py. Wall budget via OBS_SMOKE_BUDGET_S.
JAX_PLATFORMS=cpu python scripts/obs_smoke.py --seed 7

echo "== plan-compiler smoke (<5s; compiled-vs-oracle, 100% warm plan-cache hit, fallback exercised) =="
# Whole-plan pjit query execution: the compiled route must agree with
# the retained interpreter oracle (counter sums BIT-equal), every
# compilable query must actually compile (no silent fallback — incl.
# the round-16 families: subqueries, topk/quantile/stddev, group
# matching, irate/timestamp/quantile_over_time), the warm pass must be
# served 100% from the plan cache, and a set op must fall back cleanly.
# The 8-virtual-device mesh exercises the shard_map collective fan-in.
# Full matrix: tests/test_plan_compile.py.
# Wall budget via PLAN_SMOKE_BUDGET_S.
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python scripts/plan_smoke.py

echo "== serve smoke (<5s; columnar HTTP result frames byte-identical to render_result_ref, one compiled round-trip per round-16 lowering family) =="
# The columnar result plane: every response on /api/v1/query_range and
# /api/v1/query renders straight from the value matrix (query/render.py,
# zero per-series dicts) and must be byte-identical to the retained
# per-series oracle; one query per new lowering family must take the
# compiled route over real HTTP. Full matrix: tests/test_result_frame.py.
# Wall budget via SERVE_SMOKE_BUDGET_S.
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python scripts/serve_smoke.py

echo "== explain smoke (<5s; EXPLAIN route round-trip via /debug/explain, ?explain=true + ANALYZE stages beside data, mini-corpus coverage) =="
# The query observatory: a compiled query and a subquery fallback must
# round-trip GET /debug/explain with correct per-node routes (typed
# FallbackReason pinned on the raising node), ?explain=true must ride
# the explain payload beside the PromQL data with ANALYZE stage wall
# times, the reason-tagged telemetry.plan_fallback counters must move,
# and a recorded mini-corpus must yield a coverage number whose
# per-reason counts sum to the total (the scripts/coverage_report.py
# contract). Full matrix: tests/test_explain.py +
# tests/test_plan_compile.py::TestExplainCorpus. Wall budget via
# EXPLAIN_SMOKE_BUDGET_S.
JAX_PLATFORMS=cpu python scripts/explain_smoke.py

echo "== aggregator smoke (<5s; mesh-vs-ref bit-equality, one-publish-per-destination forwarding, tenant fair-share) =="
# The aggregator tier's columnar/mesh flush: the production path
# (collect_into + emit_batch + mesh quantile ordering) must emit
# BIT-identical rows to the retained host oracle (reduce_and_emit_ref)
# with the mesh program proven dispatched, a flush round must ride ONE
# publish per topic shard and ONE fbatch frame per (destination, meta
# group), and the DAGOR-style tenant gate must shed the noisy tenant at
# its share while quiet and CRITICAL traffic pass. Full matrix:
# tests/test_agg_mesh.py + tests/test_overload.py. Wall budget via
# AGG_SMOKE_BUDGET_S.
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python scripts/agg_smoke.py

echo "== numerics witness (plan+agg smokes under M3_TPU_NUMERICS=1; witnessed ⊆ static-accepted, padding lanes never finite) =="
# Runtime numerics witness (utils/numwatch.py): re-run the two
# kernel-heavy smokes with the jit-builder result observation points
# armed — every compiled plan's padded output plane and every
# aggregator quantile gather is checked (no finite value in a padding
# row, count-0 rows exactly zero, NaN/inf in live lanes only where the
# static numerics pass derives acceptance from the module ASTs:
# m3_tpu/analysis/numeric_rules.accepted_witness). Closes the
# static/runtime loop the lockdep tier closes for lock discipline.
# Wall budget via NUMERICS_SMOKE_BUDGET_S (feeds both smokes' budgets).
( NUM_OUT=$(mktemp -d)
  trap 'rm -rf "$NUM_OUT"' EXIT  # cleanup on failure too (set -e)
  if [ -n "${NUMERICS_SMOKE_BUDGET_S:-}" ]; then
    export PLAN_SMOKE_BUDGET_S="$NUMERICS_SMOKE_BUDGET_S"
    export AGG_SMOKE_BUDGET_S="$NUMERICS_SMOKE_BUDGET_S"
  fi
  export M3_TPU_NUMERICS=1 M3_TPU_NUMERICS_OUT="$NUM_OUT"
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python scripts/plan_smoke.py
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python scripts/agg_smoke.py
  unset M3_TPU_NUMERICS
  python scripts/numerics_check.py "$NUM_OUT" )

echo "== race witness (write+churn smokes under M3_TPU_RACEWATCH=1; cross-thread pairs ⊆ protection model ∪ lock-free ledger, vacuous pass refused) =="
# Runtime race witness (utils/racewatch.py): re-run the two most
# thread-crossing smokes with the registered shared-state attrs wrapped
# in recording descriptors (lockdep installed underneath for held-lock
# snapshots), then assert every witnessed cross-thread access pair with
# a write either shares a common held lock consistent with the static
# protection model (analysis/race_rules.protection_model) or is a
# declared lock-free protocol (analysis/lockfree_ledger.txt) — and
# refuse a vacuous pass (zero observed shared accesses fails). Closes
# the static/runtime loop for the concurrency plane, the same way the
# lockdep and numerics tiers do for lock order and numerics. Wall
# budget via RACE_SMOKE_BUDGET_S (feeds both smokes' budgets).
( RACE_OUT=$(mktemp -d)
  trap 'rm -rf "$RACE_OUT"' EXIT  # cleanup on failure too (set -e)
  if [ -n "${RACE_SMOKE_BUDGET_S:-}" ]; then
    export WRITE_SMOKE_BUDGET_S="$RACE_SMOKE_BUDGET_S"
    export CHURN_SMOKE_BUDGET_S="$RACE_SMOKE_BUDGET_S"
  fi
  export M3_TPU_RACEWATCH=1 M3_TPU_RACEWATCH_OUT="$RACE_OUT"
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python scripts/write_smoke.py
  JAX_PLATFORMS=cpu python scripts/churn_smoke.py --seed 7
  unset M3_TPU_RACEWATCH
  python scripts/race_check.py "$RACE_OUT" )

echo "== test suite =="
python -m pytest tests/ -x -q

echo "== multichip dryrun (virtual 8-device mesh) =="
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun OK')"

echo "== fuzz campaigns =="
JAX_PLATFORMS=cpu python scripts/fuzz_codec.py --rounds "$ROUNDS" --seed 7
python scripts/fuzz_index.py --rounds "$IROUNDS" --seed 7
python scripts/fuzz_durability.py --rounds "$DROUNDS" --seed 7
python scripts/fuzz_cluster.py --rounds "$CROUNDS" --ops 10 --seed 7

echo "== multi-process smoke =="
bash scripts/integration_smoke.sh

echo "== soaks =="
bash scripts/soak.sh
SOAK_TARGET=aggregator bash scripts/soak.sh

echo "ALL TIERS PASS"
