#!/usr/bin/env bash
# Tier-1 verification gate: the driver's own tier-1 command (`commands` in
# /root/TESTS_LAST_RUN.json: six workers, a file the unit xdist deals, limit
# 1470 s; ROADMAP.md's "Tier-1 verify" line is the one-core form), failing in
# addition on any pytest collection error: regressions like the
# `from jax import shard_map` import break (which silently dropped 2 test
# files from collection at seed) must be caught pre-merge, not by the next
# round's driver. Then what the suite does not run: dslint over the default
# target, the schedule matrix, the smoke scripts, ruff where installed.
#
# Usage: scripts/verify_tier1.sh   (from anywhere; cd's to the repo root)
set -u
cd "$(dirname "$0")/.."
# this run's logs: a directory of its own under TMPDIR, so that two checkouts
# running the gate at once do not write each other's
LOGS=$(mktemp -d "${TMPDIR:-/tmp}/verify_tier1.XXXXXX")

# --- the driver's tier-1 command ------------------------------------------
set -o pipefail; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile -p no:randomly 2>&1 | tee "$LOGS"/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOGS"/_t1.log | tr -cd . | wc -c)
# --------------------------------------------------------------------------

# Collection errors render as "ERROR tests/<file>.py" in the short summary
# and "N errors" in the tail line; either one fails the gate even when the
# exit code is masked by --continue-on-collection-errors + timeout.
if grep -aqE '^ERROR[[:space:]]+tests/' "$LOGS"/_t1.log; then
    echo "verify_tier1: FAIL — collection errors:" >&2
    grep -aE '^ERROR[[:space:]]+tests/' "$LOGS"/_t1.log >&2
    exit 1
fi
if grep -aqE 'errors? during collection' "$LOGS"/_t1.log; then
    echo "verify_tier1: FAIL — errors during collection" >&2
    exit 1
fi

# A timeout kill (rc 124) is a budget condition, not a collection regression;
# surface it distinctly so the caller can tell the two apart.
if [ "$rc" -eq 124 ]; then
    echo "verify_tier1: suite hit the 1470s tier-1 budget (rc=124); no" \
         "collection errors detected in the portion that ran" >&2
fi

# --- static analysis gate (docs/STATIC_ANALYSIS.md) -----------------------
# dslint over the default target: traces the engine's fused train
# program (no execution) and exits 2 on ERROR-severity findings — the
# sharding/precision/collective/config regressions that would otherwise
# surface as burned TPU-hours.
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
        XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        python -m deepspeed_tpu.analysis > "$LOGS"/_t1_dslint.log 2>&1; then
    echo "verify_tier1: FAIL — dslint reported ERROR findings (or crashed):" >&2
    tail -40 "$LOGS"/_t1_dslint.log >&2
    exit 1
fi

# --- pipeline-schedule gate (docs/STATIC_ANALYSIS.md "Pipeline schedules")
# the dslint pipe/* gate: prove the shipped 1F1B/interleaved/zero-bubble
# generators over the schedule matrix and report static bubble % — exits 2
# if any generated schedule is rejected by its own prover.
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
        python -m deepspeed_tpu.analysis --schedules \
        > "$LOGS"/_t1_schedules_cli.log 2>&1; then
    echo "verify_tier1: FAIL — pipeline-schedule prover gate" \
         "(python -m deepspeed_tpu.analysis --schedules):" >&2
    tail -30 "$LOGS"/_t1_schedules_cli.log >&2
    exit 1
fi

# --- serving gate (docs/SERVING.md) ---------------------------------------
# the CPU-fallback scheduler smoke: admit/evict/finish a mixed-length
# request stream end to end (paged prefill/decode, preemption, eos,
# greedy-equivalence vs generate) — the serving contract in one script.
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
        python scripts/serving_smoke.py > "$LOGS"/_t1_serving_smoke.log 2>&1; then
    echo "verify_tier1: FAIL — serving smoke (scripts/serving_smoke.py):" >&2
    tail -30 "$LOGS"/_t1_serving_smoke.log >&2
    exit 1
fi
grep -a "serving_smoke: PASS" "$LOGS"/_t1_serving_smoke.log || true

# the prefix-caching smoke (docs/SERVING.md "KV quantization & prefix
# caching"): a shared-system-prompt stream through the copy-on-write
# prefix cache — physical pages < sum of logical pages, greedy outputs
# generate-identical, refcount audit clean after the drain.
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
        python scripts/serving_smoke.py --prefix \
        > "$LOGS"/_t1_serving_prefix.log 2>&1; then
    echo "verify_tier1: FAIL — serving prefix-cache smoke" \
         "(scripts/serving_smoke.py --prefix):" >&2
    tail -30 "$LOGS"/_t1_serving_prefix.log >&2
    exit 1
fi
grep -a "serving_smoke\[prefix\]: PASS" "$LOGS"/_t1_serving_prefix.log || true

# the speculative-decoding smoke (docs/SERVING.md "Speculative decoding"):
# both drafters against the real engine — >= 1 full-reject window (n-gram
# on random history) and >= 1 full-accept window (draft == target), greedy
# outputs generate-IDENTICAL under both, page audit clean.
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
        python scripts/serving_smoke.py --spec \
        > "$LOGS"/_t1_serving_spec.log 2>&1; then
    echo "verify_tier1: FAIL — speculative-decoding smoke" \
         "(scripts/serving_smoke.py --spec):" >&2
    tail -30 "$LOGS"/_t1_serving_spec.log >&2
    exit 1
fi
grep -a "serving_smoke\[spec\]: PASS" "$LOGS"/_t1_serving_spec.log || true

# the serving chaos smoke (docs/SERVING.md "Overload & failure"): one
# injected dispatch-failure episode (preempt-and-requeue heal) and one
# deadline expiry against the REAL engine, asserting generate-identical
# outputs and a clean page-conservation audit after each recovery.
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
        python scripts/serving_smoke.py --chaos \
        > "$LOGS"/_t1_serving_chaos.log 2>&1; then
    echo "verify_tier1: FAIL — serving chaos smoke" \
         "(scripts/serving_smoke.py --chaos):" >&2
    tail -30 "$LOGS"/_t1_serving_chaos.log >&2
    exit 1
fi
grep -a "serving_smoke\[chaos\]: PASS" "$LOGS"/_t1_serving_chaos.log || true

# the fleet failover smoke (docs/SERVING.md "Fleet"): two real-engine
# replica PROCESSES behind the router, one SIGKILL'd mid-stream — the
# dead replica's requests must re-route to the survivor with kept tokens,
# finish generate-identical, and leave the survivor's page audit clean.
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
        python scripts/serving_smoke.py --fleet \
        > "$LOGS"/_t1_serving_fleet.log 2>&1; then
    echo "verify_tier1: FAIL — serving fleet smoke" \
         "(scripts/serving_smoke.py --fleet):" >&2
    tail -30 "$LOGS"/_t1_serving_fleet.log >&2
    exit 1
fi
grep -a "serving_smoke\[fleet\]: PASS" "$LOGS"/_t1_serving_fleet.log || true

# the disaggregated prefill/decode smoke (docs/SERVING.md "Tensor parallel
# & disaggregation"): a prefill-specialist and a decode-specialist worker
# process behind the role-aware router — every request prefills on one,
# hands its int8 KV pages off over the wire (ownership transfer), decodes
# on the other, generate-identical, with BOTH pools drained to zero.
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
        python scripts/serving_smoke.py --disagg \
        > "$LOGS"/_t1_serving_disagg.log 2>&1; then
    echo "verify_tier1: FAIL — serving disagg smoke" \
         "(scripts/serving_smoke.py --disagg):" >&2
    tail -30 "$LOGS"/_t1_serving_disagg.log >&2
    exit 1
fi
grep -a "serving_smoke\[disagg\]: PASS" "$LOGS"/_t1_serving_disagg.log || true

# the multi-tenancy smoke (docs/SERVING.md "Multi-tenancy & SLO tiers"):
# a 3-tier mixed-tenant stream with an injected noisy-neighbor batch
# flood — interactive/standard outputs generate-identical, >= 1 full
# brownout enter/exit cycle with every transition page-audited, the flood
# shed with typed verdicts but never fully starved, pools drained.
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
        python scripts/serving_smoke.py --tiers \
        > "$LOGS"/_t1_serving_tiers.log 2>&1; then
    echo "verify_tier1: FAIL — serving multi-tenancy smoke" \
         "(scripts/serving_smoke.py --tiers):" >&2
    tail -30 "$LOGS"/_t1_serving_tiers.log >&2
    exit 1
fi
grep -a "serving_smoke\[tiers\]: PASS" "$LOGS"/_t1_serving_tiers.log || true

# --- offload gate (docs/OFFLOAD.md) ---------------------------------------
# the offload smoke: streamed step == inline step bitwise, quantized-fetch
# ledger ratio, an injected DMA hang flagged as an offload_fetch stall, and
# SIGKILL mid host-shard flush -> committed-tag resume, bitwise step-exact.
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
        python scripts/offload_smoke.py > "$LOGS"/_t1_offload_smoke.log 2>&1; then
    echo "verify_tier1: FAIL — offload smoke (scripts/offload_smoke.py):" >&2
    tail -30 "$LOGS"/_t1_offload_smoke.log >&2
    exit 1
fi
grep -a "offload_smoke: PASS" "$LOGS"/_t1_offload_smoke.log || true

# --- elastic gate (docs/RESILIENCE.md "Elastic membership") ---------------
# the elastic device-loss smoke: SIGKILL one of four dp workers mid-run ->
# the agent relaunches at dp3 from the newest committed tag (budget-free
# membership change), the resharded run is bitwise-identical to a dp3 run
# resumed from the same anchor, and no data sample is dropped or replayed.
if ! timeout -k 10 420 env JAX_PLATFORMS=cpu \
        python scripts/elastic_smoke.py > "$LOGS"/_t1_elastic.log 2>&1; then
    echo "verify_tier1: FAIL — elastic smoke (scripts/elastic_smoke.py):" >&2
    tail -40 "$LOGS"/_t1_elastic.log >&2
    exit 1
fi
grep -a "elastic_smoke: PASS" "$LOGS"/_t1_elastic.log || true

# --- fault-injection smoke (docs/RESILIENCE.md) ---------------------------
# two heal cycles on the CPU mesh: SIGKILL mid-checkpoint + auto-resume
# (crash consistency), and injected NaN -> divergence rollback -> poisoned
# data-cursor skip -> rejoin (in-run health). Either contract regressing
# must fail the gate, not the next incident in production.
if ! timeout -k 10 420 env JAX_PLATFORMS=cpu \
        python scripts/chaos_smoke.py > "$LOGS"/_t1_chaos.log 2>&1; then
    echo "verify_tier1: FAIL — fault-injection smoke (kill/NaN heal cycles):" >&2
    tail -40 "$LOGS"/_t1_chaos.log >&2
    exit 1
fi
grep -a "chaos_smoke: PASS" "$LOGS"/_t1_chaos.log || true

# --- silent-data-corruption smoke (docs/RESILIENCE.md "Data integrity") ---
# a REAL bit flip in a cpu-offloaded optimizer shard must be detected and
# healed step-exact (rollback + replay, same final loss), and a flip in a
# prefix-shared KV page must be quarantined with borrowers re-prefilled to
# identical token streams — both on real engines, with clean runs raising
# zero sdc_detected events.
if ! timeout -k 10 420 env JAX_PLATFORMS=cpu \
        python scripts/chaos_smoke.py --sdc > "$LOGS"/_t1_sdc.log 2>&1; then
    echo "verify_tier1: FAIL — SDC smoke (scripts/chaos_smoke.py --sdc):" >&2
    tail -40 "$LOGS"/_t1_sdc.log >&2
    exit 1
fi
grep -a "chaos_smoke: PASS" "$LOGS"/_t1_sdc.log || true

# --- lint gate (ruff.toml: analysis subsystem + its tests) ----------------
# advisory where the interpreter lacks ruff (this image does not bundle it);
# CI lanes that have it get the real check.
if python -c "import ruff" 2>/dev/null || command -v ruff >/dev/null 2>&1; then
    if ! python -m ruff check deepspeed_tpu/analysis tests/test_analysis.py \
            2>/dev/null && ! ruff check deepspeed_tpu/analysis \
            tests/test_analysis.py; then
        echo "verify_tier1: FAIL — ruff findings in the analysis subsystem" >&2
        exit 1
    fi
else
    echo "verify_tier1: ruff not installed; lint gate skipped" >&2
fi

exit "$rc"
