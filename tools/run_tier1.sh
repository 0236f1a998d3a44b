#!/usr/bin/env bash
# Tier-1 verify: the command the driver runs after every PR (the
# `commands` of /root/TESTS_LAST_RUN.json: xdist, 6 workers, a file to a
# worker, 1470 s), so a local run counts what the gate counts.  The
# one-process line in ROADMAP.md ("Tier-1 verify", 870 s, -p no:xdist)
# has reached its end in no record of this round: ~80 minutes of cases.
# Fast tests only (-m 'not slow'); fault-injection and multi-process tests
# marked @pytest.mark.slow run in the full suite instead.  PR 45's run:
# see ROADMAP.md D0 for passed / wall / CPU-seconds and the rules that
# keep the suite inside its clock.
#
# Usage: tools/run_tier1.sh [extra pytest args...]
#        CHAOS=1 tools/run_tier1.sh   # also run the fault-matrix chaos
#                                     # suite (tools/chaos_run.sh) after
#        PERF=1 tools/run_tier1.sh    # also run the io_bench smoke lane
#                                     # (tiny synthetic imgbin, validates
#                                     # the per-stage JSON schema only —
#                                     # no flaky throughput assertions)
#        LOOP=1 tools/run_tier1.sh    # also run the closed-loop smoke:
#                                     # a real task=serve_train process,
#                                     # >=1k HTTP feedback records, the
#                                     # eval gate rejecting a poisoned
#                                     # update and publishing+reloading
#                                     # an improving one (JSON verdict)
#        TUNE=1 tools/run_tier1.sh    # also run the self-tuning smoke:
#                                     # io_bench + serve_bench --autotune
#                                     # start from deliberately bad knobs
#                                     # (1 worker / queue 1 / batch 1 /
#                                     # 1 ms window) and the controller
#                                     # must recover >= 90% of the hand-
#                                     # tuned throughput (JSON verdicts,
#                                     # schema-validated by the tools);
#                                     # both reports append to a
#                                     # perf_guard history
#        MESH=1 tools/run_tier1.sh    # also run the SPMD mesh parity
#                                     # lane: a 4-process CPU-mesh CLI
#                                     # train must produce checkpoint
#                                     # CRCs BITWISE equal to the
#                                     # single-process run of the same
#                                     # 4-device mesh (MNIST MLP conf,
#                                     # dist_shard=block, gloo
#                                     # collectives), with per-rank
#                                     # compile counts proving the step
#                                     # is ONE program (no per-replica
#                                     # re-jits); verdict JSON appends
#                                     # to a perf_guard history
#        QUANT=1 tools/run_tier1.sh   # also run the quantized-inference
#                                     # smoke: train + gated int8 export
#                                     # of the MNIST MLP (top-1 agreement
#                                     # >= 0.99 asserted), serve engine
#                                     # weight bytes >= 3.5x smaller via
#                                     # the serve_weight_bytes gauges,
#                                     # f32-vs-int8 closed-loop serve A/B
#                                     # (quant leg must not regress), and
#                                     # a quant_bench perf_guard entry
#        CRASH=1 tools/run_tier1.sh   # also run the crash-consistency
#                                     # audit: record every durable-write
#                                     # op sequence (checkpoint, publish
#                                     # pointer, feedback pages+commits,
#                                     # retention boundary) and replay
#                                     # EVERY crash-point prefix under the
#                                     # ext4-reorder model (flush/sync/
#                                     # torn variants) into a fresh dir,
#                                     # running the real recovery path and
#                                     # asserting the declared invariants
#                                     # (>=300 distinct states, zero
#                                     # violations) plus 5 named
#                                     # regression replays; the verdict
#                                     # appends to a perf_guard history
#                                     # (crash_audit flattener)
#        ELASTIC=1 tools/run_tier1.sh # also run the elastic-pod lane:
#                                     # a 4-process CPU-mesh CLI train
#                                     # has one NON-ZERO rank SIGKILLed
#                                     # mid-round; the survivors must
#                                     # rebuild as a 3-process mesh
#                                     # inside the same invocation, a
#                                     # waiting joiner grows it back to
#                                     # 4, and every checkpoint CRC
#                                     # must be BITWISE equal to a
#                                     # planned-resize run of the same
#                                     # shrink/grow schedule; rebuild
#                                     # latency + recovered throughput
#                                     # append to a perf_guard history;
#                                     # also runs the kill -9 crash-
#                                     # window check: rank 0 SIGKILLed
#                                     # between the consensus checkpoint
#                                     # tmp fsync and its rename — the
#                                     # torn tmp must be ignored and a
#                                     # continue=1 restart must resume
#                                     # from the prior CRC-valid round
#        FLEET=1 tools/run_tier1.sh   # also run the serving-fleet
#                                     # smoke: a REAL 2-replica
#                                     # task=serve fleet (CLI child
#                                     # processes) under open-loop
#                                     # burst load has one replica
#                                     # SIGKILLed mid-run — every
#                                     # non-shed request must still
#                                     # succeed, the supervisor must
#                                     # restart the dead replica in
#                                     # budget (JSON verdict via
#                                     # tools/fleet_smoke.py), plus a
#                                     # scaled-down in-process
#                                     # serve_bench --open-loop --burst
#                                     # profile; both land in a
#                                     # perf_guard history
#                                     # (fleet_bench / serve_bench)
#        WIRE=1 tools/run_tier1.sh    # also run the binary wire-format
#                                     # A/B: serve_bench --wire-ab
#                                     # drives JSON and CXB1-frame
#                                     # closed-loop legs over real HTTP
#                                     # (pooled keep-alive clients) and
#                                     # the binary plane must be
#                                     # >= 1.5x JSON req/s with BITWISE
#                                     # equal scores (doc/serving.md
#                                     # "Binary wire protocol"); the
#                                     # report appends to a perf_guard
#                                     # history (wire_bench flattener)
#        ASYNC=1 tools/run_tier1.sh   # also run the async data-parallel
#                                     # lane: a 4-process CPU-mesh CLI
#                                     # train with async_overlap=1,
#                                     # staleness=0 must write checkpoint
#                                     # CRCs BITWISE equal to the
#                                     # det_reduce synchronous run of the
#                                     # same conf/seed (the overlap is
#                                     # dispatch scheduling, not
#                                     # different arithmetic), plus a
#                                     # tiny staleness convergence A/B
#                                     # smoke (sync vs staleness=0 legs,
#                                     # schema-validated verdict JSON via
#                                     # tools/async_ab.py); the verdict
#                                     # appends to a perf_guard history
#                                     # (async_bench flattener:
#                                     # overlap_fraction higher-is-
#                                     # better, step_wall lower)
#        TENANT=1 tools/run_tier1.sh  # also run the multi-tenant loop
#                                     # smoke: a REAL task=loop_fleet
#                                     # process hosting 2 tenants on one
#                                     # device pool — per-model HTTP
#                                     # routing, a cohort-poisoned
#                                     # candidate rejected by the
#                                     # per-slice gate (cohort named,
#                                     # lineage-attributable), BOTH
#                                     # tenants publishing while the
#                                     # serve p99 alert stays silent,
#                                     # retention compacting consumed
#                                     # shards (disk bytes drop), and a
#                                     # kill -9 crash-window CRC check;
#                                     # verdict JSON appends to a
#                                     # perf_guard history (tenant_bench)
#        KERNEL=1 tools/run_tier1.sh  # also run the Pallas kernel-
#                                     # library lane: the interpret-mode
#                                     # parity suite (tests/
#                                     # test_kernels.py — all three
#                                     # kernels bit-equal to the jitted
#                                     # stock lowering on CPU) plus
#                                     # tools/kernel_ab.py --smoke (the
#                                     # bisect A/B end to end: parity
#                                     # gate, timed legs, schema-valid
#                                     # verdict JSON appended to a
#                                     # kernel_bench perf_guard history);
#                                     # the full-size CPU measurement +
#                                     # --record writes ops/kernels/
#                                     # verdicts.json; the TPU legs
#                                     # are ROADMAP S9
#        SDC=1 tools/run_tier1.sh     # also run the silent-data-
#                                     # corruption lane: a 4-process
#                                     # CPU-mesh CLI train has one real
#                                     # bit flipped in a live parameter
#                                     # tensor on rank 3; the fingerprint
#                                     # vote must detect it within
#                                     # integrity_every rounds, name the
#                                     # rank, quarantine it (exit 41) and
#                                     # rebuild in-process, and the
#                                     # finished run's checkpoint CRCs
#                                     # must be BITWISE equal to a clean
#                                     # run that never contained the
#                                     # corrupt rank; plus the serve
#                                     # golden-canary degrade/readmit
#                                     # walk and the <=2% fingerprint
#                                     # overhead bound; verdict JSON
#                                     # appends to a perf_guard history
#                                     # (integrity_bench flattener)
#        DSVC=1 tools/run_tier1.sh    # also run the data-service lane:
#                                     # a REAL task=data_service process
#                                     # feeds a CLI trainer whose data
#                                     # section is iter=service; its
#                                     # checkpoint CRCs must be BITWISE
#                                     # equal to the local-chain run,
#                                     # INCLUDING after the server is
#                                     # SIGKILLed mid-training and a
#                                     # replacement on the same port
#                                     # resumes the stream; two
#                                     # concurrent tenants must both
#                                     # hold parity with the shared
#                                     # chunk cache showing hit_rate > 0
#                                     # (tools/dataservice_smoke.py),
#                                     # plus the local-vs-service A/B
#                                     # (io_bench --service --smoke);
#                                     # both verdicts append to a
#                                     # perf_guard history
#                                     # (dataservice_bench flattener)
#        OBS=1 tools/run_tier1.sh     # also run the observability smoke:
#                                     # short telemetry=1 train + serve
#                                     # scrape of /metricsz + /alertz
#                                     # (alert fire/degrade/clear walked
#                                     # end to end), then schema-validate
#                                     # the exposition text (device-plane
#                                     # families pinned), alertz.json,
#                                     # telemetry.jsonl and events.jsonl
#                                     # via tools/obs_dump.py --check,
#                                     # plus a perf_guard --smoke verdict
set -o pipefail
cd "$(dirname "$0")/.."
t1=${TMPDIR:-/tmp}
rm -f "$t1/_t1.log" "$t1/_t1.xml"
timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
  python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
  --dist loadfile --junitxml="$t1/_t1.xml" -p no:randomly \
  "$@" 2>&1 | tee "$t1/_t1.log"
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$t1/_t1.log" | tr -cd . | wc -c)
if [ "${CHAOS:-0}" = "1" ]; then
  echo "=== opt-in chaos stage (CHAOS=1) ==="
  tools/chaos_run.sh || rc=1
fi
if [ "${PERF:-0}" = "1" ]; then
  echo "=== opt-in perf smoke (PERF=1) ==="
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/io_bench.py --smoke || rc=1
fi
if [ "${KERNEL:-0}" = "1" ]; then
  echo "=== opt-in Pallas kernel-library lane (KERNEL=1) ==="
  kernel_out=/tmp/_kernel_ab
  rm -rf "$kernel_out"; mkdir -p "$kernel_out"
  timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_kernels.py -q -m 'not slow' \
      -p no:cacheprovider -p no:xdist -p no:randomly || rc=1
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/kernel_ab.py --smoke \
      --history "$kernel_out/bench_history.jsonl" \
      --json "$kernel_out/kernel_ab.json" > /dev/null || rc=1
  echo "KERNEL lane verdict: $kernel_out/kernel_ab.json"
fi
if [ "${LOOP:-0}" = "1" ]; then
  echo "=== opt-in closed-loop smoke (LOOP=1) ==="
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/loop_smoke.py || rc=1
fi
if [ "${TUNE:-0}" = "1" ]; then
  echo "=== opt-in self-tuning smoke (TUNE=1) ==="
  tune_out=/tmp/_tune_smoke
  rm -rf "$tune_out"; mkdir -p "$tune_out"
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/io_bench.py 1024 160 --autotune \
      --json "$tune_out/io_autotune.json" || rc=1
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/serve_bench.py --autotune --autotune-seconds 20 \
      --json "$tune_out/serve_autotune.json" > /dev/null || rc=1
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench io_bench \
      --input "$tune_out/io_autotune.json" \
      --history "$tune_out/bench_history.jsonl" > /dev/null || rc=1
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench serve_bench \
      --input "$tune_out/serve_autotune.json" \
      --history "$tune_out/bench_history.jsonl" > /dev/null || rc=1
  echo "TUNE lane verdicts: $tune_out/{io,serve}_autotune.json"
fi
if [ "${MESH:-0}" = "1" ]; then
  echo "=== opt-in SPMD mesh parity lane (MESH=1) ==="
  mesh_out=/tmp/_mesh_parity
  rm -rf "$mesh_out"; mkdir -p "$mesh_out"
  # outer budget > 2x the tool's per-side --timeout (240 s each) plus
  # setup slack, so a slow-but-in-budget run is never killed mid-flight
  timeout -k 10 560 env JAX_PLATFORMS=cpu \
    python tools/mesh_parity.py --out "$mesh_out" > /dev/null || rc=1
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench mesh_parity \
      --input "$mesh_out/mesh_parity.json" \
      --history "$mesh_out/bench_history.jsonl" > /dev/null || rc=1
  echo "MESH lane verdict: $mesh_out/mesh_parity.json"
fi
if [ "${CRASH:-0}" = "1" ]; then
  echo "=== opt-in crash-consistency audit (CRASH=1) ==="
  crash_out=/tmp/_crash_audit
  rm -rf "$crash_out"; mkdir -p "$crash_out"
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/crash_audit.py --smoke \
      --out "$crash_out/crash_audit.json" || rc=1
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench crash_audit \
      --input "$crash_out/crash_audit.json" \
      --history "$crash_out/bench_history.jsonl" > /dev/null || rc=1
  echo "CRASH lane verdict: $crash_out/crash_audit.json"
fi
if [ "${ELASTIC:-0}" = "1" ]; then
  echo "=== opt-in elastic-pod lane (ELASTIC=1) ==="
  elastic_out=/tmp/_elastic_lane
  rm -rf "$elastic_out"; mkdir -p "$elastic_out"
  # outer budget > 2x the tool's per-run --timeout (420 s each) plus
  # data/conf setup slack
  timeout -k 10 880 env JAX_PLATFORMS=cpu \
    python tools/elastic_kill.py --out "$elastic_out" > /dev/null || rc=1
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench elastic \
      --input "$elastic_out/elastic.json" \
      --history "$elastic_out/bench_history.jsonl" > /dev/null || rc=1
  # kill -9 crash-window check: SIGKILL rank 0 between the consensus
  # checkpoint's tmp fsync and its rename, then restart with continue=1
  # (full run took ~30 s; budget covers a slow machine)
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/elastic_kill.py --kill-checkpoint \
      --out "$elastic_out" > /dev/null || rc=1
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench elastic_crash \
      --input "$elastic_out/elastic_crash.json" \
      --history "$elastic_out/bench_history.jsonl" > /dev/null || rc=1
  echo "ELASTIC lane verdict: $elastic_out/elastic.json $elastic_out/elastic_crash.json"
fi
if [ "${QUANT:-0}" = "1" ]; then
  echo "=== opt-in quantized-inference smoke (QUANT=1) ==="
  quant_out=/tmp/_quant_smoke
  rm -rf "$quant_out"; mkdir -p "$quant_out"
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/quant_smoke.py --out "$quant_out" \
      > "$quant_out/verdict.json" || rc=1
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench quant_bench \
      --input "$quant_out/verdict.json" \
      --history "$quant_out/bench_history.jsonl" > /dev/null || rc=1
  echo "QUANT lane verdict: $quant_out/verdict.json"
fi
if [ "${FLEET:-0}" = "1" ]; then
  echo "=== opt-in serving-fleet smoke (FLEET=1) ==="
  fleet_out=/tmp/_fleet_smoke
  rm -rf "$fleet_out"; mkdir -p "$fleet_out"
  timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python tools/fleet_smoke.py --out "$fleet_out" --replicas 2 \
      > "$fleet_out/verdict.json" || rc=1
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench fleet_bench \
      --input "$fleet_out/fleet_smoke.json" \
      --history "$fleet_out/bench_history.jsonl" > /dev/null || rc=1
  # scaled-down burst profile over the in-process engine (the full
  # >=10^6-request invocation is ROADMAP S10)
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/serve_bench.py --open-loop --burst --duration 6 \
      --base-rate 50 --burst-rate 200 --phase 1 \
      --json "$fleet_out/burst.json" > /dev/null || rc=1
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench serve_bench \
      --input "$fleet_out/burst.json" \
      --history "$fleet_out/bench_history.jsonl" > /dev/null || rc=1
  echo "FLEET lane verdict: $fleet_out/fleet_smoke.json"
fi
if [ "${WIRE:-0}" = "1" ]; then
  echo "=== opt-in binary wire-format A/B (WIRE=1) ==="
  wire_out=/tmp/_wire_ab
  rm -rf "$wire_out"; mkdir -p "$wire_out"
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/serve_bench.py --wire-ab --rows 32 --concurrency 8 \
      --requests 60 --max-batch 256 --timeout-ms 1 \
      --json "$wire_out/wire_ab.json" > /dev/null || rc=1
  # the hard acceptance bar: binary >= 1.5x JSON req/s, bitwise-equal
  # scores (the parity bit is also serve_bench's own exit status)
  python - "$wire_out/wire_ab.json" <<'PYEOF' || rc=1
import json, sys
ab = json.load(open(sys.argv[1]))["wire_ab"]
ok = ab["bitwise_equal_scores"] and ab["speedup"] >= 1.5
print(f"WIRE lane: speedup {ab['speedup']:.3f} (bar 1.5) parity "
      f"{'ok' if ab['bitwise_equal_scores'] else 'FAIL'}"
      f" -> {'OK' if ok else 'FAIL'}")
sys.exit(0 if ok else 1)
PYEOF
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench wire_bench \
      --input "$wire_out/wire_ab.json" \
      --history "$wire_out/bench_history.jsonl" > /dev/null || rc=1
  echo "WIRE lane verdict: $wire_out/wire_ab.json"
fi
if [ "${ASYNC:-0}" = "1" ]; then
  echo "=== opt-in async data-parallel lane (ASYNC=1) ==="
  async_out=/tmp/_async_lane
  rm -rf "$async_out"; mkdir -p "$async_out"
  # outer budget > the tool's per-leg --timeout (240 s) x the smoke's
  # four legs (2 parity + 2 A/B) plus data/conf setup slack
  timeout -k 10 1080 env JAX_PLATFORMS=cpu \
    python tools/async_ab.py --smoke --out "$async_out" \
      > /dev/null || rc=1
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench async_bench \
      --input "$async_out/async_ab.json" \
      --history "$async_out/bench_history.jsonl" > /dev/null || rc=1
  echo "ASYNC lane verdict: $async_out/async_ab.json"
fi
if [ "${TENANT:-0}" = "1" ]; then
  echo "=== opt-in multi-tenant loop smoke (TENANT=1) ==="
  tenant_out=/tmp/_tenant_smoke
  rm -rf "$tenant_out"; mkdir -p "$tenant_out"
  timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python tools/tenant_smoke.py --out "$tenant_out" \
      > "$tenant_out/verdict.json" || rc=1
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench tenant_bench \
      --input "$tenant_out/verdict.json" \
      --history "$tenant_out/bench_history.jsonl" > /dev/null || rc=1
  echo "TENANT lane verdict: $tenant_out/verdict.json"
fi
if [ "${SDC:-0}" = "1" ]; then
  echo "=== opt-in silent-data-corruption lane (SDC=1) ==="
  sdc_out=/tmp/_sdc_lane
  rm -rf "$sdc_out"; mkdir -p "$sdc_out"
  # outer budget > 2x the tool's per-run --timeout (420 s) plus the
  # overhead run and canary walk
  timeout -k 10 1000 env JAX_PLATFORMS=cpu \
    python tools/sdc_smoke.py --out "$sdc_out" > /dev/null || rc=1
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench integrity_bench \
      --input "$sdc_out/sdc.json" \
      --history "$sdc_out/bench_history.jsonl" > /dev/null || rc=1
  echo "SDC lane verdict: $sdc_out/sdc.json"
fi
if [ "${DSVC:-0}" = "1" ]; then
  echo "=== opt-in data-service lane (DSVC=1) ==="
  dsvc_out=/tmp/_dsvc_lane
  rm -rf "$dsvc_out"; mkdir -p "$dsvc_out"
  # outer budget > the tool's per-leg --timeout (240 s) x four legs
  # (local, service, kill/resume, 2-tenant) plus server startup slack;
  # the full run takes ~30 s on a healthy machine
  timeout -k 10 1000 env JAX_PLATFORMS=cpu \
    python tools/dataservice_smoke.py --out "$dsvc_out" \
      > /dev/null || rc=1
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/io_bench.py --service --smoke \
      --json "$dsvc_out/dsvc_bench.json" || rc=1
  timeout -k 10 60 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --bench dataservice_bench \
      --input "$dsvc_out/dsvc_bench.json" \
      --history "$dsvc_out/bench_history.jsonl" > /dev/null || rc=1
  echo "DSVC lane verdict: $dsvc_out/dataservice_smoke.json $dsvc_out/dsvc_bench.json"
fi
if [ "${OBS:-0}" = "1" ]; then
  echo "=== opt-in observability smoke (OBS=1) ==="
  obs_out=/tmp/_obs_smoke
  rm -rf "$obs_out"
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/obs_smoke.py --out "$obs_out" || rc=1
  timeout -k 10 60 python tools/obs_dump.py --check \
    --metrics "$obs_out/metricsz.txt" \
    --alertz "$obs_out/alertz.json" \
    --require xla_program_compile_seconds,xla_compile_seconds_total,obs_alerts_firing \
    --telemetry "$obs_out/telemetry.jsonl" \
    --events "$obs_out/events.jsonl" || rc=1
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/perf_guard.py --smoke \
    --history "$obs_out/bench_history.jsonl" \
    --json "$obs_out/perf_verdict.json" || rc=1
fi
exit $rc
