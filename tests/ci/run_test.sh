#!/bin/bash
# CI task runner (parity: tests/travis/run_test.sh task dispatch).
# Tasks compose the same make targets developers run locally, so a CI
# failure is always reproducible with one command.
#
#   TASK=lint        python lint (pyflakes if present, else compileall)
#                    + the mxlint graph-lint sweep over the model zoo
#   TASK=python      fast suite on the virtual CPU mesh (tests/conftest.py
#                    forces JAX_PLATFORMS=cpu + 8 fake devices)
#   TASK=python_nonative  same suite with the native .so disabled —
#                    certifies the pure-python fallback
#   TASK=cpp         native engine/recordio unit tests
#   TASK=capi        C ABI consumers (needs python headers)
#   TASK=nightly     multi-process distributed suite (slow)
#   TASK=resilience  fault-injection recovery matrix + graph lint
#   TASK=observability  telemetry unit tests + the 2-process drill +
#                    an mxtop --json smoke over the drill's event dir
#   TASK=perf        overlap unit suite + the 2-process overlap drill
#                    (asserts overlap_ratio > 1.05, bit-identical math)
#   TASK=autotune    chip-free config search (docs/perf.md "Autotuning
#                    & chip windows"): byte-identical manifest
#                    determinism on ResNet-50/v5e and the dp=2,tp=2
#                    transformer, the v5e ranking pin (b512 first),
#                    and the slo-gated replay over the pinned fixture
#   TASK=serving     serving unit suite (planner/batcher/server + KV
#                    cache + generation) + the serve_load and
#                    serve_generate acceptance drills (>= 3x serial
#                    batch-1; decode == full forward; structured KV
#                    429s; zero lowerings after warmup) +
#                    serve_bench/mxtop smoke in both modes + the
#                    networked-fleet chaos drill (KV partition +
#                    leader-router SIGKILL, zero client errors) and
#                    an mxkv TCP-server smoke
set -e
cd "$(dirname "$0")/../.."

case "${TASK:-python}" in
  lint)
    if python -c "import pyflakes" 2>/dev/null; then
      python -m pyflakes mxnet_tpu tools bench.py __graft_entry__.py
    else
      python -m compileall -q mxnet_tpu tools bench.py __graft_entry__.py
    fi
    # fast pre-merge step: lint only what this change touches (changed
    # symbol JSONs, models whose builders changed, changed framework
    # .py through the MXL-D rank-divergence pass) before the full
    # sweeps below — a quick early exit for broken changes
    if git rev-parse --verify -q HEAD~1 >/dev/null; then
      JAX_PLATFORMS=cpu python tools/mxlint.py --diff HEAD~1 \
        --fail-on=error --format=github
    fi
    # graph lint sweep over the bundled model zoo (docs/graph_lint.md):
    # every model must carry zero error-severity findings
    JAX_PLATFORMS=cpu python tools/mxlint.py --all-models --fail-on=error
    # SPMD sweep: sharding propagation + collective audit + peak-HBM
    # report on the transformer under a dp=2,tp=2 logical mesh — no
    # implicit reshard (MXL-P001) may appear at error severity
    JAX_PLATFORMS=cpu python tools/mxlint.py --model transformer \
      --mesh dp=2,tp=2 --fail-on=error
    # kernel + roofline sweep (docs/graph_lint.md MXL-K/MXL-R): every
    # registered Pallas kernel spec must satisfy Mosaic's tile rules,
    # and the static roofline must price resnet at training batch
    # sizes without an error-severity finding — all chip-free
    JAX_PLATFORMS=cpu python tools/mxlint.py --model resnet \
      --select 'MXL-K*,MXL-R*' --shapes "data=(64,3,224,224)" \
      --fail-on=error --format=github
    JAX_PLATFORMS=cpu python tools/mxlint.py --model resnet \
      --select 'MXL-K*,MXL-R*' --shapes "data=(256,3,224,224)" \
      --fail-on=error --format=github
    JAX_PLATFORMS=cpu python tools/mxlint.py --model transformer \
      --mesh dp=2,tp=2 --select 'MXL-K*,MXL-R*' \
      --fail-on=error --format=github
    # distributed sweep (docs/graph_lint.md MXL-D): the per-rank
    # collective-trace diff over the zoo at a simulated 4-rank pod,
    # plus the rank-divergence dataflow self-lint over mxnet_tpu/ —
    # the framework's own source must carry zero error-severity
    # divergence findings (intentional seams are @collective_seam /
    # rank-divergent-ok annotated)
    JAX_PLATFORMS=cpu python tools/mxlint.py --all-models \
      --distributed --world-size 4 --fail-on=error --format=github
    JAX_PLATFORMS=cpu python tools/mxlint.py --distributed \
      --world-size 4 mxnet_tpu --fail-on=error --format=github
    # the elastic re-mesh protocol is the most divergence-sensitive
    # code in the tree (rank 0 proposes, everyone else adopts): pin
    # its self-lint as an explicit leg so a sweep-config change can
    # never silently drop it
    JAX_PLATFORMS=cpu python tools/mxlint.py --distributed \
      mxnet_tpu/resilience/elastic.py --fail-on=error --format=github
    # the async-collective machinery (bucketed push, FIFO launcher) is
    # the newest divergence-sensitive seam — pinned for the same reason
    JAX_PLATFORMS=cpu python tools/mxlint.py --distributed \
      mxnet_tpu/parallel/overlap.py --fail-on=error --format=github
    # the serving scheduler rides those same launchers and makes its
    # own per-process dispatch decisions (queue depth, timers) — pin
    # its self-lint so the divergence pass always prices it
    JAX_PLATFORMS=cpu python tools/mxlint.py --distributed \
      mxnet_tpu/serving --fail-on=error --format=github
    # the fleet router makes the most divergence-sensitive serving
    # decisions of all (per-replica dispatch, generation verdicts,
    # rotation during hot-swap) — pinned on top of the directory sweep
    # so a sweep-config change can never silently drop it
    JAX_PLATFORMS=cpu python tools/mxlint.py --distributed \
      mxnet_tpu/serving/fleet.py --fail-on=error --format=github
    # the coordination KV + lease (docs/serving.md "Networked fleet")
    # sits under every cross-process verdict the fleet makes — pinned
    # explicitly like fleet.py so the sweep can never drop it
    JAX_PLATFORMS=cpu python tools/mxlint.py --distributed \
      mxnet_tpu/resilience/netkv.py --fail-on=error --format=github
    # generative serving's cache allocator + engine make per-process
    # admission and scheduling decisions (block budgets, prefill/decode
    # alternation) — pinned explicitly on top of the directory sweep so
    # a future sweep-config change can never silently drop them
    JAX_PLATFORMS=cpu python tools/mxlint.py --distributed \
      mxnet_tpu/serving/kvcache.py mxnet_tpu/serving/generate.py \
      --fail-on=error --format=github
    # the paged KV cache's (block_size, head_dim) decode layout must
    # stay MXL-K tile-legal at every serving dtype — including the
    # int8 the quantized tier will bind — straight from the registered
    # kernel spec
    JAX_PLATFORMS=cpu python -c '
from mxnet_tpu.serving.kvcache import cache_kernel_spec
from mxnet_tpu.analysis.tiling import spec_findings
for dt in ("float32", "bfloat16", "int8"):
    bad = [f for f in spec_findings(cache_kernel_spec(dtype=dt))
           if f[1] == "error"]
    assert not bad, (dt, bad)
print("paged_kv_cache MXL-K sweep OK (f32/bf16/int8)")
'
    # the quantized + fused kernel tier (docs/perf.md "Quantization &
    # fused kernels"): both Pallas specs — dequant matmul, fused
    # optimizer sweep — must stay Mosaic tile-legal at every compute
    # dtype they serve
    JAX_PLATFORMS=cpu python -c '
from mxnet_tpu.analysis.tiling import spec_findings
from mxnet_tpu.kernels.fused_opt import fused_opt_kernel_spec
from mxnet_tpu.kernels.quantize import qmm_kernel_spec
for mk in (qmm_kernel_spec, fused_opt_kernel_spec):
    for dt in ("float32", "bfloat16", "int8"):
        spec = mk(dtype=dt)
        bad = [f for f in spec_findings(spec) if f[1] == "error"]
        assert not bad, (spec["name"], bad)
print("kernel-tier MXL-K sweep OK (qmm/fused_opt x f32/bf16/int8)")
'
    # ...and the kernel tier itself (env-gated dispatch, bucket plans)
    # must stay divergence-clean under the MXL-D self-lint
    JAX_PLATFORMS=cpu python tools/mxlint.py --distributed \
      mxnet_tpu/kernels --fail-on=error --format=github
    # the tracing tier touches every collective seam (rank-uniform seq
    # counters, the flight ledger, the SLO sentry's emit path) — its
    # three modules must stay divergence-clean under MXL-D
    JAX_PLATFORMS=cpu python tools/mxlint.py --distributed \
      mxnet_tpu/observability/trace.py \
      mxnet_tpu/observability/flight.py \
      mxnet_tpu/observability/slo.py --fail-on=error --format=github
    # warm elasticity's shard-directory agreement is another pod-wide
    # decision protocol (rank 0 publishes, everyone adopts) — pin its
    # MXL-D self-lint like elastic.py's
    JAX_PLATFORMS=cpu python tools/mxlint.py --distributed \
      mxnet_tpu/resilience/hotstate.py --fail-on=error --format=github
    # the autotuner plans pod-wide chip windows (per-rank bench
    # commands, sharding grammars, pruning verdicts) — its own source
    # must stay divergence-clean under the MXL-D self-lint
    JAX_PLATFORMS=cpu python tools/mxlint.py --distributed \
      mxnet_tpu/analysis/autotune.py --fail-on=error --format=github
    # the pre-fix PR-3 regression fixtures are expected-FAIL inputs:
    # MXL-D must keep flagging each with its documented rule id
    fx=tests/fixtures/divergence
    for f in "$fx/pid_scratch_path.py:MXL-D004" \
             "$fx/per_rank_barrier_probe.py:MXL-D005" \
             "$fx/device0_sentinel.py:MXL-D005"; do
      file="${f%:*}"; rule="${f##*:}"
      if out=$(JAX_PLATFORMS=cpu python tools/mxlint.py --distributed \
          "$file" --fail-on=error --format=github); then
        echo "FIXTURE NOT FLAGGED: $file"; exit 1
      fi
      echo "$out" | grep -q "$rule" || {
        echo "FIXTURE $file missing $rule:"; echo "$out"; exit 1; }
      echo "fixture $file flagged with $rule (expected-fail OK)"
    done
    # concurrency self-lint (docs/graph_lint.md MXL-Q): the threaded
    # serving/resilience/observability runtime must carry zero
    # error-severity race / lock-order / callback-context findings
    # (intentional lock-free handshakes are thread-shared-ok annotated
    # with their happens-before argument)
    JAX_PLATFORMS=cpu python tools/mxlint.py --concurrency \
      mxnet_tpu --fail-on=error --format=github
    # the networked fleet's lock-densest files (router lease/takeover,
    # KV connection handling, bget parking) — pinned on top of the
    # directory sweep so a sweep-config change can never drop them
    JAX_PLATFORMS=cpu python tools/mxlint.py --concurrency \
      mxnet_tpu/resilience/netkv.py mxnet_tpu/serving/fleet.py \
      --fail-on=error --format=github
    # the pre-fix concurrency regression fixtures are expected-FAIL
    # inputs: MXL-Q must keep flagging each with its documented rule id
    qx=tests/fixtures/concurrency
    for f in "$qx/torch_callback_race.py:MXL-Q005" \
             "$qx/prefetcher_shutdown_race.py:MXL-Q001"; do
      file="${f%:*}"; rule="${f##*:}"
      if out=$(JAX_PLATFORMS=cpu python tools/mxlint.py --concurrency \
          "$file" --fail-on=error --format=github); then
        echo "FIXTURE NOT FLAGGED: $file"; exit 1
      fi
      echo "$out" | grep -q "$rule" || {
        echo "FIXTURE $file missing $rule:"; echo "$out"; exit 1; }
      echo "fixture $file flagged with $rule (expected-fail OK)"
    done
    # retrace-stability self-lint (docs/graph_lint.md MXL-X): the
    # traced/jitted surface must carry zero error-severity retrace
    # findings — tensor-dependent host branching, unstable cache-key
    # ingredients, per-step jit construction, unbucketed AOT shapes,
    # and donated-buffer reuse all break the zero-steady-state-
    # lowerings contract the serving benches assert at runtime
    JAX_PLATFORMS=cpu python tools/mxlint.py --retrace \
      mxnet_tpu --fail-on=error --format=github
    # the networked-fleet swap path re-aims AOT programs at new params
    # mid-serve — pin its files so MXL-X always prices them
    JAX_PLATFORMS=cpu python tools/mxlint.py --retrace \
      mxnet_tpu/resilience/netkv.py mxnet_tpu/serving/fleet.py \
      --fail-on=error --format=github
    # the pre-fix retrace regression fixture (the PR-17 id()-keyed
    # fused-step cache bug) is an expected-FAIL input: MXL-X must keep
    # flagging it with its documented rule id
    rx=tests/fixtures/retrace
    for f in "$rx/id_keyed_program_cache.py:MXL-X002"; do
      file="${f%:*}"; rule="${f##*:}"
      if out=$(JAX_PLATFORMS=cpu python tools/mxlint.py --retrace \
          "$file" --fail-on=error --format=github); then
        echo "FIXTURE NOT FLAGGED: $file"; exit 1
      fi
      echo "$out" | grep -q "$rule" || {
        echo "FIXTURE $file missing $rule:"; echo "$out"; exit 1; }
      echo "fixture $file flagged with $rule (expected-fail OK)"
    done
    # schedule lint (docs/graph_lint.md MXL-E): the pipeline-parallel
    # transformer sweep (dp=2,pp=4 flops-balanced auto-split) and the
    # expert-parallel MoE sweep (top-1 routing, ep=4, the priced
    # dispatch/combine all-to-all pair replayed through the MXL-D
    # collective trace at world 4) must both price clean
    JAX_PLATFORMS=cpu python tools/mxlint.py --model transformer \
      --mesh dp=2,pp=4 --schedule --fail-on=error --format=github
    JAX_PLATFORMS=cpu python tools/mxlint.py --model transformer_moe \
      --mesh dp=1,ep=4 --schedule --distributed --world-size 4 \
      --fail-on=error --format=github
    # the MXL-E analyzer, the MoE op and the 1F1B runtime are
    # themselves lint subjects: pin the divergence/concurrency/retrace
    # self-lints on them so the pricing machinery stays clean under
    # the families that police it
    JAX_PLATFORMS=cpu python tools/mxlint.py --distributed \
      --concurrency --retrace mxnet_tpu/analysis/schedule.py \
      mxnet_tpu/ops/moe.py mxnet_tpu/parallel/pipeline.py \
      --fail-on=error --format=github
    # the pre-fix schedule regression fixtures are expected-FAIL
    # symbol graphs: MXL-E must keep flagging each with its
    # documented rule id (an imbalanced ctx_group split, a
    # cross-stage back-edge, an expert count the ep mesh cannot
    # divide)
    sx=tests/fixtures/schedule
    for f in "$sx/imbalanced_stages.json|MXL-E001|data=(256,4096)|" \
             "$sx/cross_stage_backedge.json|MXL-E003|data=(256,4096)|" \
             "$sx/indivisible_experts.json|MXL-E006|data=(512,64)|ep=4"
    do
      IFS='|' read -r file rule shapes mesh <<< "$f"
      cmd=(tools/mxlint.py "$file" --schedule --shapes "$shapes"
           --fail-on=error --format=github)
      [ -n "$mesh" ] && cmd+=(--mesh "$mesh")
      if out=$(JAX_PLATFORMS=cpu python "${cmd[@]}"); then
        echo "FIXTURE NOT FLAGGED: $file"; exit 1
      fi
      echo "$out" | grep -q "$rule" || {
        echo "FIXTURE $file missing $rule:"; echo "$out"; exit 1; }
      echo "fixture $file flagged with $rule (expected-fail OK)"
    done
    ;;
  python)
    make -s all || echo "native build unavailable; python fallback"
    python -m pytest tests/ -x -q
    ;;
  python_nonative)
    MXTPU_NO_NATIVE=1 python -m pytest tests/ -x -q
    ;;
  cpp)
    make -s test-cpp
    ;;
  capi)
    make -s test-capi
    ;;
  nightly)
    make -s all
    MXTPU_NIGHTLY=1 python -m pytest tests/test_nightly_dist.py -x -q
    ;;
  resilience)
    # the whole leg runs under the lock-discipline sanitizer
    # (docs/graph_lint.md "MXL-Q"): every package lock records
    # per-thread acquisition order, and a lock-order inversion anywhere
    # in the sentinel/watchdog/elastic threads fails the suite as a
    # structured ResilienceError(kind="lock_order") instead of an
    # intermittent hang
    export MXTPU_LOCKCHECK=1
    # ...and under the retrace sentry (docs/graph_lint.md "MXL-X"):
    # every post-warmup lowering is counted and attributed to the
    # divergent cache-key ingredient, so a recovery path that silently
    # re-lowers steady-state programs surfaces as a structured
    # "retrace" telemetry event instead of a latency mystery
    export MXTPU_RETRACE_SENTRY=1
    # fault-injection matrix (docs/resilience.md): injected NaN/hang/
    # ckpt-crash/dead-node faults must each hit their recovery path,
    # plus the kill-one-worker resume smoke
    JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q \
      --deselect tests/test_resilience.py::test_elastic_shrink_grow_drill \
      --deselect tests/test_resilience.py::test_warm_shrink_grow_drill \
      --deselect tests/test_resilience.py::test_warm_corrupt_shard_falls_back_to_checkpoint \
      --deselect tests/test_resilience.py::test_multihost_warm_shrink_grow_drill
    # elasticity acceptance (docs/resilience.md "Elasticity"): its own
    # leg so a skip/deselect upstream can never silently drop it —
    # kill one of three workers, agree a generation-stamped shrink
    # verdict, resume resharded, grow back, and match the fixed-world
    # reference losses bit-for-bit
    JAX_PLATFORMS=cpu python -m pytest -q \
      tests/test_resilience.py::test_elastic_shrink_grow_drill
    # warm-elasticity acceptance (docs/resilience.md "Warm elasticity"):
    # the same kill/shrink/grow drill with MXTPU_WARM_REMESH=1 — losses
    # must stay bit-identical to the cold references while the telemetry
    # log shows zero checkpoint reads on the warm path
    JAX_PLATFORMS=cpu python -m pytest -q \
      tests/test_resilience.py::test_warm_shrink_grow_drill
    # structured degradation: a CRC-corrupt hot shard on rank 0 must fall
    # back to the PR-3 checkpoint with a named fallback_reason, never crash
    JAX_PLATFORMS=cpu python -m pytest -q \
      tests/test_resilience.py::test_warm_corrupt_shard_falls_back_to_checkpoint
    # multi-host-sim shrink/grow: 4 workers over 2 simulated hosts, lose a
    # whole host, rebuild from ring-buddy copies on the survivor
    JAX_PLATFORMS=cpu python -m pytest -q \
      tests/test_resilience.py::test_multihost_warm_shrink_grow_drill
    # lint must stay clean under the resilience wiring (github-annotated
    # output so findings land on the PR diff)
    JAX_PLATFORMS=cpu python tools/mxlint.py --all-models \
      --format=github --fail-on=error
    ;;
  observability)
    # telemetry suite (docs/observability.md): event-log semantics, the
    # <2% enabled-overhead bound, and the 2-process acceptance drill
    # (sentinel -> watchdog -> ckpt must land in the merged report);
    # plus the quantile-sketch/registry and SLO-engine unit suites
    JAX_PLATFORMS=cpu python -m pytest tests/test_observability.py \
      tests/test_metrics.py tests/test_sloengine.py -q
    # end-to-end CLI smoke: a real 2-worker run's event dir must render
    # through mxtop --json with a nonempty pod rollup
    TELDIR="$(mktemp -d)"
    MXTPU_TELEMETRY=1 MXTPU_TELEMETRY_DIR="$TELDIR" MXTPU_RUN_ID=ci \
      MXTPU_SENTINEL=1 MXTPU_FAULT_SPEC="step=2:kind=nan" \
      MXTPU_TEL_PREFIX="$TELDIR/ckpt" \
      python tools/launch.py -n 2 --launcher local --port 9899 \
      python tests/nightly/dist_telemetry.py
    python tools/mxtop.py "$TELDIR" --json | python -c '
import json, sys
rep = json.load(sys.stdin)
assert len(rep["per_rank"]) == 2, rep
assert rep["pod"]["step_ms_p50"] is not None, rep
print("mxtop --json smoke OK")
'
    # trace-merge smoke: the same run must render through mxtrace as a
    # valid Chrome-trace document with one process track per rank and
    # cross-rank flow events stitching the collectives
    python tools/mxtrace.py "$TELDIR" -o "$TELDIR/trace.json"
    python -c '
import json, sys
doc = json.load(open(sys.argv[1]))
evs = doc["traceEvents"]
assert isinstance(evs, list) and evs, "empty trace"
assert doc["displayTimeUnit"] == "ms", doc.keys()
pids = {e["pid"] for e in evs if e["ph"] == "M"}
assert pids == {0, 1}, pids
flows = [e for e in evs if e["ph"] in ("s", "f")]
assert flows, "no cross-rank flow events"
print("mxtrace smoke OK: %d events, %d flow arrows"
      % (len(evs), len(flows)))
' "$TELDIR/trace.json"
    rm -rf "$TELDIR"
    # hung-collective flight-dump drill: kill one of two workers
    # mid-allreduce; the survivor must dump a postmortem naming the
    # hung seq and the absent rank (asserted inside the drill), and
    # mxtrace must fold the dump's pending marker into the trace.
    # MXTPU_STEP_TIMEOUT_S stays unset: the drill arms its own watchdog.
    TELDIR="$(mktemp -d)"
    MXTPU_TELEMETRY=1 MXTPU_TELEMETRY_DIR="$TELDIR" MXTPU_RUN_ID=ci-flight \
      python tools/launch.py -n 2 --launcher local --port 9898 \
      python tests/nightly/dist_flight.py
    python tools/mxtrace.py "$TELDIR" -o "$TELDIR/trace.json"
    python -c '
import json, sys
doc = json.load(open(sys.argv[1]))
pend = [e for e in doc["traceEvents"]
        if e["ph"] == "i" and e["name"].startswith("PENDING")]
assert pend, "flight dump pending marker missing from trace"
print("flight drill trace OK: %s" % pend[0]["name"])
' "$TELDIR/trace.json"
    rm -rf "$TELDIR"
    # perf-regression gate: benchdiff must pass an unchanged run and
    # flag a synthetic +20% step-time regression against a pinned
    # baseline (a single file: zero noise, the 10% floor applies)
    python tools/benchdiff.py --baseline BENCH_r05.json \
      --against BENCH_r05.json
    if python tools/benchdiff.py --baseline BENCH_r05.json \
        --metrics "$(python -c '
import json
doc = json.load(open("BENCH_r05.json"))
print(json.dumps({"step_time_ms": doc["parsed"]["step_time_ms"] * 1.2}))
')"; then
      echo "benchdiff FAILED to flag a +20% step-time regression"
      exit 1
    fi
    echo "benchdiff gate OK (clean run passes, +20% regression flags)"
    # live SLO drill (docs/observability.md "Live metrics & SLO
    # engine"): /metrics exposition smoke (Prometheus-parseable,
    # counters monotone across two scrapes), then the burn-rate drill —
    # bursty open-loop traffic must stay quiet clean and must page +
    # recommend_grow within the fast window under an injected
    # serve_dispatch latency fault (asserted inside the drill)
    JAX_PLATFORMS=cpu python tests/nightly/serve_slo_drill.py
    ;;
  perf)
    # overlap machinery (docs/perf.md "Overlap"): prefetcher/bucketing/
    # compile-cache unit suite, then the 2-process acceptance drill —
    # the async feed must yield overlap_ratio > 1.05 with parameters
    # bit-identical to the serial run (asserted inside the drill)
    JAX_PLATFORMS=cpu python -m pytest tests/test_overlap.py -q
    TELDIR="$(mktemp -d)"
    JAX_PLATFORMS=cpu MXTPU_TELEMETRY=1 MXTPU_TELEMETRY_DIR="$TELDIR" \
      MXTPU_RUN_ID=ci-perf MXTPU_PREFETCH=1 MXTPU_BUCKET_MB=0.001 \
      python tools/launch.py -n 2 --launcher local --port 9899 \
      python tests/nightly/dist_overlap.py
    # the same events must surface through the operator CLI
    python tools/mxtop.py "$TELDIR" --json | python -c '
import json, sys
rep = json.load(sys.stdin)
ratio = rep["pod"].get("overlap_ratio")
assert ratio is not None and ratio > 1.05, rep["pod"]
print("mxtop overlap_ratio %.3f OK" % ratio)
'
    rm -rf "$TELDIR"
    ;;
  autotune)
    # autotuner unit suite (docs/perf.md "Autotuning & chip windows"):
    # the pinned v5e ceiling table, pruning-before-pricing, memoized
    # sweeps, manifest determinism, the replay/correction loop
    JAX_PLATFORMS=cpu python -m pytest tests/test_autotune.py -q
    ATDIR="$(mktemp -d)"
    # manifest determinism (snapshot assert): the same search inputs
    # must produce byte-identical manifests.  Two fresh runs + cmp is
    # the right snapshot — the provenance block pins the git commit,
    # so a repo-committed byte snapshot would break on every merge.
    JAX_PLATFORMS=cpu python tools/autotune.py --model resnet50 \
      --device-kind v5e -o "$ATDIR/resnet.a.json"
    JAX_PLATFORMS=cpu python tools/autotune.py --model resnet50 \
      --device-kind v5e -o "$ATDIR/resnet.b.json"
    cmp "$ATDIR/resnet.a.json" "$ATDIR/resnet.b.json"
    echo "autotune manifest determinism OK (resnet50/v5e)"
    # the v5e ranking pin: batch 512 (the 0.331 AOT ceiling) must rank
    # above batch 256 (0.293) for ResNet-50, and the HBM-infeasible
    # tail must have been pruned before pricing
    python -c '
import json, sys
man = json.load(open(sys.argv[1]))
top = man["configs"][0]
assert top["config"]["batch"] == 512, top["config"]
assert abs(top["predicted"]["mfu_ceiling"] - 0.331) < 0.01, top
nxt = [e for e in man["configs"] if e["config"]["batch"] == 256][0]
assert abs(nxt["predicted"]["mfu_ceiling"] - 0.293) < 0.01, nxt
assert top["predicted"]["mfu_ceiling"] > nxt["predicted"]["mfu_ceiling"]
assert top["bench_cmd"].startswith("BENCH_BATCH="), top["bench_cmd"]
print("autotune v5e ranking pin OK: b512 %.4f > b256 %.4f"
      % (top["predicted"]["mfu_ceiling"], nxt["predicted"]["mfu_ceiling"]))
' "$ATDIR/resnet.a.json"
    # dp=2,tp=2 transformer sweep: the SPMD axes must price (ICI bytes
    # present) and the manifest must stay deterministic there too
    JAX_PLATFORMS=cpu python tools/autotune.py --model transformer \
      --space "sharding=dp2tp2;batch=8,16" -o "$ATDIR/tfm.a.json"
    JAX_PLATFORMS=cpu python tools/autotune.py --model transformer \
      --space "sharding=dp2tp2;batch=8,16" -o "$ATDIR/tfm.b.json"
    cmp "$ATDIR/tfm.a.json" "$ATDIR/tfm.b.json"
    python -c '
import json, sys
man = json.load(open(sys.argv[1]))
assert man["configs"], man
for e in man["configs"]:
    assert e["config"]["sharding"] == "dp2tp2", e["config"]
    assert e["predicted"]["ici_bytes"] and e["predicted"]["ici_bytes"] > 0, e
print("autotune dp2tp2 transformer OK: %d configs, ici %.1f MB at top"
      % (len(man["configs"]),
         man["configs"][0]["predicted"]["ici_bytes"] / 1e6))
' "$ATDIR/tfm.a.json"
    # pipeline/MoE axes (docs/graph_lint.md MXL-E): the dp2pp2 sweep
    # must price with a simulated 1F1B bubble, the indivisible expert
    # count must be mxl-e-pruned before pricing, and the manifest must
    # stay byte-identical over the new axes
    JAX_PLATFORMS=cpu python tools/autotune.py --model transformer_moe \
      --space "sharding=dp2pp2,ep4;batch=8;microbatches=4,8;experts=8,6;capacity_factor=1.25" \
      -o "$ATDIR/moe.a.json"
    JAX_PLATFORMS=cpu python tools/autotune.py --model transformer_moe \
      --space "sharding=dp2pp2,ep4;batch=8;microbatches=4,8;experts=8,6;capacity_factor=1.25" \
      -o "$ATDIR/moe.b.json"
    cmp "$ATDIR/moe.a.json" "$ATDIR/moe.b.json"
    python -c '
import json, sys
man = json.load(open(sys.argv[1]))
piped = [e for e in man["configs"] if e["config"]["sharding"] == "dp2pp2"]
assert piped, [e["config"] for e in man["configs"]]
for e in piped:
    b = e["predicted"]["bubble_fraction"]
    assert b is not None and 0.0 < b < 1.0, e["predicted"]
    assert "BENCH_PP_STAGES=2" in e["bench_cmd"], e["bench_cmd"]
bad = [p for p in man["pruned"] if p["config"].get("experts") == 6
       and p["config"]["sharding"] == "ep4"]
assert bad and all(p["reason"].startswith("mxl-e:") for p in bad), \
    man["pruned"]
print("autotune pp/MoE axes OK: %d pipelined configs priced with "
      "bubbles, %d expert-indivisible config(s) mxl-e-pruned"
      % (len(piped), len(bad)))
' "$ATDIR/moe.a.json"
    # replay gate over the pinned fixture: the recorded chip-window
    # payloads must pass the slo sentry clean against the committed
    # BENCH_r05 baseline, fit a correction, and emit a corrected order
    JAX_PLATFORMS=cpu python tools/autotune.py \
      --replay "$ATDIR/resnet.a.json" \
      --results tests/fixtures/autotune/replay_results.json \
      --baseline BENCH_r05.json --fail-on-regression \
      > "$ATDIR/replay.json"
    python -c '
import json, sys
rep = json.load(open(sys.argv[1]))
assert rep["regressions"] == 0, rep
assert rep["correction"] and rep["correction"]["n"] >= 2, rep["correction"]
assert rep["corrected_order"], rep
ok = [r for r in rep["runs"] if r["status"] == "ok"]
assert ok and all(r.get("slo_checked") for r in ok), rep["runs"]
print("autotune replay gate OK: %d runs, correction a=%.3f"
      % (len(ok), rep["correction"]["a"]))
' "$ATDIR/replay.json"
    # ...and a synthetic halved-throughput window must flag through the
    # same gate (exit 1), like the observability benchdiff leg
    python -c '
import json, sys
doc = json.load(open("tests/fixtures/autotune/replay_results.json"))
for run in doc["runs"]:
    run["value"] = run["value"] * 0.5
    run["step_time_ms"] = run["step_time_ms"] * 2.0
json.dump(doc, open(sys.argv[1], "w"))
' "$ATDIR/regressed.json"
    if JAX_PLATFORMS=cpu python tools/autotune.py \
        --replay "$ATDIR/resnet.a.json" --results "$ATDIR/regressed.json" \
        --baseline BENCH_r05.json --fail-on-regression \
        > "$ATDIR/replay_bad.json"; then
      echo "autotune replay FAILED to flag a halved-throughput window"
      exit 1
    fi
    echo "autotune replay regression gate OK (clean passes, halved flags)"
    rm -rf "$ATDIR"
    ;;
  serving)
    # the whole leg runs under the lock-discipline sanitizer — the
    # batcher/fleet/router threads are the most lock-dense code in the
    # tree; a lock-order inversion fails as a structured error instead
    # of a flaky hang (docs/graph_lint.md "MXL-Q")
    export MXTPU_LOCKCHECK=1
    # ...and under the retrace sentry (docs/graph_lint.md "MXL-X"):
    # after each model's warmup boundary every unexpected lowering is
    # counted and attributed to its divergent cache-key ingredient —
    # the zero-steady-state-lowerings contract becomes an observable,
    # not a hope.  serve_bench stamps retraces_after_warmup into its
    # BENCH line below, which must stay 0
    export MXTPU_RETRACE_SENTRY=1
    # serving stack (docs/serving.md): planner/batcher/server unit
    # suite, then the acceptance drill — continuous batching must beat
    # the serial batch-1 Predictor >= 3x at bounded p95 with zero
    # lowerings after warmup (all asserted inside the drill)
    JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py \
      tests/test_kvcache.py tests/test_generate.py tests/test_kernels.py -q
    JAX_PLATFORMS=cpu python tests/nightly/serve_load.py
    # fleet unit suite + the multi-process fleet drill (docs/serving.md
    # "Fleet"): 3 real replica processes behind the router; SIGKILL one
    # and hot-swap weights mid-load — zero client-visible errors, p95
    # within the degraded-window bound, zero swap lowerings, post-swap
    # outputs bit-identical, and a generation-stamped replica_death
    # verdict in the fleet ledger (all asserted inside the drill)
    JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q
    JAX_PLATFORMS=cpu python tests/nightly/serve_load_fleet.py
    # networked-fleet stack (docs/serving.md "Networked fleet"): the
    # KV backend-parity + lease + fault-discipline unit suite runs
    # file:// and tcp:// through one contract, then the chaos drill —
    # 3 replica processes + 2 router doors over a tcp:// KV survive a
    # 5s KV partition AND SIGKILL of the leader router with zero
    # client-visible errors, zero fabricated death verdicts (the
    # partition must HOLD the last liveness verdict, not invent
    # deaths), a lease takeover, client address failover, a converged
    # swap-on-commit to v2 (bit-identical outputs), and bounded p95
    # (all asserted inside the drill)
    JAX_PLATFORMS=cpu python -m pytest tests/test_netkv.py -q
    JAX_PLATFORMS=cpu python tests/nightly/serve_fleet_net.py
    # mxkv smoke: the standalone TCP KV server must answer the CLI
    # client ops (ping/set/get/dir/del) over tcp://
    MXKV_URL="tcp://127.0.0.1:8979"
    python tools/mxkv.py serve --port 8979 &
    MXKV_PID=$!
    for _ in $(seq 1 50); do
      python tools/mxkv.py --kv "$MXKV_URL" ping >/dev/null 2>&1 \
        && break
      sleep 0.2
    done
    python tools/mxkv.py --kv "$MXKV_URL" ping | grep -q '"ok": true'
    python tools/mxkv.py --kv "$MXKV_URL" set smoke/k v1
    test "$(python tools/mxkv.py --kv "$MXKV_URL" get smoke/k)" = "v1"
    python tools/mxkv.py --kv "$MXKV_URL" dir smoke/ | grep -q "^smoke/k"
    python tools/mxkv.py --kv "$MXKV_URL" del smoke/k
    if python tools/mxkv.py --kv "$MXKV_URL" get smoke/k 2>/dev/null; then
      echo "mxkv: deleted key still readable"; exit 1
    fi
    kill "$MXKV_PID"; wait "$MXKV_PID" 2>/dev/null || true
    echo "mxkv smoke OK"
    # generative acceptance drill (docs/serving.md "Generation"):
    # decode == full forward, zero lowerings, structured 429 under KV
    # pressure while running decodes finish, bounded p95 TTFT
    JAX_PLATFORMS=cpu python tests/nightly/serve_generate.py
    # bench smoke with telemetry on: the BENCH JSON line must show an
    # intact AOT contract and carry the latency/occupancy/waste fields
    # the SLO dashboards read
    TELDIR="$(mktemp -d)"
    JAX_PLATFORMS=cpu MXTPU_TELEMETRY=1 MXTPU_TELEMETRY_DIR="$TELDIR" \
      MXTPU_RUN_ID=ci-serve \
      python tools/serve_bench.py --requests 200 | python -c '
import json, sys
rep = json.loads(sys.stdin.readlines()[-1])
assert rep["lowerings_after_warmup"] == 0, rep
assert rep.get("retraces_after_warmup", 0) == 0, rep
assert rep["completed"] == 200 and rep["errors"] == 0, rep
assert rep["latency_ms"]["p95"] is not None, rep
assert 0.0 < rep["occupancy"] <= 1.0, rep
assert rep["padding_waste"] is not None, rep
print("serve_bench smoke OK: %.0f rps, p95 %.2f ms"
      % (rep["value"], rep["latency_ms"]["p95"]))
'
    # the per-batch serve events must surface through the operator CLI
    python tools/mxtop.py "$TELDIR" --json --serve | python -c '
import json, sys
sv = json.load(sys.stdin)
assert sv["models"], sv
assert sv["total"]["requests"] >= 200, sv["total"]
print("mxtop --serve smoke OK: %d requests" % sv["total"]["requests"])
'
    rm -rf "$TELDIR"
    # generative bench smoke: the tokens/sec BENCH line must show the
    # AOT contract intact (zero lowerings across prefill AND decode)
    # and carry the TTFT/ITL percentiles the SLO sentry prices
    JAX_PLATFORMS=cpu python tools/serve_bench.py --generate \
      --requests 40 --max-new 8 | python -c '
import json, sys
rep = json.loads(sys.stdin.readlines()[-1])
assert rep["metric"] == "serve_tokens_per_sec", rep
assert rep["lowerings_after_warmup"] == 0, rep
assert rep["errors"] == 0 and rep["requests"] == 40, rep
assert rep["ttft_ms"]["p95"] is not None, rep
assert rep["itl_ms"]["p95"] is not None, rep
print("serve_bench --generate smoke OK: %.0f tok/s, ttft p95 %.2f ms"
      % (rep["value"], rep["ttft_ms"]["p95"]))
'
    # fleet bench smoke: the fleet_throughput_rps BENCH line must show
    # a balanced fleet, an AOT-clean mid-run hot-swap (zero lowerings,
    # enforced by serve_bench itself via exit 1), and carry the
    # balance/swap-pause fields the SLO sentry prices
    JAX_PLATFORMS=cpu python tools/serve_bench.py --fleet 2 \
      --requests 120 | python -c '
import json, sys
rep = json.loads(sys.stdin.readlines()[-1])
assert rep["metric"] == "fleet_throughput_rps", rep
assert rep["errors"] == 0, rep
assert rep["swap_lowerings"] == 0, rep
assert rep["balance_ratio"] is not None, rep
assert rep["swap_pause_ms_p95"] is not None, rep
assert sorted(rep["version_skew"]) == ["v2"], rep
print("serve_bench --fleet smoke OK: %.0f rps, balance %.2f"
      % (rep["value"], rep["balance_ratio"]))
'
    # quantized serving smoke (docs/perf.md "Quantization & fused
    # kernels"): int8 weight-only generation must keep the AOT contract
    # (zero steady-state lowerings) AND pass the logits-equivalence
    # gate — per-step cosine >= 0.999 vs the f32 reference, enforced
    # both by serve_bench itself (exit 1) and re-asserted here
    JAX_PLATFORMS=cpu python tools/serve_bench.py --generate \
      --quantize int8 --check-logits --requests 24 --max-new 6 \
      | python -c '
import json, sys
rep = json.loads(sys.stdin.readlines()[-1])
assert rep["lowerings_after_warmup"] == 0, rep
assert rep["errors"] == 0, rep
assert rep["quantize"] == "int8" and rep["serving_dtype"] == "int8", rep
assert rep["logits_cosine_min"] >= 0.999, rep
print("quantized serve_bench smoke OK: %.0f tok/s at int8, "
      "logits cosine %.5f" % (rep["value"], rep["logits_cosine_min"]))
'
    ;;
  *)
    echo "unknown TASK=${TASK}" >&2
    exit 1
    ;;
esac
