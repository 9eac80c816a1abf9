"""Entrypoint: ``python -m pytorch_distributed_training_tpu.cli.main``.

Reproduces the reference driver's observable behavior (src/main.py:18-88) —
same seven flags with the same defaults, the same printed milestones (process
group info :42, device :59, start/end banners :66/:82, elapsed wall-clock
:84) — with its documented defects fixed toward intent (SURVEY.md §0):
trains on the *train* split, shards data per process, and maps process →
device without the reversed-modulo crash of src/main.py:52.

TPU semantics of the flags:
  --distributed  → multi-host: ``jax.distributed.initialize`` (replaces
                   ``dist.init_process_group``, src/main.py:39-41).
  --use-cpu      → run on the CPU backend.  The reference's CUDA-else-CPU
                   selection (src/main.py:56-57) is NOT reproduced: without
                   this flag a run that finds no accelerator is a usage
                   error, never a silent CPU run.
  --num-workers  → decode worker processes, as in DataLoader(num_workers=2).
"""

from __future__ import annotations

import contextlib
import time

import click

from ..utils.compile_cache import compile_events, compile_phase, compile_totals

# Model-config fields whose --model-overrides values are strings; all other
# keys take int/float/bool only (value typos must fail at parse time).
_STRING_OVERRIDE_KEYS = frozenset({"moe_dispatch", "hybrid_override_pattern"})
# --model-overrides keys whose value is a range "first:count" (a chip's
# contiguous share of a layer's experts, models/sdar.SdarConfig).
_RANGE_OVERRIDE_KEYS = frozenset({"experts_held"})


@click.command()
@click.option("--data-dir", default="./data", show_default=True, help="Dataset root.")
@click.option("--distributed", is_flag=True, help="Multi-host run (coordinator from env).")
@click.option("--use-cpu", is_flag=True, help="Force the CPU backend.")
@click.option("--cpu-devices", default=None, type=int,
              help="With --use-cpu: simulate this many CPU devices "
                   "(exercise dp/tp/sp meshes without TPU hardware).")
@click.option("--batch-size", default=32, show_default=True, help="Global batch size.")
@click.option("--num-workers", default=2, show_default=True, help="Decode worker processes.")
@click.option("--learning-rate", default=0.1, show_default=True)
@click.option("--weight-decay", default=0.001, show_default=True)
# --- extensions beyond the reference's 7 flags (BASELINE.json configs) ---
@click.option("--model", default="resnet18", show_default=True,
              help="resnet18|resnet50|vit_b16|gpt2")
@click.option("--dataset", default="cifar10", show_default=True,
              help="cifar10|shapes|synthetic-images|synthetic-tokens|"
                   "token-file:<path>|"
                   "imagefolder:<root>|packed-images:<path>")
@click.option("--synthetic-data", is_flag=True,
              help="Use synthetic data (zero-egress environments).")
@click.option("--epochs", default=1, show_default=True)
@click.option("--precision", default="f32", show_default=True, help="f32|bf16|bf16_full")
@click.option("--accum-steps", default=1, show_default=True,
              help="Gradient-accumulation microbatches per step.")
@click.option("--fsdp", default=1, show_default=True, help="FSDP mesh axis size.")
@click.option("--tensor-parallel", default=1, show_default=True, help="TP mesh axis size.")
@click.option("--pipeline-parallel", default=1, show_default=True,
              help="Pipeline stages (GPT-2 only; GPipe schedule).")
@click.option("--pipeline-schedule", default="gpipe", show_default=True,
              type=click.Choice(["gpipe", "1f1b", "interleaved"]),
              help="gpipe (autodiff backward) | 1f1b (fwd/bwd interleaving: "
                   "live activations bounded by stages, not microbatches; "
                   "per-stage recompute is built in, so --remat adds "
                   "nothing) | interleaved (multi-chunk 1F1B: "
                   "--pipeline-chunks model chunks per stage divide the "
                   "bubble by ~V). Microbatching belongs to "
                   "--pipeline-microbatches, not --accum-steps.")
@click.option("--pipeline-microbatches", default=None, type=int,
              help="Microbatches per pipeline step (default 2x stages).")
@click.option("--pipeline-chunks", default=2, show_default=True,
              help="Model chunks per stage (interleaved schedule only).")
@click.option("--sequence-parallel", default=1, show_default=True,
              help="Sequence-parallel attention shards (LM models).")
@click.option("--sequence-parallel-mode", default="ring", show_default=True,
              type=click.Choice(["ring", "ulysses"]),
              help="SP decomposition: ring (K/V rotation, any head count) "
                   "or ulysses (all-to-all head resharding, needs "
                   "heads divisible by shards).")
@click.option("--seed", default=0, show_default=True)
@click.option("--checkpoint-dir", default=None, help="Save a checkpoint per epoch.")
@click.option("--resume", is_flag=True, help="Resume from --checkpoint-dir if present.")
@click.option("--steps-per-epoch", default=None, type=int,
              help="Cap steps per epoch (smoke runs).")
@click.option("--image-size", default=32, show_default=True,
              help="Synthetic image side (224 for ImageNet-like runs).")
@click.option("--seq-len", default=1024, show_default=True, help="LM sequence length.")
@click.option("--profile-dir", default=None,
              help="Capture a jax.profiler trace into this dir: the whole "
                   "first epoch by default, or the --profile-steps window.")
@click.option("--profile-steps", default=None,
              help="START:STOP global-step window to trace (with "
                   "--profile-dir): bracket N steady-state steps instead "
                   "of the whole first epoch; the supervisor heartbeat is "
                   "beaten on every captured step so long captures are "
                   "never mistaken for hangs.")
@click.option("--metrics-dir", default=None,
              help="Telemetry spine (obs/): write this process's "
                   "schema-versioned structured event log "
                   "(events.rank*.jsonl) here — per-step records with "
                   "counter deltas (analytic DCN bytes under --grad-sync), "
                   "phase/heartbeat/anomaly flight-recorder events, a "
                   "compiled-cost record (FLOPs/bytes from "
                   "cost_analysis), and a closing summary.  Every process "
                   "writes its own file; merge with "
                   "tools/telemetry_report.py.")
@click.option("--log-format", default="jsonl", show_default=True,
              type=click.Choice(["jsonl", "tsv"]),
              help="--metrics-dir event format (tsv is write-only export; "
                   "the report tooling reads jsonl).")
@click.option("--trace", is_flag=True,
              help="Request-scoped tracing (obs/spans.py): record span "
                   "events into the --metrics-dir log — the full request "
                   "lifecycle (route decision, queue wait, prefill chunks, "
                   "per-tick decode/verify with slot attribution) under "
                   "--serve, per-step host spans (dispatch, host sync, "
                   "snapshot, checkpoint) in training.  Export with "
                   "tools/trace_export.py (Perfetto / chrome://tracing); "
                   "tools/telemetry_report.py adds the TTFT decomposition.")
@click.option("--trace-sample-rate", default=1.0, show_default=True,
              help="Fraction of requests (serve) / steps (train) traced "
                   "(--trace).  Deterministic per correlation id: a "
                   "sampled request records its WHOLE span chain, an "
                   "unsampled one records nothing.")
@click.option("--slo", default=None,
              help="Declared service objectives over the live telemetry "
                   "plane (obs/slo.py), e.g. "
                   "'ttft_p99=250ms,tpot_p99=40ms,goodput=0.99' (serve) "
                   "or 'step_time_p95=120ms' (train): Google-SRE "
                   "multi-window burn-rate alerts (fast 1m / slow 10m) "
                   "evaluated at every tick/step, each state transition "
                   "emitted as a schema-v4 alert event into the "
                   "--metrics-dir log and surfaced on /slo.  Requires "
                   "--metrics-dir (one spine, two sinks).")
@click.option("--metrics-port", default=None, type=int,
              help="Scrapeable ops endpoint (obs/http.py): a stdlib "
                   "background thread serving /metrics (Prometheus text "
                   "exposition of live counters/gauges/histogram "
                   "buckets), /healthz (heartbeat-staleness liveness), "
                   "and /slo (objective status + active burn-rate "
                   "alerts + live TTFT decomposition).  0 binds an "
                   "ephemeral port (printed).  Requires --metrics-dir.")
@click.option("--healthz-stale-s", default=60.0, show_default=True,
              help="/healthz staleness bound (--metrics-port): a "
                   "component whose last event/gauge is older than this "
                   "flips the probe to 503.  Liveness refreshes per "
                   "optimizer step (train) / scheduler tick (serve), so "
                   "set it comfortably above the step time — and expect "
                   "503 during the initial compile, before the first "
                   "step lands (readiness, not a crash).")
@click.option("--goodput", is_flag=True,
              help="Training goodput ledger (obs/ledger.py): classify "
                   "every second of the run into mutually exclusive "
                   "categories — compile, step_compute, grad_sync "
                   "(ICI/DCN split via the analytic wall model), "
                   "data_wait, ckpt_save, ckpt_restore, rework (steps "
                   "re-executed after a rollback or crash restart), "
                   "supervisor_backoff, other — with sum(categories) == "
                   "wall clock EXACT.  Live goodput_fraction + "
                   "per-category gauges on /metrics, a goodput block on "
                   "/slo, a goodput_ledger record in the event log "
                   "(tools/telemetry_report.py renders the fleet merge).  "
                   "Requires --metrics-dir; training runs only.")
@click.option("--lr-schedule", default="constant", show_default=True,
              help="constant|cosine|warmup-cosine")
@click.option("--warmup-steps", default=0, show_default=True,
              help="Linear warmup steps (warmup-cosine schedule).")
@click.option("--total-steps", default=None, type=int,
              help="Decay horizon for cosine schedules (defaults to epochs×len(loader)).")
@click.option("--zero1", is_flag=True,
              help="ZeRO-1 weight-update sharding (arXiv:2004.13336): "
                   "params stay replicated but optimizer slots and the "
                   "update math shard over the data axis.")
@click.option("--grad-sync", default="flat", show_default=True,
              type=click.Choice([
                  "flat", "hier", "hier-bf16", "hier-int8", "hier-int4",
                  "hier-topk",
              ]),
              help="Gradient all-reduce strategy (comm/hierarchical.py). "
                   "flat: XLA's implicit psum (DDP's allreduce, lowered "
                   "generically). hier: explicit two-tier sync — "
                   "reduce-scatter on ICI, cross-slice all-reduce of the "
                   "1/N shard on DCN, all-gather on ICI — overlapped with "
                   "the --accum-steps scan (DDP's bucket overlap). "
                   "hier-bf16/hier-int8/hier-int4 compress the DCN hop "
                   "(the lossy modes add per-bucket scales + error-"
                   "feedback residuals; int4 packs nibble pairs, 8x fewer "
                   "DCN bytes). hier-topk sends only the top "
                   "--grad-sync-topk-frac of each bucket by magnitude "
                   "(bitmap + int8 values, >=15x fewer bytes at 10%), "
                   "untransmitted coordinates re-fed via the same EF "
                   "residuals. Data-parallel meshes only (composes with "
                   "--zero1, which keeps gradients reduce-scattered for "
                   "the sharded update and skips the trailing all-gather).")
@click.option("--grad-sync-slices", default=None, type=int,
              help="Override the detected slice count for --grad-sync "
                   "(simulate a multi-slice DCN topology on CPU/single-"
                   "slice runs; the per-slice granules follow "
                   "make_hybrid_mesh's slice-major data-axis order).")
@click.option("--grad-sync-bucket-mb", default="auto", show_default=True,
              help="Gradient bucket size for --grad-sync: 'auto' derives "
                   "it from the DCN latency x bandwidth crossover per "
                   "compression mode (comm.compress.auto_bucket_mb — "
                   "replaces DDP's static bucket_cap_mb=25), or a number "
                   "in MB of f32 gradient.  The chosen size is recorded "
                   "in the grad_sync_model telemetry event.")
@click.option("--grad-sync-topk-frac", default=0.1, show_default=True,
              type=float,
              help="Transmitted fraction per bucket under --grad-sync "
                   "hier-topk (magnitude top-k).")
@click.option("--grad-sync-stripe", default="off", show_default=True,
              help="Multi-path DCN striping for the --grad-sync hier* DCN "
                   "hop (comm/striping.py): split each bucket's compressed "
                   "payload across N distinct slice-boundary crossing "
                   "edges via ICI lane rotations (NCCL's multi-channel "
                   "analogue; FlexLink arXiv:2510.15882) instead of one "
                   "serialized hop per rail.  'auto' uses min(ici, 4) "
                   "lanes, 'off' one, or pass an explicit lane count.  "
                   "Value-exact — gradients stay bitwise identical.  Also "
                   "stripes the --pp-compress stage-boundary payloads "
                   "when pipeline parallelism is on.")
@click.option("--grad-sync-overlap", default="off", show_default=True,
              type=click.Choice(["on", "off"]),
              help="ICI/DCN phase pipelining for the --grad-sync hier* "
                   "bucket walk (comm/striping.py): bucket i's DCN "
                   "all-reduce runs concurrently with bucket i+1's ICI "
                   "reduce-scatter and bucket i-1's ICI all-gather, so "
                   "the sync wall is max(ICI, DCN) + one fill/drain "
                   "bubble instead of their sum.  Value-exact (bitwise-"
                   "identical gradients); the modeled walls land in the "
                   "grad_sync_model telemetry event.")
@click.option("--pp-compress", default="none", show_default=True,
              type=click.Choice(["none", "bf16", "int8"]),
              help="Compress the pipeline stage-boundary ppermute "
                   "payloads (--pipeline-parallel), which otherwise cross "
                   "DCN uncompressed in bf16/f32 every tick: bf16 halves "
                   "them; int8 quarters them with per-token scales and "
                   "error-feedback residuals carried in the tick scan "
                   "(comm/compress.py — the same codec ladder as the "
                   "grad-sync DCN hop).  All three schedules.")
@click.option("--remat", is_flag=True,
              help="Rematerialize transformer blocks in the backward "
                   "(jax.checkpoint): trades ~33% forward FLOPs for "
                   "activation memory — long-context / deep-model runs.")
@click.option("--ce-chunk", default=None, type=int,
              help="LM loss: compute the head matmul + softmax-CE in "
                   "sequence chunks of this size instead of materializing "
                   "the (batch, seq, vocab) logits — unlocks large "
                   "per-chip batches (GPT-2's 50k vocab logits are ~6.6GB "
                   "f32 at batch 32 x 1024).")
@click.option("--device-cache", is_flag=True,
              help="Keep the whole dataset in device HBM and run shuffle/"
                   "crop/flip on-device (uint8 datasets that fit: cifar10, "
                   "shapes, packed-images) or, for LM runs, the token "
                   "corpus with on-device window sampling (token-file). "
                   "Zero steady-state host->device traffic. "
                   "Augmentation trade: crop boxes are drawn per-BATCH, not "
                   "per-sample as torchvision's RandomCrop draws them (the "
                   "per-sample form lowers to a ~1GB/s windowed gather at "
                   "224px); flips stay per-sample. Use the host loader when "
                   "per-sample crop diversity matters more than input speed.")
@click.option("--eval", "do_eval", is_flag=True,
              help="Run an evaluation pass on the held-out split after each epoch.")
@click.option("--eval-steps", default=None, type=int,
              help="Cap eval batches per pass (smoke runs).")
@click.option("--model-overrides", default=None,
              help="Comma-separated config overrides for LM models, "
                   "e.g. 'num_layers=2,hidden_dim=64,vocab_size=512'.")
@click.option("--metrics-jsonl", default=None,
              help="Append per-epoch metrics to this JSONL file.")
@click.option("--optimizer", default="adam", show_default=True,
              help="adam (coupled L2, torch Adam(weight_decay=) semantics, "
                   "src/main.py:63) | adamw (decoupled) | sgd (momentum, "
                   "coupled L2 — the classic ImageNet recipe).")
@click.option("--momentum", default=0.9, show_default=True,
              help="SGD momentum (torch SGD semantics; --optimizer sgd only).")
@click.option("--grad-clip", default=None, type=float,
              help="Global-norm gradient clipping (the GPT-2 recipe's 1.0).")
@click.option("--label-smoothing", default=0.0, show_default=True,
              help="CE label smoothing (the 90-epoch ResNet recipe's 0.1).")
@click.option("--serve", is_flag=True,
              help="Serve the model with the continuous-batching engine "
                   "(serve/) on a synthetic mixed-length request trace "
                   "instead of training — LM models only.  Restores "
                   "params from --checkpoint-dir when a committed step "
                   "exists (the served model IS the training artifact); "
                   "otherwise serves fresh-init weights with a warning.  "
                   "--metrics-jsonl appends one per-request record per "
                   "finished request.")
@click.option("--serve-requests", default=16, show_default=True,
              help="Synthetic requests in the trace (--serve).")
@click.option("--serve-rate", default=0.0, show_default=True,
              help="Offered load in requests/sec, Poisson arrivals "
                   "(0 = all requests arrive at t=0; --serve).")
@click.option("--serve-slots", default=4, show_default=True,
              help="Concurrent decode slots (KV-cache pool rows; --serve).")
@click.option("--serve-max-new", default=32, show_default=True,
              help="Per-request generation budget cap (--serve).")
@click.option("--serve-prefill-chunk", default=16, show_default=True,
              help="Prompt tokens prefetched into the cache per prefill "
                   "tick (chunked prefill; --serve).")
@click.option("--serve-paged", is_flag=True,
              help="Paged KV cache (--serve): fixed-size blocks + per-slot "
                   "block tables instead of contiguous max_len-per-slot "
                   "rows — admission is bounded by the GLOBAL block pool, "
                   "and shared prompt prefixes skip prefill via the "
                   "hash-addressed block cache.")
@click.option("--serve-block-size", default=16, show_default=True,
              help="KV positions per physical block (--serve-paged); also "
                   "the prefix-cache sharing granularity.")
@click.option("--serve-kv-dtype", default="bf16", show_default=True,
              type=click.Choice(["bf16", "int8", "int4"]),
              help="KV-cache storage dtype (--serve-paged): bf16 stores "
                   "K/V in the model's native compute dtype (status "
                   "quo); int8/int4 quantize the paged blocks with "
                   "per-position-per-head bf16 scales — encoded at the "
                   "pool's write path, dequantized inside the paged "
                   "Pallas kernels — so the same HBM byte budget holds "
                   "~2-4x more live slots (and host-tier spills shrink "
                   "by the same factor).")
@click.option("--serve-num-blocks", default=0, show_default=True,
              help="Physical blocks in the pool (--serve-paged); 0 sizes "
                   "it byte-equivalent to the contiguous pool "
                   "(slots x ceil(max_len / block_size)).")
@click.option("--serve-spec", is_flag=True,
              help="Speculative decoding (--serve): a model-free "
                   "prompt-lookup drafter proposes up to --serve-spec-k "
                   "continuation tokens per slot per tick and a third "
                   "AOT-compiled program verifies them in ONE forward "
                   "pass — accepted tokens amortize the per-tick "
                   "param/KV-cache read.  Greedy output is token-exact "
                   "vs the plain engine; sampling uses rejection-style "
                   "acceptance under the identical distribution.")
@click.option("--serve-spec-k", default=4, show_default=True,
              help="Max draft tokens verified per slot per tick "
                   "(--serve-spec).")
@click.option("--serve-spec-ngram", default=4, show_default=True,
              help="Longest suffix n-gram the prompt-lookup drafter "
                   "matches (--serve-spec; the match floor rides one "
                   "below it); also the shared cross-request index "
                   "granularity.")
@click.option("--serve-tp", default=1, show_default=True,
              help="Tensor-parallel size per serving replica (--serve): "
                   "all three AOT programs compile against a NamedSharding "
                   "over a tensor=N submesh — params via the megatron "
                   "column/row rules (tp_rules_for), the KV pool sharded "
                   "on the heads axis.  Greedy output stays token-exact "
                   "vs the single-device engine.  1 = unsharded.")
@click.option("--serve-replicas", default=1, show_default=True,
              help="Independent engine replicas behind one router "
                   "(--serve): replica k compiles its programs on devices "
                   "[k*tp, (k+1)*tp) and requests route by prefix-cache "
                   "affinity + least-loaded dispatch (serve/router.py).  "
                   "Needs serve_tp x serve_replicas devices.")
@click.option("--serve-affinity/--no-serve-affinity", default=True,
              show_default=True,
              help="Prefix-cache-affinity routing (--serve-replicas > 1, "
                   "paged engines): a prompt whose hash-chained prefix is "
                   "hot on replica k lands on replica k unless k is "
                   "saturated; off = pure least-loaded dispatch.")
@click.option("--serve-ttl", default=None, type=float,
              help="Deadline in seconds after arrival (--serve): a "
                   "request still queued past it is shed (finish reason "
                   "'shed'); one already decoding is retired at the next "
                   "tick (finish reason 'cancelled'), freeing its slot "
                   "and paged blocks instead of finishing a response the "
                   "caller timed out on.  Both are excluded from goodput.")
@click.option("--serve-disagg", default=None, metavar="P:D",
              help="Disaggregated prefill/decode serving (--serve): split "
                   "each replica into a P-slot prefill-role pool and a "
                   "D-slot decode-role pool (serve/disagg.py) with KV "
                   "handoff through the shared paged block pool (or a "
                   "row copy, contiguous) — a long-prompt burst stops "
                   "inflating every co-scheduled request's decode TPOT.  "
                   "Replaces --serve-slots for the split engine.")
@click.option("--serve-kv-host-mb", default=0.0, show_default=True,
              help="Host-RAM KV tier capacity in MB (--serve-paged): "
                   "evicted refcount-0 prefix blocks SPILL there (LRU, "
                   "capacity-bounded) and are restored bit-identically on "
                   "a hash-chain hit instead of recomputed "
                   "(serve/kv_store.py).  0 = no host tier (evictions "
                   "vanish, exactly as before).")
@click.option("--serve-inject-faults", default=None, metavar="SPEC",
              help="Serving-tier chaos plane (resilience/faults.py): "
                   "comma-separated kind@tick[:replica[:arg]] with kinds "
                   "replica_crash[:role], replica_stall[:ticks], "
                   "replica_slow:factor, handoff_drop — evaluated at "
                   "router tick boundaries, each fires once per run "
                   "(markers persist in <ckpt-dir>/.fault_state across "
                   "supervised relaunches).  Forces the replica router "
                   "even at --serve-replicas 1.  Chaos testing only.")
@click.option("--serve-failover/--no-serve-failover", default=True,
              show_default=True,
              help="Router-level replica failover (serve/failover.py, "
                   "multi-replica or chaos runs): missed-tick/heartbeat "
                   "death detection, fence + drain, token-exact requeue "
                   "of a dead replica's queued and in-flight requests "
                   "onto survivors, exactly-once retirement, brown-out "
                   "shedding, backoff-scheduled respawn.  --no-serve-"
                   "failover is the control: a dead replica strands its "
                   "work (expect a hung run under replica faults).")
@click.option("--serve-retry-budget", default=2, show_default=True, type=int,
              help="Failover re-placements a request may consume before "
                   "it is retired with finish reason 'failed' "
                   "(--serve-failover).")
@click.option("--serve-brownout-s", default=0.0, show_default=True,
              type=float,
              help="Brown-out margin (--serve-failover): while the tier "
                   "is under capacity after a replica death, queued "
                   "requests shed this many seconds BEFORE their "
                   "--serve-ttl deadline instead of at it.")
@click.option("--serve-autoscale", is_flag=True,
              help="Closed-loop autoscaling (serve/autoscale.py): the "
                   "fleet compiles at --serve-replicas up front, spares "
                   "park, and a controller on the router tick revives/"
                   "retires replicas from queue depth + SLO burn alerts, "
                   "re-splits disagg roles from the live TTFT "
                   "decomposition, and walks a pressure ladder "
                   "(host-tier shedding, brown-out) before dropping "
                   "work.  Zero new compiles per action; every action is "
                   "a schema'd autoscale_action event with its cause.  "
                   "Implies the router path and needs --serve-failover.")
@click.option("--serve-autoscale-min", default=1, show_default=True,
              type=int,
              help="Floor of active replicas (--serve-autoscale); the "
                   "controller starts here and parks the rest.")
@click.option("--serve-autoscale-max", default=0, show_default=True,
              type=int,
              help="Ceiling of active replicas (--serve-autoscale); "
                   "0 = the full compiled fleet (--serve-replicas).")
@click.option("--serve-autoscale-up-depth", default=8, show_default=True,
              type=int,
              help="Queued requests across the tier (incl. the failover "
                   "pending buffer) that count as scale-up pressure "
                   "(--serve-autoscale).")
@click.option("--serve-autoscale-down-idle", default=32, show_default=True,
              type=int,
              help="Consecutive fully-idle ticks before one replica is "
                   "drained and parked (--serve-autoscale).")
@click.option("--serve-autoscale-cooldown", default=16, show_default=True,
              type=int,
              help="Minimum ticks between replica-count actions "
                   "(--serve-autoscale).")
@click.option("--serve-priority", default=None, metavar="SPEC",
              help="Priority classes for SLO-weighted admission "
                   "(serve/policy.py): 'interactive=4,batch=1' maps "
                   "tenant names to scheduling weights popped by "
                   "weighted deficit over the tenant-fair queue; "
                   "per-class --slo objectives "
                   "(ttft_p99[interactive]=250ms) boost a class while "
                   "its live window is out of budget.")
@click.option("--elastic", is_flag=True,
              help="Supervise the run: restart on crash/hang, resuming from "
                   "--checkpoint-dir (torchelastic equivalent).  Crash "
                   "relaunches back off exponentially with jitter; a "
                   "preemption exit (SIGTERM -> step checkpoint -> exit 75) "
                   "relaunches immediately without charging --max-restarts.")
@click.option("--max-restarts", default=3, show_default=True,
              help="Restart budget under --elastic.")
@click.option("--heartbeat-timeout", default=600.0, show_default=True,
              help="Seconds without training progress before a hung run is "
                   "killed (--elastic).")
@click.option("--ckpt-every-steps", default=None, type=int,
              help="Mid-epoch checkpoint cadence (global steps): async "
                   "step-granular saves so a crash/preemption loses at most "
                   "this many steps; resume skips the consumed batches of "
                   "the partial epoch deterministically (requires "
                   "--checkpoint-dir).")
@click.option("--skip-bad-steps", is_flag=True,
              help="Jit-safe anomaly skip policy (resilience/): a step with "
                   "non-finite loss/grads (or grad norm over "
                   "--grad-spike-threshold) becomes a no-op update instead "
                   "of halting or poisoning params; K consecutive bad steps "
                   "roll params back to the last host snapshot, R rollbacks "
                   "abort for a supervised restart.")
@click.option("--grad-spike-threshold", default=None, type=float,
              help="Skip finite steps whose global grad norm exceeds this "
                   "(--skip-bad-steps; default: non-finite only).")
@click.option("--rollback-after", default=8, show_default=True, type=int,
              help="Consecutive skipped steps before rolling back to the "
                   "last-good snapshot (--skip-bad-steps).")
@click.option("--max-rollbacks", default=2, show_default=True, type=int,
              help="Rollbacks before aborting the run for a supervised "
                   "restart (--skip-bad-steps).")
@click.option("--snapshot-every-steps", default=200, show_default=True,
              type=int,
              help="Host-snapshot staging cadence for the rollback path "
                   "(--skip-bad-steps).")
@click.option("--inject-faults", default=None,
              help="Deterministic fault injection (resilience/faults.py): "
                   "comma-separated kind@step[:arg] with kinds crash, "
                   "stall, sigterm, nan_batch, spike_batch, ckpt_truncate "
                   "— each fires once per run (markers persist across "
                   "supervised relaunches in <ckpt-dir>/.fault_state).  "
                   "Chaos testing only.")
@click.option("--elastic-resize", default=None, metavar="SPEC",
              help="Elastic membership chaos episode "
                   "(resilience/elastic.py): comma-separated "
                   "kind@step[:arg] with kinds slice_lost@N:K, "
                   "slice_return@N, host_hang@N[:S].  Unlike --elastic, "
                   "a lost slice does NOT kill the run: the survivors "
                   "restore from the peer-RAM snapshot tier, shrink the "
                   "mesh, scale grad accumulation to preserve the global "
                   "batch, and grow back when the slice returns.")
def main(**opts):
    from ..utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if opts.pop("elastic", False):
        _run_elastic(
            opts,
            max_restarts=opts.pop("max_restarts"),
            heartbeat_timeout=opts.pop("heartbeat_timeout"),
        )
        return
    opts.pop("max_restarts", None)
    opts.pop("heartbeat_timeout", None)
    elastic_resize = opts.pop("elastic_resize", None)
    if elastic_resize is not None:
        _run_elastic_resize(elastic_resize, opts)
        return
    run(**opts)


def _opts_to_argv(opts: dict) -> list[str]:
    """Serialize parsed options back to an argv for the supervised child.

    Built from the *parsed* options (not sys.argv) so programmatic
    invocations (tests, notebooks) supervise the intended command rather
    than the host process's argv.  Flag spellings and kinds come from the
    click command itself: a hand-kept table of which options are boolean
    went stale three flags ago and every --elastic child died on
    ``unexpected extra arguments (False False False)``.
    """
    params = {p.name: p for p in main.params}
    argv: list[str] = []
    for key, value in opts.items():
        param = params[key]
        if param.is_flag:
            # --x/--no-x toggles are spelled out either way; a plain flag
            # is emitted bare, only when set.
            if param.secondary_opts:
                argv.append(
                    param.opts[0] if value else param.secondary_opts[0]
                )
            elif value:
                argv.append(param.opts[0])
        elif value is not None:
            argv.extend([param.opts[0], str(value)])
    return argv


def _run_elastic(opts: dict, *, max_restarts, heartbeat_timeout):
    """Re-execute this entrypoint under the failure supervisor.

    The reference's failure story is three asserts (src/main.py:36-38) and a
    hang; this is the torchelastic-equivalent: crash or heartbeat stall →
    relaunch with --resume, restoring the latest checkpoint and continuing
    at the right epoch.
    """
    import os
    import sys

    from ..utils.supervisor import supervise

    checkpoint_dir = opts.get("checkpoint_dir")
    if not checkpoint_dir:
        raise click.UsageError("--elastic requires --checkpoint-dir to resume into")
    os.makedirs(checkpoint_dir, exist_ok=True)
    child_opts = {
        k: v for k, v in opts.items()
        if k not in ("max_restarts", "heartbeat_timeout")
    }
    argv = _opts_to_argv(child_opts)
    child = [sys.executable, "-m", "pytorch_distributed_training_tpu.cli.main", *argv]
    result = supervise(
        child,
        max_restarts=max_restarts,
        heartbeat_path=os.path.join(checkpoint_dir, ".heartbeat"),
        heartbeat_timeout_s=heartbeat_timeout,
    )
    if result.restarts or result.hung_kills or result.preemptions:
        print(
            f"supervisor: finished after {result.restarts} restarts "
            f"({result.hung_kills} hang kills, {result.preemptions} "
            f"preemptions), exit {result.exit_code}"
        )
    # Signal deaths (negative Popen codes) map to the 128+N shell convention
    # (e.g. SIGKILL -> 137) so orchestration tooling sees the usual status.
    code = result.exit_code
    sys.exit(128 + abs(code) if code < 0 else code)


def _select_backend(use_cpu, cpu_devices, *, distributed=False):
    """Apply --use-cpu/--cpu-devices.  Must run before anything touches
    devices; itself it touches them only to verify --cpu-devices, and not
    under --distributed, where ``jax.distributed.initialize`` has to come
    first (the start line shows the count there)."""
    import jax

    if not use_cpu:
        if cpu_devices:
            raise click.UsageError("--cpu-devices requires --use-cpu")
        return
    jax.config.update("jax_platforms", "cpu")
    if not cpu_devices:
        return
    from ..compat import set_cpu_device_count

    try:
        set_cpu_device_count(int(cpu_devices))
    except RuntimeError as e:  # backend already initialized
        raise click.UsageError(
            f"--cpu-devices must be set before JAX initializes its "
            f"backends; this process already touched devices ({e})"
        )
    if not distributed and jax.local_device_count() != int(cpu_devices):
        raise click.UsageError(
            f"--cpu-devices {cpu_devices} did not take effect "
            f"({jax.local_device_count()} devices visible); the "
            "backend was initialized before this flag was applied"
        )


def _require_accelerator(use_cpu):
    """Without --use-cpu the run is for an accelerator: a CPU default
    backend means JAX found none (or was held to the CPU), and carrying on
    would report CPU work under a device's name."""
    import jax

    if not use_cpu and jax.default_backend() == "cpu":
        raise click.UsageError(
            "JAX found no accelerator (the default backend is 'cpu'); "
            "pass --use-cpu to run on the CPU on purpose"
        )


def _run_elastic_resize(spec: str, opts: dict):
    """One scripted elastic episode on the simulated multi-slice mesh.

    The chaos driver for the membership plane: parses the elastic fault
    plan, runs the episode (shrink on slice loss, peer-RAM restore,
    grow-back), and prints the audited outcome.  Deterministic — the
    same spec and seed replay the identical episode.
    """
    import json
    import os

    _select_backend(opts.get("use_cpu"), opts.get("cpu_devices"))
    _require_accelerator(opts.get("use_cpu"))

    from ..obs import MetricsEmitter
    from ..resilience.elastic import ElasticConfig, run_elastic_episode
    from ..resilience.faults import parse_elastic_faults

    faults = parse_elastic_faults(spec)
    # Run past the last scripted fault so detection (patience) and the
    # grow-back both land inside the episode.
    n_steps = max(8, max((f.step for f in faults), default=0) + 3)
    cadence = opts.get("snapshot_every_steps") or 2
    config = ElasticConfig(snapshot_every_steps=min(cadence, n_steps))
    checkpoint_dir = opts.get("checkpoint_dir")
    state_dir = (
        os.path.join(checkpoint_dir, ".elastic_state")
        if checkpoint_dir else None
    )
    emitter = MetricsEmitter(opts.get("metrics_dir"), rank=0, world=1)
    report = run_elastic_episode(
        faults=faults, n_steps=n_steps, config=config,
        seed=opts.get("seed") or 0, emitter=emitter, state_dir=state_dir,
    )
    emitter.summary()
    emitter.close()
    ledger = report["ledger"]
    print(
        f"elastic: world {report['world']['initial']} -> "
        f"{report['world']['final']} over {len(report['transitions'])} "
        f"transitions, final step {report['final_step']}"
    )
    for t in report["transitions"]:
        print(
            f"elastic: {t['transition']}@{t['step']} "
            f"{t['world_from']} -> {t['world_to']}"
        )
    print(
        f"elastic: peer restore bit-identical: "
        f"{report['restore_bit_identical']}; ledger identity_ok: "
        f"{ledger['identity_ok']} "
        f"(rework {ledger['seconds']['rework']:.3f}s of "
        f"{ledger['wall_s']:.3f}s wall)"
    )
    print("elastic: counters " + json.dumps(report["counters"], sort_keys=True))


def run(
    data_dir, distributed, use_cpu, batch_size, num_workers,
    learning_rate,
    weight_decay, model, dataset, synthetic_data, epochs, precision,
    accum_steps, fsdp, tensor_parallel, seed, checkpoint_dir, resume,
    steps_per_epoch, image_size, seq_len, profile_dir,
    profile_steps=None, metrics_dir=None, log_format="jsonl",
    trace=False, trace_sample_rate=1.0, slo=None, metrics_port=None,
    healthz_stale_s=60.0, goodput=False,
    lr_schedule="constant", warmup_steps=0, total_steps=None,
    do_eval=False, eval_steps=None, model_overrides=None, metrics_jsonl=None,
    optimizer="adam", pipeline_parallel=1, pipeline_microbatches=None,
    pipeline_schedule="gpipe", pipeline_chunks=2,
    sequence_parallel=1, sequence_parallel_mode="ring", grad_clip=None,
    device_cache=False, remat=False, ce_chunk=None, cpu_devices=None,
    momentum=0.9, label_smoothing=0.0, zero1=False,
    grad_sync="flat", grad_sync_slices=None,
    grad_sync_bucket_mb="auto", grad_sync_topk_frac=0.1,
    grad_sync_stripe="off", grad_sync_overlap="off", pp_compress="none",
    serve=False, serve_requests=16, serve_rate=0.0, serve_slots=4,
    serve_max_new=32, serve_prefill_chunk=16, serve_paged=False,
    serve_block_size=16, serve_num_blocks=0, serve_kv_dtype="bf16",
    serve_ttl=None,
    serve_spec=False, serve_spec_k=4, serve_spec_ngram=4,
    serve_tp=1, serve_replicas=1, serve_affinity=True,
    serve_disagg=None, serve_kv_host_mb=0.0,
    serve_inject_faults=None, serve_failover=True, serve_retry_budget=2,
    serve_brownout_s=0.0,
    serve_autoscale=False, serve_autoscale_min=1, serve_autoscale_max=0,
    serve_autoscale_up_depth=8, serve_autoscale_down_idle=32,
    serve_autoscale_cooldown=16, serve_priority=None,
    ckpt_every_steps=None, skip_bad_steps=False, grad_spike_threshold=None,
    rollback_after=8, max_rollbacks=2, snapshot_every_steps=200,
    inject_faults=None,
):
    _select_backend(use_cpu, cpu_devices, distributed=distributed)

    import jax
    import jax.numpy as jnp
    import optax

    from .. import comm, data as data_lib
    from ..models import create_model
    from ..parallel.sharding import DDP_RULES, tp_rules_for
    from ..train import (
        Trainer, TrainerConfig, create_train_state, make_policy, make_train_step,
    )
    from ..utils import metrics as metrics_lib

    if distributed:
        # Replaces the reference's assert-guarded init_process_group block
        # (src/main.py:35-42); rank/world size are discovered, not env asserts.
        comm.initialize()
    _require_accelerator(use_cpu)
    print(
        f"process {comm.process_index()}/{comm.process_count()} | "
        f"platform={jax.devices()[0].platform} | "
        f"device_kind={jax.devices()[0].device_kind} | "
        f"devices={jax.local_device_count()}"
    )

    # Cheap flag validations FIRST — a typo'd compression flag must fail
    # here, not after minutes of model init + XLA compile.
    if pp_compress != "none" and pipeline_parallel <= 1:
        raise click.UsageError(
            "--pp-compress compresses pipeline stage-boundary payloads; "
            "it needs --pipeline-parallel > 1"
        )
    if grad_sync == "flat" and grad_sync_slices is not None:
        raise click.UsageError(
            "--grad-sync-slices only affects the explicit two-tier sync; "
            "pass --grad-sync hier|hier-bf16|hier-int8|hier-int4|hier-topk "
            "with it (the flat GSPMD psum has no slice parameter to "
            "simulate)"
        )
    if grad_sync == "flat" and pp_compress == "none" \
            and str(grad_sync_stripe) != "off":
        raise click.UsageError(
            "--grad-sync-stripe lanes the explicit two-tier sync's DCN hop "
            "(and --pp-compress stage boundaries); the flat GSPMD psum has "
            "no DCN hop to stripe — pass a --grad-sync mode or "
            "--pp-compress with it"
        )
    if grad_sync == "flat" and grad_sync_overlap != "off":
        raise click.UsageError(
            "--grad-sync-overlap pipelines the explicit two-tier sync's "
            "ICI/DCN phases across buckets; the flat GSPMD psum has no "
            "phases to pipeline — pass a --grad-sync mode with it"
        )
    if str(grad_sync_stripe) not in ("auto", "off"):
        try:
            grad_sync_stripe = int(grad_sync_stripe)
        except ValueError:
            raise click.UsageError(
                f"--grad-sync-stripe must be 'auto', 'off', or a lane "
                f"count, got {grad_sync_stripe!r}"
            )
        if grad_sync_stripe < 1:
            raise click.UsageError(
                f"--grad-sync-stripe must be >= 1, got {grad_sync_stripe}"
            )
    if grad_sync == "flat" and str(grad_sync_bucket_mb) != "auto":
        raise click.UsageError(
            "--grad-sync-bucket-mb sizes the explicit two-tier sync's "
            "buckets; the flat GSPMD psum has none — pass a --grad-sync "
            "mode with it"
        )
    if str(grad_sync_bucket_mb) != "auto":
        try:
            grad_sync_bucket_mb = float(grad_sync_bucket_mb)
        except ValueError:
            raise click.UsageError(
                f"--grad-sync-bucket-mb must be 'auto' or a number (MB), "
                f"got {grad_sync_bucket_mb!r}"
            )
        if grad_sync_bucket_mb <= 0:
            raise click.UsageError(
                f"--grad-sync-bucket-mb must be > 0, got "
                f"{grad_sync_bucket_mb}"
            )
    else:
        grad_sync_bucket_mb = "auto"

    profile_window = None
    if profile_steps is not None:
        if not profile_dir:
            raise click.UsageError("--profile-steps requires --profile-dir")
        lo, sep, hi = profile_steps.partition(":")
        try:
            profile_window = (int(lo), int(hi))
        except ValueError:
            raise click.UsageError(
                f"--profile-steps must be START:STOP, got {profile_steps!r}"
            )
        if not sep or profile_window[0] < 0 \
                or profile_window[1] <= profile_window[0]:
            raise click.UsageError(
                f"--profile-steps window must satisfy 0 <= START < STOP, "
                f"got {profile_steps!r}"
            )

    # Telemetry spine (obs/): one rank-tagged event log per process.  The
    # emitter is built disabled when --metrics-dir is absent, so every
    # wiring point below threads one object unconditionally.
    from ..obs import MetricsEmitter

    emitter = MetricsEmitter(
        metrics_dir, rank=comm.process_index(), world=comm.process_count(),
        log_format=log_format, meta={
            "mode": "serve" if serve else "train", "model": model,
            "dataset": dataset, "precision": precision,
            "batch_size": batch_size, "accum_steps": accum_steps,
            "grad_sync": grad_sync, "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "device_count": jax.device_count(),
        },
    )
    # Span spine (--trace): spans ride the same event log, so tracing
    # needs the emitter live; the jsonl reader side (trace_export,
    # telemetry_report) is the only consumer.
    spans = None
    if trace:
        if not emitter.enabled:
            raise click.UsageError(
                "--trace records span events into the --metrics-dir log; "
                "pass --metrics-dir"
            )
        if log_format != "jsonl":
            raise click.UsageError(
                "--trace needs --log-format jsonl (the exporter and the "
                "TTFT decomposition read spans back)"
            )
        from ..obs import SpanRecorder

        spans = SpanRecorder(emitter, sample_rate=trace_sample_rate)

    # Goodput ledger (--goodput, obs/ledger.py): constructed as early as
    # possible so startup (model init, data open) is on the books as
    # "other" rather than invisible.  The progress file under the
    # checkpoint dir carries the restart-rework watermark across
    # supervised relaunches; without a checkpoint dir there is no restart
    # path to attribute, so it is simply absent.
    ledger = None
    if goodput:
        if serve:
            raise click.UsageError(
                "--goodput attributes a TRAINING run's wall clock; "
                "serving goodput is the --slo plane's job"
            )
        if not emitter.enabled:
            raise click.UsageError(
                "--goodput writes the goodput_ledger record into the "
                "--metrics-dir log; pass --metrics-dir"
            )
        import os as _ledger_os

        from ..obs import GoodputLedger

        ledger = GoodputLedger(
            clock=emitter.clock,
            progress_path=(
                _ledger_os.path.join(checkpoint_dir, ".progress")
                if checkpoint_dir else None
            ),
        )

    # Live SLO plane (--slo / --metrics-port): the aggregator and the
    # burn-rate policy tee from the SAME emitter (one spine, two sinks),
    # so they only exist where the JSONL spine does — and the offline
    # report of the run's log reduces to exactly the live numbers.
    live_agg = None
    slo_policy = None
    ops_server = None
    if slo is not None or metrics_port is not None:
        if not emitter.enabled:
            raise click.UsageError(
                "--slo/--metrics-port aggregate the telemetry spine "
                "live; pass --metrics-dir"
            )
        from ..obs import LiveAggregator, OpsServer, SLOPolicy, parse_slo_spec

        live_agg = LiveAggregator(clock=emitter.clock)
        try:
            objectives = parse_slo_spec(slo) if slo else []
        except ValueError as e:
            raise click.UsageError(f"--slo: {e}")
        slo_policy = SLOPolicy(live_agg, objectives, emitter=emitter)
        emitter.attach_sink(live_agg)
        emitter.attach_sink(slo_policy)  # anomaly -> alert promotion
        if metrics_port is not None:
            ops_server = OpsServer(
                live_agg, slo_policy, port=metrics_port,
                stale_after_s=healthz_stale_s, ledger=ledger,
            ).start()
            print(
                f"ops endpoint: {ops_server.url} (/metrics /healthz /slo)"
            )

    # Fault-injection plane (resilience/faults.py): chaos specs arm
    # deterministic faults at named global steps; fired-markers persist
    # under the checkpoint dir so a supervised relaunch (which resumes
    # BELOW the fault step) does not refire them.
    import os as _os_mod

    faults = None
    fault_spec = inject_faults or _os_mod.environ.get("PDT_FAULTS")
    if fault_spec:
        from ..resilience import FaultInjector

        fault_state = (
            _os_mod.path.join(checkpoint_dir, ".fault_state")
            if checkpoint_dir else None
        )
        faults = FaultInjector.from_spec(
            fault_spec, state_dir=fault_state,
            emitter=emitter if emitter.enabled else None,
        )

    mesh_cfg = comm.MeshConfig(
        data=-1, fsdp=fsdp, tensor=tensor_parallel,
        pipeline=pipeline_parallel, sequence=sequence_parallel,
    )
    mesh = comm.make_mesh(mesh_cfg)
    print(f"mesh: {dict(mesh.shape)}")

    # --- dataset (L5) ---
    from ..models.registry import MODEL_REGISTRY

    if model not in MODEL_REGISTRY:
        raise click.BadParameter(
            f"unknown model {model!r}; available: {sorted(MODEL_REGISTRY)}"
        )
    model_kind = MODEL_REGISTRY[model].kind
    overrides = {}
    if model_overrides:
        for item in model_overrides.split(","):
            if not item.strip():
                continue  # tolerate trailing commas
            k, sep, v = item.partition("=")
            k, v = k.strip(), v.strip()
            if not sep or not k or not v:
                raise click.BadParameter(
                    f"--model-overrides entry {item!r} is not key=value"
                )
            if v.lower() in ("true", "false"):
                overrides[k] = v.lower() == "true"
                continue
            if k in _RANGE_OVERRIDE_KEYS:
                try:
                    first, count = (int(x) for x in v.split(":"))
                except ValueError:
                    raise click.BadParameter(
                        f"--model-overrides {k} takes first:count, got {v!r}"
                    )
                overrides[k] = (first, count)
                continue
            try:
                overrides[k] = int(v)
            except ValueError:
                try:
                    overrides[k] = float(v)
                except ValueError:
                    # Only declared string-typed config fields may take
                    # non-numeric values; anything else is a value typo
                    # (e.g. hidden_dim=7a68) and must fail here, not as
                    # an obscure TypeError deep inside tracing.
                    if k in _STRING_OVERRIDE_KEYS:
                        overrides[k] = v
                    else:
                        raise click.BadParameter(
                            f"--model-overrides value for {k!r} must be "
                            f"int/float/bool, got {v!r}"
                        )
    if remat:
        if model.startswith("resnet"):
            raise click.UsageError(
                "--remat applies to transformer models (gpt2*, vit_*); "
                "ResNet's fused-BN path already minimizes saved activations"
            )
        overrides["remat"] = True
    if serve:
        if model_kind != "lm":
            raise click.UsageError(
                "--serve requires a transformer LM (--model gpt2*)"
            )
        try:
            return _run_serve(
                model=model, overrides=overrides, precision=precision,
                checkpoint_dir=checkpoint_dir, seed=seed, seq_len=seq_len,
                metrics_jsonl=metrics_jsonl, n_requests=serve_requests,
                rate=serve_rate, num_slots=serve_slots, max_new=serve_max_new,
                prefill_chunk=serve_prefill_chunk, emitter=emitter,
                paged=serve_paged, block_size=serve_block_size,
                num_blocks=serve_num_blocks, kv_dtype=serve_kv_dtype,
                ttl=serve_ttl,
                spec_k=serve_spec_k if serve_spec else 0,
                spec_ngram=serve_spec_ngram,
                tp=serve_tp, replicas=serve_replicas, affinity=serve_affinity,
                disagg=serve_disagg, kv_host_mb=serve_kv_host_mb,
                inject_faults=serve_inject_faults, failover=serve_failover,
                retry_budget=serve_retry_budget,
                brownout_s=serve_brownout_s,
                autoscale=serve_autoscale,
                autoscale_min=serve_autoscale_min,
                autoscale_max=serve_autoscale_max,
                autoscale_up_depth=serve_autoscale_up_depth,
                autoscale_down_idle=serve_autoscale_down_idle,
                autoscale_cooldown=serve_autoscale_cooldown,
                priority=serve_priority,
                healthz_stale_s=healthz_stale_s,
                spans=spans, slo_policy=slo_policy, ops_server=ops_server,
            )
        finally:
            if ops_server is not None:
                ops_server.stop()
    kind = "image_classifier"
    eval_ds = None
    input_normalize = None
    if dataset == "cifar10":
        ds = data_lib.cifar10(data_dir, train=True, synthetic=synthetic_data)
        num_classes = len(ds.classes)
        if do_eval:
            eval_ds = data_lib.cifar10(data_dir, train=False, synthetic=synthetic_data)
    elif dataset == "synthetic-images":
        ds = data_lib.SyntheticImages(image_size=image_size, num_classes=1000)
        num_classes = 1000
        if do_eval:
            eval_ds = data_lib.SyntheticImages(
                n=1000, image_size=image_size, num_classes=1000, seed=1
            )
    elif dataset == "shapes":
        # Learnable procedural 10-class set (CIFAR-10-shaped records): the
        # convergence-evidence dataset for the zero-egress sandbox, where
        # the reference's CIFAR-10 download (src/main.py:47) is impossible.
        # Train and val are disjoint iid draws (split-salted RNG streams).
        ds = data_lib.ShapeImages(n=50_000, train=True, seed=seed)
        num_classes = len(ds.classes)
        if do_eval:
            eval_ds = data_lib.ShapeImages(n=10_000, train=False, seed=seed)
    elif dataset == "synthetic-tokens":
        # Token range must match the model's embedding table — a shrunken
        # --model-overrides vocab_size with default-range tokens silently
        # degrades to clamped lookups.
        vocab = int(overrides.get("vocab_size", 50257))
        ds = data_lib.SyntheticTokens(seq_len=seq_len, vocab_size=vocab)
        kind, num_classes = "lm", None
        if do_eval:
            eval_ds = data_lib.SyntheticTokens(
                n=512, seq_len=seq_len, vocab_size=vocab, seed=1
            )
    elif dataset.startswith("imagefolder:"):
        # torchvision-style class-folder JPEG tree with the standard ImageNet
        # recipe (the reference's transform slot, src/main.py:44-46, filled
        # with RandomResizedCrop/flip/normalize); decode parallelized by
        # --num-workers like DataLoader(num_workers=2) (src/main.py:61, 23).
        # The conventional root/train + root/val layout provides the held-out
        # eval split; a flat root falls back to training images with a
        # warning (no silent train-as-eval).
        root = dataset.split(":", 1)[1]
        import os as _os

        train_root, eval_root = root, root
        if _os.path.isdir(_os.path.join(root, "train")):
            train_root = _os.path.join(root, "train")
            if _os.path.isdir(_os.path.join(root, "val")):
                eval_root = _os.path.join(root, "val")
            else:
                eval_root = train_root
        ds = data_lib.ImageFolder(
            train_root, transform=data_lib.imagenet_train_transform(image_size),
            seed=seed,
        )
        num_classes = len(ds.classes)
        if do_eval:
            if eval_root == train_root:
                print(
                    "warning: no val/ split found — eval runs on the "
                    "training images (use <root>/train + <root>/val)"
                )
            eval_ds = data_lib.ImageFolder(
                eval_root, transform=data_lib.imagenet_eval_transform(image_size),
                seed=seed,
            )
    elif dataset.startswith("packed-images:"):
        # Pre-decoded packed records; batch assembly (gather + crop + flip)
        # is one multithreaded native call emitting uint8 (4x smaller H2D),
        # with ToTensor+Normalize fused into the jitted step on device —
        # the ImageNet-rate input path.
        path = dataset.split(":", 1)[1]
        ds = data_lib.PackedImages(
            path, train=True, crop_size=image_size, seed=seed, output_dtype="uint8"
        )
        num_classes = len(ds.classes)
        input_normalize = (ds.mean, ds.std)
        if do_eval:
            # Held-out split: a sibling <path>.eval packed file if present,
            # else the training records with a warning.
            import os as _os

            eval_path = path + ".eval" if _os.path.exists(path + ".eval") else path
            if eval_path == path:
                print(
                    "warning: no .eval packed file found — eval runs on the "
                    f"training records (pack a held-out split to {path}.eval)"
                )
            eval_ds = data_lib.PackedImages(
                eval_path, train=False, crop_size=image_size, seed=seed,
                output_dtype="uint8",
            )
    elif dataset.startswith("token-file:"):
        path = dataset.split(":", 1)[1]
        full = data_lib.TokenFile(path, seq_len=seq_len)
        kind, num_classes = "lm", None
        if do_eval:
            import os as _os

            # Prefer a sibling val.bin — the lm_corpus build layout
            # (data/lm_corpus.py writes train.bin + val.bin split by
            # document, so val text never appears in train).  Fall back to
            # holding out the final 5% of windows of the single bin.
            val_path = _os.path.join(_os.path.dirname(path), "val.bin")
            if _os.path.exists(val_path) and _os.path.abspath(val_path) \
                    != _os.path.abspath(path):
                ds = full
                eval_ds = data_lib.TokenFile(val_path, seq_len=seq_len)
            else:
                from ..data.datasets import Subset

                n_eval = max(len(full) // 20, 1)
                ds = Subset(full, 0, len(full) - n_eval)
                eval_ds = Subset(full, len(full) - n_eval, len(full))
        else:
            ds = full
    else:
        raise click.BadParameter(f"unknown dataset {dataset!r}")

    if model_kind != kind:
        raise click.UsageError(
            f"--model {model} is a {model_kind!r} model but --dataset {dataset} "
            f"provides {kind!r} batches; pick a matching pair (e.g. gpt2 with "
            "synthetic-tokens, resnet50 with cifar10/synthetic-images)"
        )

    loader = data_lib.DataLoader(
        ds,
        data_lib.DataLoaderConfig(
            batch_size=batch_size, num_workers=num_workers, seed=seed
        ),
        shard_index=comm.process_index(),
        num_shards=comm.process_count(),
    )

    # --- model + optimizer (L4/L2) ---
    policy = make_policy(precision)
    # MoE dispatch auto-selection: the CLI mesh has no expert axis, so the
    # scatter formulation (no (T,E,C) one-hots — models/moe.py, measured
    # +15% tok/s in rounds 1-5, another machine) is always sound here; an
    # explicit --model-overrides moe_dispatch=einsum wins.
    is_moe = model == "gpt2_moe" or (
        model.startswith("gpt2") and int(overrides.get("num_experts", 0) or 0) > 0
    )
    if is_moe and dict(mesh.shape).get("expert", 1) == 1:
        overrides.setdefault("moe_dispatch", "scatter")
    model_kw = {"cfg_overrides": overrides} if overrides else {}
    net = create_model(
        model, num_classes=num_classes, dtype=policy.compute_dtype, **model_kw
    )
    if kind == "lm":
        # Batch-axes-divisible init sample: params are batch-size-independent
        # and shard_map-based paths (ring attention) need the divisibility.
        from ..comm.mesh import batch_shard_size

        sample = jnp.zeros((batch_shard_size(mesh), seq_len), jnp.int32)
    else:
        side = ds[0]["image"].shape[0]
        sample = jnp.zeros((1, side, side, 3), policy.compute_dtype)
    # LR schedule — absent from the reference (fixed lr, src/main.py:24, 63);
    # required in practice for the ImageNet/GPT-2 BASELINE configs.
    if total_steps is None:
        per_epoch = steps_per_epoch if steps_per_epoch is not None else max(
            len(ds) // batch_size, 1
        )
        total_steps = max(epochs * per_epoch, 1)
    if lr_schedule == "constant":
        lr = learning_rate
    elif lr_schedule == "cosine":
        lr = optax.cosine_decay_schedule(learning_rate, decay_steps=total_steps)
    elif lr_schedule == "warmup-cosine":
        warmup = max(warmup_steps, 1)
        lr = optax.warmup_cosine_decay_schedule(
            0.0, learning_rate, warmup_steps=warmup,
            decay_steps=max(total_steps, warmup + 1),
        )
    else:
        raise click.BadParameter(f"unknown lr schedule {lr_schedule!r}")
    if sequence_parallel > 1:
        # Sequence parallelism over the `sequence` axis: ring attention
        # (parallel/ring_attention — K/V shards rotate over ICI) or Ulysses
        # (parallel/ulysses — all-to-all head resharding).  Length-sharded
        # activations end to end either way.
        if kind != "lm" or not hasattr(net, "cfg"):
            raise click.UsageError(
                "--sequence-parallel requires a transformer LM (--model gpt2)"
            )
        if pipeline_parallel > 1 and (
            pipeline_schedule != "gpipe" or sequence_parallel_mode != "ring"
        ):
            raise click.UsageError(
                "--sequence-parallel composes with --pipeline-parallel "
                "only as ring SP under --pipeline-schedule gpipe (the "
                "branch-free tick loop; collectives inside the manual "
                "schedules' cond-gated stage bodies are unsound — see "
                "parallel/gpt2_pipeline.py)"
            )
        if seq_len % sequence_parallel:
            raise click.BadParameter(
                f"--seq-len {seq_len} not divisible by "
                f"--sequence-parallel {sequence_parallel}"
            )
        if tensor_parallel > 1 and net.cfg.num_heads % tensor_parallel:
            raise click.BadParameter(
                f"--tensor-parallel {tensor_parallel} needs heads "
                f"({net.cfg.num_heads}) divisible by it (the SP attention "
                "shards heads over the tensor axis)"
            )
        local_heads = net.cfg.num_heads // tensor_parallel
        if (
            sequence_parallel_mode == "ulysses"
            and local_heads % sequence_parallel
        ):
            raise click.BadParameter(
                f"--sequence-parallel-mode ulysses needs per-tensor-shard "
                f"heads ({local_heads}) divisible by --sequence-parallel "
                f"{sequence_parallel}; use ring for this head count"
            )
        if pipeline_parallel == 1:
            # The pipelined path below rebuilds the model from net.cfg
            # and reads the mesh's sequence axis itself — cloning here
            # would be dead work it immediately discards.
            net = net.clone(sp_mesh=mesh, sp_mode=sequence_parallel_mode)
    rules = DDP_RULES
    if pipeline_parallel > 1:
        # GPipe over GPT-2's block stack (parallel/gpt2_pipeline.py); the
        # pipelined wrapper exposes init/apply so the rest of the stack is
        # untouched.
        if kind != "lm" or not hasattr(net, "cfg"):
            raise click.UsageError(
                "--pipeline-parallel requires a transformer LM (--model gpt2)"
            )
        if fsdp > 1 and tensor_parallel > 1:
            raise click.UsageError(
                "--fsdp and --tensor-parallel do not combine under "
                "--pipeline-parallel (both split the same matmul dims)"
            )
        from ..comm.striping import (
            resolve_channel_stripe as _resolve_channel_stripe,
        )
        from ..parallel.gpt2_pipeline import (
            PipelinedGPT2, pipelined_rules, pp_fsdp_rules, pp_tp_rules,
        )

        # --remat maps to the pipeline's per-tick checkpoint (GPT2Config's
        # block-level remat lives in GPT2.__call__, which the pipelined
        # wrapper bypasses — without this mapping the flag would be a
        # silent no-op here).
        net = PipelinedGPT2(
            net.cfg, mesh,
            num_microbatches=pipeline_microbatches or 2 * pipeline_parallel,
            dtype=policy.compute_dtype,
            remat_ticks=remat,
            schedule=pipeline_schedule,
            num_chunks=pipeline_chunks,
            pp_compress=pp_compress,
            pp_stripe=_resolve_channel_stripe(grad_sync_stripe),
        )
        # PP x TP: tensor > 1 switches the stage body to the manual
        # Megatron block; stage params shard over (pipeline, tensor).
        # PP x FSDP (any schedule): stage leaves additionally shard their
        # largest dim over `fsdp` — gathered per tick in the stage body
        # under GPipe, hoisted before the tick scan under 1f1b/
        # interleaved.
        if fsdp > 1:
            rules = pp_fsdp_rules()
        elif tensor_parallel > 1:
            rules = pp_tp_rules(
                num_chunks=net.num_chunks if net.num_chunks > 1 else 0
            )
        else:
            rules = pipelined_rules()
    elif fsdp > 1 or tensor_parallel > 1:
        rules = tp_rules_for(model)
    if optimizer == "adam":
        # torch.optim.Adam(lr, weight_decay=wd) semantics (src/main.py:63):
        # coupled L2 — decay is added to the gradient *before* the moment
        # estimates, unlike adamw's decoupled decay.
        tx = optax.chain(
            optax.add_decayed_weights(weight_decay),
            optax.scale_by_adam(),
            optax.scale_by_learning_rate(lr),
        )
    elif optimizer == "adamw":
        tx = optax.adamw(lr, weight_decay=weight_decay)
    elif optimizer == "sgd":
        # torch.optim.SGD(lr, momentum, weight_decay) semantics: coupled L2
        # added to the gradient before the momentum buffer update
        # (buf = m*buf + g; p -= lr*buf).
        tx = optax.chain(
            optax.add_decayed_weights(weight_decay),
            optax.sgd(lr, momentum=momentum),
        )
    else:
        raise click.BadParameter(f"unknown optimizer {optimizer!r}")
    if grad_clip is not None:
        # Global-norm clip BEFORE the optimizer (the standard transformer
        # recipe); fuses into the jitted step like everything else.
        tx = optax.chain(optax.clip_by_global_norm(grad_clip), tx)
    opt_rules = None
    if zero1:
        if fsdp > 1:
            raise click.UsageError(
                "--zero1 shards optimizer slots over the data axis; with "
                "--fsdp the slots are already sharded (ZeRO-3) — pick one"
            )
        if tensor_parallel > 1 or pipeline_parallel > 1:
            # ZERO1_OPT_RULES would *replace* the TP/PP slot sharding: mu/nu
            # would replicate over tensor/pipeline (memory regression, plus
            # per-step resharding between TP-sharded grads and data-sharded
            # slots) — the opposite of what the flag promises.
            raise click.UsageError(
                "--zero1 composes with data parallelism only (not "
                "--tensor-parallel/--pipeline-parallel, whose rules already "
                "shard the optimizer slots over their axes)"
            )
        from ..parallel.sharding import ZERO1_OPT_RULES

        opt_rules = ZERO1_OPT_RULES
    with compile_phase("startup/state"):
        state = create_train_state(
            net, jax.random.PRNGKey(seed), sample, tx,
            mesh=mesh, rules=rules, opt_rules=opt_rules,
            init_kwargs={"train": False},
        )

    grad_sync_obj = None
    if grad_sync != "flat":
        # Two-tier DCN-aware sync runs the fwd+bwd per-device inside its
        # own shard_map over the data axis — model-parallel axes would need
        # their collectives threaded through it, so it is data-parallel
        # only (the DDP regime it accelerates; zero1 composes by design).
        if fsdp > 1 or tensor_parallel > 1 or pipeline_parallel > 1 \
                or sequence_parallel > 1:
            raise click.UsageError(
                f"--grad-sync {grad_sync} composes with data parallelism "
                "only (not --fsdp/--tensor-parallel/--pipeline-parallel/"
                "--sequence-parallel)"
            )
        from ..comm import GradSync, GradSyncConfig

        try:
            grad_sync_obj = GradSync(
                mesh, state.params,
                GradSyncConfig(
                    mode=grad_sync, n_slices=grad_sync_slices, zero1=zero1,
                    bucket_mb=grad_sync_bucket_mb,
                    topk_frac=grad_sync_topk_frac,
                    stripe=grad_sync_stripe,
                    phase_overlap=grad_sync_overlap == "on",
                ),
            )
        except ValueError as e:
            raise click.UsageError(f"--grad-sync {grad_sync}: {e}")
        state = state.replace(
            grad_sync_residual=grad_sync_obj.init_residual()
        )
        print(
            f"grad-sync: {grad_sync} over {grad_sync_obj.n_slices} "
            f"slice(s) x {grad_sync_obj.ici_size} ici, "
            f"{grad_sync_obj.layout.n_buckets} bucket(s) of "
            f"{grad_sync_obj.bucket_mb} MB ({grad_sync_obj.bucket_policy}), "
            f"stripe={grad_sync_obj.stripe} "
            f"overlap={'on' if grad_sync_obj.phase_overlap else 'off'}"
        )

    # Anomaly skip/rollback policy (resilience/): the jit-safe gate rides
    # the train step; the host-side RecoveryManager stages snapshots and
    # rolls back/aborts at the trainer's log cadence.
    anomaly_policy = None
    recovery = None
    if skip_bad_steps:
        from ..resilience import (
            AnomalyPolicy, RecoveryConfig, RecoveryManager,
            init_resilience_state,
        )

        anomaly_policy = AnomalyPolicy(
            grad_norm_threshold=grad_spike_threshold
        )
        state = state.replace(resilience=init_resilience_state())
        recovery = RecoveryManager(
            RecoveryConfig(
                rollback_after=rollback_after, max_rollbacks=max_rollbacks,
                snapshot_every_steps=snapshot_every_steps,
            ),
            emitter=emitter if emitter.enabled else None,
            ledger=ledger,
        )

    if emitter.enabled:
        # Per-step DCN byte counters from the analytic model
        # (comm.hierarchical.dcn_bytes_per_sync), attributed to every step
        # event — the ROADMAP byte-model validation as live telemetry.
        # Accounting must never kill the run: the flat-mode path derives a
        # slice split from the mesh, which legitimately fails on layouts
        # the model doesn't cover (fsdp consuming the data axis, meshes
        # not built slice-major) — record the miss and train on.
        from ..obs import dcn_step_counters, pp_step_counters

        step_counters = {}
        try:
            step_counters.update(dcn_step_counters(
                grad_sync=grad_sync_obj, mesh=mesh, params=state.params,
                num_microbatches=accum_steps,
            ))
        except ValueError as e:
            emitter.emit("record", {
                "record": "dcn_model_unavailable", "error": str(e),
            })
        if pipeline_parallel > 1:
            # Stage-boundary byte model (--pp-compress): the per-step
            # ppermute payload counters plus a record carrying every input
            # the model takes, so the counter stays recomputable from the
            # log alone (tests/test_obs.py pins it).
            pp_m = pipeline_microbatches or 2 * pipeline_parallel
            pp_fields = dict(
                schedule=pipeline_schedule, num_stages=pipeline_parallel,
                num_microbatches=pp_m,
                microbatch_rows=batch_size // pp_m, seq_len=seq_len,
                hidden=net.cfg.hidden_dim,
                act_itemsize=jnp.dtype(policy.compute_dtype).itemsize,
                mode=pp_compress,
                num_chunks=(
                    pipeline_chunks
                    if pipeline_schedule == "interleaved" else 1
                ),
            )
            pp_counters = pp_step_counters(**pp_fields)
            step_counters.update(pp_counters)
            emitter.emit("record", {
                "record": "pp_compress_model", **pp_fields,
                "pp_boundary_bytes_per_step":
                    pp_counters["pp_boundary_bytes"],
            })
        emitter.set_step_counters(step_counters)
        if grad_sync_obj is not None:
            # Enough context to recompute the model from the log alone
            # (the test pins counter == dcn_bytes_per_sync(these fields)).
            from ..obs import grad_sync_wall_model

            wall = grad_sync_wall_model(
                ici_bytes=grad_sync_obj.ici_bytes_per_sync(),
                dcn_bytes=grad_sync_obj.dcn_bytes_per_sync(),
                n_buckets=grad_sync_obj.layout.n_buckets,
                n_slices=grad_sync_obj.n_slices,
                ici_size=grad_sync_obj.ici_size,
                stripe=grad_sync_obj.stripe,
                phase_overlap=grad_sync_obj.phase_overlap,
            )
            emitter.emit("record", {
                "record": "grad_sync_model", "mode": grad_sync,
                "dcn_bytes_per_sync": grad_sync_obj.dcn_bytes_per_sync(),
                "ici_bytes_per_sync": grad_sync_obj.ici_bytes_per_sync(),
                "n_elems_padded": grad_sync_obj.layout.padded,
                "n_slices": grad_sync_obj.n_slices,
                "ici": grad_sync_obj.ici_size,
                "n_buckets": grad_sync_obj.layout.n_buckets,
                "topk_frac": grad_sync_obj.config.topk_frac,
                "bucket_mb": grad_sync_obj.bucket_mb,
                "bucket_policy": grad_sync_obj.bucket_policy,
                "syncs_per_step": grad_sync_obj.syncs_per_step(accum_steps),
                "stripe": grad_sync_obj.stripe,
                "phase_overlap": grad_sync_obj.phase_overlap,
                "overlap_depth": grad_sync_obj.overlap_depth,
                "wall_serial_s": wall["wall_serial_s"],
                "wall_overlap_s": wall["wall_overlap_s"],
                "wall_s": wall["wall_s"],
                "bubble_s": wall["bubble_s"],
                "overlap_ratio": wall["overlap_ratio"],
            })
            if ledger is not None:
                # Per-step analytic grad-sync quota: the wall model's
                # per-sync seconds x syncs/step, ICI share from the
                # per-bucket fabric costs.  The ledger consumes this
                # budget out of each step interval as grad_sync (ICI
                # first, then DCN) — the cross-check telemetry_report
                # prints against the measured shares.
                u = wall["ici_per_bucket_s"]
                v = wall["dcn_per_bucket_s"]
                syncs = grad_sync_obj.syncs_per_step(accum_steps)
                ledger.set_grad_sync_model(
                    wall["wall_s"] * syncs,
                    ici_share=u / (u + v) if (u + v) > 0 else 0.0,
                    model={
                        "mode": grad_sync,
                        "wall_s_per_sync": wall["wall_s"],
                        "syncs_per_step": syncs,
                        "per_step_s": wall["wall_s"] * syncs,
                        "ici_share": u / (u + v) if (u + v) > 0 else 0.0,
                    },
                )

    # Optimizer steps per epoch — needed to translate a restored step counter
    # back into an epoch index on --resume.  len(loader) is the per-process
    # step count, which equals the global optimizer step count (every
    # process advances state.step together).
    per_epoch_steps = steps_per_epoch if steps_per_epoch is not None else max(
        len(loader), 1
    )

    if ckpt_every_steps and not checkpoint_dir:
        raise click.UsageError("--ckpt-every-steps requires --checkpoint-dir")
    start_epoch = 0
    resume_skip_steps = 0
    ckpt_mgr = None
    if checkpoint_dir:
        from ..checkpoint import CheckpointManager

        def _ckpt_anomaly(kind, **fields):
            # Integrity events must be visible even without --metrics-dir:
            # a silent fallback to an older step is a debugging trap.
            print(f"checkpoint: {kind} {fields}")
            if emitter.enabled:
                emitter.anomaly(kind, **fields)

        ckpt_mgr = CheckpointManager(
            checkpoint_dir, on_anomaly=_ckpt_anomaly, fault_injector=faults
        )
        if resume:
            with (
                ledger.bracket("ckpt_restore") if ledger is not None
                else contextlib.nullcontext()
            ), compile_phase("startup/restore"):
                restored = ckpt_mgr.restore_latest(state)
            if ledger is not None:
                # Restart rework: the interrupted attempt completed steps
                # up to the progress-file watermark; every step this
                # attempt re-executes below it is rework (the first
                # dispatched step still classifies as compile — the
                # restart's recompile is its own, larger, cost).
                prev = ledger.read_progress(ledger.progress_path)
                if prev is not None:
                    ledger.set_rework_until(prev)
            if restored is not None:
                state = restored
                # Restore provenance: the elastic peer tier stamps its
                # one-hop RAM restores restore_source="peer"; the disk
                # manifest walk is the fallback tier and says so.
                if emitter.enabled:
                    emitter.emit("record", {
                        "record": "checkpoint_restore",
                        "step": int(state.step),
                        "restore_source": "disk",
                    })
                # Resume where training left off: replaying from epoch 0
                # would re-run the full epoch count on top of the restored
                # step (and reuse epoch-0's shuffle order).  A mid-epoch
                # step checkpoint (--ckpt-every-steps) additionally skips
                # the partial epoch's consumed batches — the loader's
                # epoch-seeded order is deterministic, so the resumed run
                # sees exactly the batches the interrupted one never
                # trained on (pinned by tests/test_resilience.py).
                start_epoch = min(int(state.step) // per_epoch_steps, epochs)
                if start_epoch < epochs:
                    resume_skip_steps = (
                        int(state.step) - start_epoch * per_epoch_steps
                    )
                print(
                    f"resumed from step {int(state.step)} "
                    f"(epoch {start_epoch}, skipping {resume_skip_steps} "
                    "consumed batches)"
                )

    if ce_chunk is not None and kind != "lm":
        raise click.UsageError("--ce-chunk applies to LM models (--model gpt2*)")
    if ce_chunk is not None and pipeline_parallel > 1:
        raise click.UsageError(
            "--ce-chunk is not wired through the pipelined model "
            "(PipelinedGPT2 has no hidden-state output)"
        )
    pipeline_grad_fn = None
    if pipeline_parallel > 1 and getattr(net, "schedule", None) in (
        "1f1b", "interleaved"
    ):
        from ..parallel.gpt2_pipeline import make_pipeline_grad_fn

        if accum_steps > 1:
            # The grad_fn path bypasses accumulate_gradients — accepting
            # the flag would silently run the whole batch through one
            # pipeline pass at accum_steps x the provisioned memory.
            raise click.UsageError(
                "--accum-steps does not compose with --pipeline-schedule "
                f"{pipeline_schedule} (the schedule owns microbatching; "
                "size --pipeline-microbatches instead)"
            )
        pipeline_grad_fn = make_pipeline_grad_fn(
            net, label_smoothing=label_smoothing
        )
    state_shardings = None
    if opt_rules is not None:
        # zero1: pin the step's output state to the declared layout.
        # Propagation otherwise returns some data-sharded slots at a
        # different sharding than they entered with — donation
        # un-aliases for those leaves and the state re-lays-out every
        # step (graftcheck's memory audit is the gate).
        from ..train import infer_state_shardings

        state_shardings = infer_state_shardings(
            state, mesh, rules=rules, opt_rules=opt_rules,
            residual_sharding=(
                grad_sync_obj.residual_sharding()
                if grad_sync_obj is not None and grad_sync_obj.has_residual
                else None
            ),
        )
    step_fn = make_train_step(
        kind=kind, policy=policy, num_microbatches=accum_steps,
        base_rng=jax.random.PRNGKey(seed + 1),
        input_normalize=input_normalize,
        label_smoothing=label_smoothing,
        lm_loss_chunk=ce_chunk,
        grad_fn=pipeline_grad_fn,
        grad_sync=grad_sync_obj,
        anomaly_policy=anomaly_policy,
        state_shardings=state_shardings,
    )

    cache = None
    if device_cache and kind == "lm":
        # HBM-resident token corpus with on-device window sampling
        # (data/token_cache.py): ~2 bytes/token uploaded once, zero
        # steady-state H2D.
        if comm.process_count() > 1:
            raise click.UsageError(
                "--device-cache is single-host (each host would need its "
                "own shard); use the streaming loader for multi-host runs"
            )
        from ..data import DeviceCachedTokens
        from ..data.datasets import Subset

        src, lo, hi = ds, None, None
        if isinstance(src, Subset):
            lo, hi = src.start, src.stop
            src = src.dataset
        stream = getattr(src, "tokens", None)
        if stream is None:
            raise click.UsageError(
                f"--device-cache for LM needs a token-stream dataset "
                f"(token-file:<path>); {dataset!r} has none"
            )
        if lo is not None:
            # Window-range subset -> token-range slice (+1 so the last
            # window keeps its next-token target).
            stream = stream[lo * seq_len:hi * seq_len + 1]
        cache = DeviceCachedTokens(
            stream, mesh=mesh, seed=seed, default_seq_len=seq_len
        )
    elif device_cache:
        # HBM-resident dataset with on-device shuffle/crop/flip
        # (data/device_cache.py): upload once, zero per-step H2D.
        if comm.process_count() > 1:
            raise click.UsageError(
                "--device-cache is single-host (each host would need its "
                "own shard); use the streaming loader for multi-host runs"
            )
        images = getattr(ds, "images", None)
        if images is None:
            raise click.UsageError(
                f"--device-cache needs a dataset with uint8 records "
                f"(cifar10, shapes, packed-images); {dataset!r} has none"
            )
        from ..data import DeviceCachedImages

        side = int(images.shape[1])
        if image_size > side:
            # The cache crops from the stored records and cannot upscale;
            # silently training at the record resolution would diverge from
            # the host-loader path (which resizes to image_size).
            click.echo(
                f"warning: --device-cache trains at the stored record "
                f"resolution {side}px, not --image-size {image_size} "
                f"(records cannot be upscaled on-device; use the host "
                f"loader for resize-up training)",
                err=True,
            )
        try:
            cache = DeviceCachedImages(
                ds, mesh=mesh, crop_size=min(image_size, side), train=True,
                seed=seed,
            )
        except ValueError as e:  # non-uint8 records, crop too large, ...
            raise click.UsageError(f"--device-cache: {e}")
    # Preemption latch + step-checkpoint hook: any checkpointed run takes
    # a synchronous step checkpoint on SIGTERM and exits the distinct
    # preemption code the supervisor relaunches for free.
    preemption = None
    checkpoint_fn = None
    if ckpt_mgr is not None:
        def checkpoint_fn(s, wait=False):
            ckpt_mgr.save(s, wait=wait)

        from ..resilience import PreemptionHandler

        try:
            preemption = PreemptionHandler().install()
        except ValueError:
            preemption = None  # not the main thread (embedded callers)
    trainer = Trainer(
        state, step_fn, mesh,
        TrainerConfig(
            epochs=epochs, sequence_sharded=sequence_parallel > 1,
            prefetch=0 if cache is not None else TrainerConfig.prefetch,
            # Step-window profiling is the trainer's job; whole-first-epoch
            # capture (no --profile-steps) stays bracketed in _run_epochs.
            profile_dir=profile_dir if profile_window is not None else None,
            profile_steps=profile_window,
            checkpoint_every_steps=ckpt_every_steps,
        ),
        emitter=emitter,
        spans=spans,
        # What ONE compiled step contains — the span attrs a timeline
        # reader needs to interpret a train/step bar (the measured
        # sub-phase timelines are xprof's, via --profile-steps).
        anatomy={
            "microbatches": accum_steps,
            "grad_sync": grad_sync,
            **({"sync_tiers": [
                "grad_sync/rs_ici", "grad_sync/ar_dcn", "grad_sync/ag_ici",
            ] + (["grad_sync/stripe"]
                 if grad_sync_obj is not None and grad_sync_obj.stripe > 1
                 else [])} if grad_sync.startswith("hier") else {}),
            **({"pipeline_stages": pipeline_parallel,
                "pipeline_schedule": pipeline_schedule}
               if pipeline_parallel > 1 else {}),
        },
        faults=faults,
        recovery=recovery,
        preemption=preemption,
        checkpoint_fn=checkpoint_fn,
        slo=slo_policy,
        ledger=ledger,
    )
    logger = metrics_lib.MetricsLogger(metrics_jsonl)

    eval_loader = None
    eval_step = None
    if eval_ds is not None:
        from ..comm.mesh import batch_shard_size
        from ..train import make_eval_step

        # drop_last=True keeps every batch mesh-divisible, so a split smaller
        # than the batch would silently yield zero eval batches — shrink the
        # eval batch to the largest device-divisible size that fits instead.
        divisor = batch_shard_size(mesh) * comm.process_count()
        eval_bs = batch_size
        if len(eval_ds) < eval_bs:
            eval_bs = (len(eval_ds) // divisor) * divisor
        if eval_bs <= 0:
            print(
                f"warning: eval split ({len(eval_ds)} examples) smaller than "
                f"one device-divisible batch ({divisor}); skipping eval"
            )
        else:
            eval_loader = data_lib.DataLoader(
                eval_ds,
                data_lib.DataLoaderConfig(
                    batch_size=eval_bs, num_workers=0, shuffle=False
                ),
                shard_index=comm.process_index(),
                num_shards=comm.process_count(),
            )
            # LM eval always chunks the CE: the eval batch is not split by
            # --accum-steps the way train microbatches are, so full-batch
            # (B, L, vocab) eval logits can OOM a config whose TRAIN step
            # fits (measured: batch 128 GPT-2 eval wants a 26 GB logits
            # tensor).  Chunked CE is bit-identical math and strictly less
            # memory; eval throughput is not a headline.
            # (Not for the pipelined model, which has no hidden-state
            # output for the chunked path — its eval batch equals the
            # train batch the pipeline already fits.)
            lm_eval_chunk = ce_chunk
            if kind == "lm" and pipeline_parallel == 1:
                lm_eval_chunk = ce_chunk or 256
            eval_step = make_eval_step(
                kind=kind, policy=policy, input_normalize=input_normalize,
                lm_loss_chunk=lm_eval_chunk,
            )

    print("training started")
    t0 = time.perf_counter()
    from ..resilience import PREEMPTED_EXIT_CODE, Preempted

    preempted = None
    try:
        _run_epochs(
            trainer, logger, cache, loader, batch_size, start_epoch, epochs,
            steps_per_epoch,
            profile_dir if profile_window is None else None,
            eval_loader, eval_steps,
            eval_step, mesh, sequence_parallel, ckpt_mgr, emitter,
            skip_steps=resume_skip_steps, ledger=ledger,
        )
    except Preempted as e:
        # SIGTERM path: the trainer already committed a synchronous step
        # checkpoint at the boundary; fall through to the shared cleanup
        # and exit the distinct code the supervisor relaunches for free.
        preempted = e
    finally:
        if preemption is not None:
            preemption.uninstall()
        # Context-managed commit (CheckpointManager.close): EVERY exit
        # path — normal, exception, preemption — waits for the last
        # async save to commit before the process can die, so a
        # mid-epoch crash never strands an in-flight save uncommitted.
        if ckpt_mgr is not None:
            ckpt_mgr.close()
        if ops_server is not None:
            ops_server.stop()
        if slo_policy is not None and slo_policy.alert_log:
            red = slo_policy.snapshot()["alerts"]
            print(
                f"slo: {red['transitions']} alert transition(s), "
                f"{red['anomaly_alerts']['count']} promoted anomaly "
                f"alert(s); active: {slo_policy.active_alerts or 'none'}"
            )
        if spans is not None:
            spans.close()
        if ledger is not None:
            # Freeze the wall clock and emit the final gauges AND the
            # goodput_ledger record from ONE snapshot — the live
            # goodput_fraction gauge and the post-hoc report agree
            # exactly because they are the same dict.  Runs on every
            # exit path (normal, Preempted, crash-through), before the
            # emitter summary so the summary's gauges are final.
            snap = ledger.finalize(emitter)
            print(
                f"goodput: {snap['goodput_fraction']:.4f} over "
                f"{snap['wall_s']:.2f}s wall "
                f"(identity {'ok' if snap['identity_ok'] else 'BROKEN'})"
            )
        if emitter.enabled:
            # What each device holds at the end (None where the backend
            # keeps no memory statistics — the CPU).
            stats = {d.id: d.memory_stats() or {} for d in jax.local_devices()}
            # Why this start took as long as it did: what JAX traced,
            # lowered, compiled and found in its cache, in which phase.
            emitter.emit("record", {
                "record": "compile_events", **_compile_summary(),
            })
            emitter.emit("record", {
                "record": "device_memory",
                "devices": [
                    {"id": i, "bytes_in_use": s.get("bytes_in_use"),
                     "peak_bytes_in_use": s.get("peak_bytes_in_use")}
                    for i, s in stats.items()
                ],
            })
        emitter.summary()
        emitter.close()
    elapsed = time.perf_counter() - t0
    if preempted is not None:
        import sys

        print(
            f"preempted at step {preempted.step}; checkpoint "
            f"{'committed' if preempted.saved else 'unavailable'}; "
            f"exiting {PREEMPTED_EXIT_CODE}"
        )
        print(f"elapsed time: {elapsed:.2f}s")
        sys.exit(PREEMPTED_EXIT_CODE)
    print("training finished")
    # The reference's one self-measurement: epoch wall-clock (src/main.py:84).
    print(f"elapsed time: {elapsed:.2f}s")
    return trainer


def _run_serve(
    *, model, overrides, precision, checkpoint_dir, seed, seq_len,
    metrics_jsonl, n_requests, rate, num_slots, max_new, prefill_chunk,
    emitter=None, paged=False, block_size=16, num_blocks=0,
    kv_dtype="bf16", ttl=None,
    spec_k=0, spec_ngram=4, tp=1, replicas=1, affinity=True,
    disagg=None, kv_host_mb=0.0, inject_faults=None, failover=True,
    retry_budget=2, brownout_s=0.0, autoscale=False, autoscale_min=1,
    autoscale_max=0, autoscale_up_depth=8, autoscale_down_idle=32,
    autoscale_cooldown=16, priority=None, healthz_stale_s=60.0, spans=None,
    slo_policy=None, ops_server=None,
):
    """Continuous-batching serving (serve/) over a synthetic mixed-length
    request trace: restore the trained checkpoint, AOT-compile the
    prefill/decode steps, run the iteration-level scheduler at the offered
    load, and print the TTFT/TPOT/goodput summary.

    The served model is the SAME artifact training produces — params come
    straight from ``CheckpointManager.restore_params`` on the training
    run's ``--checkpoint-dir``.

    Scale-out (--serve-tp / --serve-replicas): each of ``replicas``
    engines compiles its three programs against its OWN tensor=tp submesh
    (replica k on devices [k*tp, (k+1)*tp) — independent MPMD programs,
    not one global SPMD program) and a prefix-affinity router
    (serve/router.py) is the single admission point above them.  With
    fewer devices than replicas*tp the replicas share the default device
    unsharded — the CPU-proxy shape.
    """
    import os as _os_mod

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import create_model
    from ..serve import (
        ContinuousScheduler, DisaggServingEngine, ReplicaRouter, Request,
        ServingEngine, summarize_records,
    )
    from ..train import make_policy
    from ..utils import metrics as metrics_lib

    policy = make_policy(precision)
    net = create_model(
        model, dtype=policy.compute_dtype,
        **({"cfg_overrides": overrides} if overrides else {}),
    )
    if max_new > net.cfg.max_seq_len - 2:
        raise click.UsageError(
            f"--serve-max-new {max_new} leaves no room for a prompt in the "
            f"model's {net.cfg.max_seq_len}-position cache"
        )
    if checkpoint_dir:
        from ..checkpoint import CheckpointManager

        # A --checkpoint-dir that cannot be served FAILS the run: random
        # weights in place of the ones asked for is not a fallback
        # (restore_params raises when committed steps exist but none
        # restores).
        params = CheckpointManager(checkpoint_dir).restore_params()
        if params is None:
            raise click.UsageError(
                f"--checkpoint-dir {checkpoint_dir} holds no committed "
                "checkpoint (drop the flag to serve fresh-init weights)"
            )
        print(f"serving params restored from {checkpoint_dir}")
    else:
        print("warning: serving FRESH-INIT weights (pass --checkpoint-dir "
              "with a trained run for real outputs)")
        params = net.init(
            jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32),
            train=False,
        )["params"]
    if emitter is not None:
        emitter.phase("serve_params_ready")
    # Serving reads every weight once per tick; compute-dtype params halve
    # the per-tick weight traffic vs the train-state fp32 tree.
    params = jax.tree_util.tree_map(
        lambda x: jnp.asarray(x, policy.compute_dtype), params
    )

    max_len = net.cfg.max_seq_len
    if tp < 1 or replicas < 1:
        raise click.UsageError("--serve-tp and --serve-replicas must be >= 1")
    devs = jax.devices()
    if tp > 1 and len(devs) < tp * replicas:
        raise click.UsageError(
            f"--serve-tp {tp} x --serve-replicas {replicas} needs "
            f"{tp * replicas} devices, have {len(devs)}"
        )
    from ..parallel.sharding import serve_tp_mesh

    def replica_mesh(k):
        # tp>1: replica k's TP submesh.  tp==1 with enough devices: a
        # single-device mesh per replica (placement only — the MPMD
        # layout).  Otherwise share the default device unsharded.
        if tp > 1:
            return serve_tp_mesh(tp, devices=devs[k * tp:(k + 1) * tp])
        if replicas > 1 and len(devs) >= replicas:
            return serve_tp_mesh(1, devices=devs[k:k + 1])
        return None

    if kv_host_mb and not paged:
        raise click.UsageError(
            "--serve-kv-host-mb spills paged blocks — add --serve-paged"
        )
    if kv_dtype != "bf16" and not paged:
        raise click.UsageError(
            "--serve-kv-dtype quantizes paged blocks — add --serve-paged"
        )
    role_slots = None
    if disagg is not None:
        try:
            p_slots, d_slots = (int(x) for x in str(disagg).split(":"))
            if p_slots < 1 or d_slots < 1:
                raise ValueError
        except ValueError:
            raise click.UsageError(
                f"--serve-disagg wants P:D with both >= 1 "
                f"(e.g. 1:3), got {disagg!r}"
            )
        role_slots = (p_slots, d_slots)
    engine_kw = dict(
        max_len=max_len,
        prefill_chunk=prefill_chunk, temperature=0.0, seed=seed,
        paged=paged, block_size=block_size,
        num_blocks=num_blocks or None, kv_dtype=kv_dtype,
        spec_k=spec_k, spec_ngram=spec_ngram,
    )
    if role_slots is not None:
        engines = [
            DisaggServingEngine(
                net, params, prefill_slots=role_slots[0],
                decode_slots=role_slots[1],
                kv_host_mb=kv_host_mb or None,
                tp_mesh=replica_mesh(k), **engine_kw,
            )
            for k in range(replicas)
        ]
    else:
        engines = [
            ServingEngine(
                net, params, num_slots=num_slots,
                kv_host_mb=kv_host_mb or None,
                tp_mesh=replica_mesh(k), **engine_kw,
            )
            for k in range(replicas)
        ]
    engine = engines[0]
    if replicas > 1:
        # Where each replica's weights actually sit (not where
        # replica_mesh meant to put them).
        print("serving replicas on devices: " + " ".join(
            f"{k}:{sorted(d.id for d in leaf.devices())}"
            for k, leaf in enumerate(
                jax.tree_util.tree_leaves(e.params)[0] for e in engines
            )
        ))
    # What the kernel dispatch actually lowered (every replica compiles
    # the same programs): Mosaic custom calls per compiled program, and
    # which kernels they are (ops.pallas_attention.KERNEL_NAMES).
    programs = engine.mosaic_kernels
    print("serving programs: mosaic_custom_calls " + " ".join(
        f"{name}={sum(kernels.values())}" + "".join(
            f" ({kernel} x{n})" for kernel, n in kernels.items()
        ) for name, kernels in programs.items()
    ))
    if emitter is not None:
        for name, kernels in programs.items():
            _gauge_mosaic_kernels(emitter, name, kernels)
        emitter.phase("serve_engines_built")
    rng = np.random.default_rng(seed)
    p_hi = max(min(seq_len, max_len - max_new) // 2, 2)
    prompts = [
        rng.integers(0, net.cfg.vocab_size,
                     (int(rng.integers(2, p_hi + 1)),)).astype(np.int32)
        for _ in range(n_requests)
    ]
    budgets = rng.integers(max(max_new // 4, 1), max_new + 1, n_requests)
    if rate and rate > 0:
        arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
    else:
        arrivals = np.zeros(n_requests)
    t0 = time.monotonic()
    requests = [
        Request(
            i, prompts[i], int(budgets[i]), float(t0 + arrivals[i]),
            deadline=(
                float(t0 + arrivals[i] + ttl) if ttl is not None else None
            ),
        )
        for i in range(n_requests)
    ]
    logger = metrics_lib.MetricsLogger(None)
    req_log = (
        metrics_lib.RequestLogger(metrics_jsonl) if metrics_jsonl else None
    )
    # The whole trace is this tool's own workload — queue it all; bounded-
    # queue backpressure (refusals) is exercised by tests and the dryrun
    # leg, not by shedding our own synthetic requests.
    live_emitter = (
        emitter if emitter is not None and emitter.enabled else None
    )
    # Chaos + failover plane (resilience/faults.py + serve/failover.py):
    # a serving fault spec forces the replica router (even at one
    # replica — the failover controller is the thing under test), and
    # failover is on by default wherever the router runs.  The
    # --no-serve-failover control under replica faults strands the dead
    # replica's work by design.
    from ..resilience.faults import SERVE_FAULTS_ENV

    fault_spec = inject_faults or _os_mod.environ.get(SERVE_FAULTS_ENV)
    chaos = None
    if fault_spec:
        from ..resilience import ServeFaultInjector

        chaos = ServeFaultInjector.from_spec(
            fault_spec,
            state_dir=(
                _os_mod.path.join(checkpoint_dir, ".fault_state")
                if checkpoint_dir else None
            ),
            emitter=live_emitter,
        )
        if not failover:
            print(
                "warning: serving faults armed WITHOUT failover — a "
                "dead replica strands its queue (control mode)"
            )
    # SLO-weighted admission (serve/policy.py): priority classes over
    # the tenant-fair queue, boosted live while a per-class --slo
    # objective's window is out of budget.
    serve_policy = None
    if priority:
        from ..serve import ServePolicy, parse_priority_spec

        try:
            weights = parse_priority_spec(priority)
        except ValueError as e:
            raise click.UsageError(f"--serve-priority: {e}")
        serve_policy = ServePolicy(
            weights,
            aggregator=(
                slo_policy.aggregator if slo_policy is not None else None
            ),
        )
        if slo_policy is not None:
            serve_policy.bind_objectives(slo_policy.objectives)
    if autoscale and not failover:
        raise click.UsageError(
            "--serve-autoscale retires/revives replicas through the "
            "failover fence/drain path — drop --no-serve-failover"
        )
    router = None
    if replicas > 1 or chaos is not None or autoscale:
        failover_ctrl = None
        if failover:
            from ..serve import FailoverController

            failover_ctrl = FailoverController(
                retry_budget=retry_budget, brownout_margin_s=brownout_s,
                aggregator=(
                    slo_policy.aggregator if slo_policy is not None
                    else None
                ),
                # One staleness bound for /healthz and the death
                # detector: the operator tunes --healthz-stale-s once.
                stale_after_s=healthz_stale_s,
            )
        autoscale_ctrl = None
        if autoscale:
            from ..serve import AutoscaleController

            try:
                autoscale_ctrl = AutoscaleController(
                    min_replicas=autoscale_min,
                    max_replicas=autoscale_max or None,
                    up_queue_depth=autoscale_up_depth,
                    down_idle_ticks=autoscale_down_idle,
                    cooldown_ticks=autoscale_cooldown,
                    slo=slo_policy,
                    aggregator=(
                        slo_policy.aggregator if slo_policy is not None
                        else None
                    ),
                )
            except ValueError as e:
                raise click.UsageError(f"--serve-autoscale: {e}")
        try:
            router = ReplicaRouter(
                engines, max_queue=n_requests, request_logger=req_log,
                emitter=live_emitter, affinity=affinity, spans=spans,
                slo=slo_policy, chaos=chaos, failover=failover_ctrl,
                autoscale=autoscale_ctrl, policy=serve_policy,
            )
        except ValueError as e:
            if autoscale_ctrl is None:
                raise
            raise click.UsageError(f"--serve-autoscale: {e}")
        if autoscale_ctrl is not None and ops_server is not None:
            # /slo grows the controller block (read-only snapshot; the
            # handler thread never mutates).
            ops_server.controller = autoscale_ctrl
        driver = router
    else:
        driver = ContinuousScheduler(
            engine, max_queue=n_requests, request_logger=req_log,
            emitter=live_emitter, spans=spans, slo=slo_policy,
            policy=serve_policy,
        )
    n_blocks = (
        engine.blocks.num_blocks if role_slots is not None
        else engine.pool.num_blocks
    ) if paged else 0
    layout = (
        f"paged ({n_blocks} blocks x {block_size})" if paged
        else "contiguous"
    )
    if kv_dtype != "bf16":
        layout += f", kv={kv_dtype}"
    if kv_host_mb:
        layout += f" + {kv_host_mb:g} MB host KV tier"
    slots_note = (
        f"{role_slots[0]}+{role_slots[1]} prefill+decode slots"
        if role_slots is not None else f"{num_slots} slots"
    )
    spec_note = (
        f", spec k={spec_k} ngram={spec_ngram}" if spec_k else ""
    )
    scale_note = ""
    if tp > 1 or replicas > 1:
        scale_note = (
            f", tp={tp} x {replicas} replica(s)"
            f"{', affinity' if replicas > 1 and affinity else ''}"
        )
    if router is not None and router.autoscale is not None:
        a = router.autoscale
        scale_note += (
            f", autoscale [{a.min_replicas}, {a.max_replicas}]"
        )
    if serve_policy is not None:
        scale_note += f", priority({priority})"
    print(
        f"serving started: {n_requests} requests, {slots_note} "
        f"({layout}), rate={rate or 'burst'} req/s, "
        f"prefill_chunk={prefill_chunk}{spec_note}{scale_note}"
    )
    records = driver.run(requests)
    elapsed = time.monotonic() - t0
    if router is not None:
        summary = summarize_records(
            records, elapsed=elapsed,
            queue_depth_samples=router.queue_depth_samples(),
            rejected=router.rejected,
            active_slot_samples=router.active_slot_samples(),
            engine_stats=(
                router.engine_stats() if (paged or spec_k) else None
            ),
            failover_stats=(
                router.failover.stats()
                if router.failover is not None else None
            ),
        )
        rt = router.stats()
        hit_rate = (
            rt["affinity_hits"] / sum(rt["routed"])
            if sum(rt["routed"]) else 0.0
        )
        print(
            f"router: routed={rt['routed']} "
            f"affinity_hit_rate={hit_rate:.3f} "
            f"rebalanced={rt['rebalanced']} rejected={rt['rejected']}"
        )
        if router.failover is not None:
            fo = router.failover.stats()
            print(
                f"failover: deaths={fo['replica_deaths']} "
                f"requeued={fo['requeued']} retried={fo['retried']} "
                f"dup_suppressed={fo['duplicates_suppressed']} "
                f"failed={fo['failed']} respawns={fo['respawns']}"
            )
        if router.autoscale is not None:
            a = router.autoscale.stats()
            print(
                f"autoscale: actions={a['actions']} "
                f"up={a['scale_ups']} down={a['scale_downs']} "
                f"resplits={a['resplits']} "
                f"ladder_moves={a['ladder_moves']} "
                f"active={a['replicas_active']}/"
                f"{a['replicas_active'] + a['replicas_parked']} "
                f"rung={a['rung']} split_bias={a['split_bias']}"
            )
    else:
        summary = summarize_records(
            records, elapsed=elapsed,
            queue_depth_samples=driver.queue_depth_samples,
            rejected=driver.rejected,
            active_slot_samples=driver.active_slot_samples,
            engine_stats=engine.stats() if (paged or spec_k) else None,
        )
    if spec_k and summary.get("spec"):
        sp = summary["spec"]
        print(
            f"speculation: acceptance_rate={sp['acceptance_rate']} "
            f"({sp['accepted_tokens']}/{sp['drafted_tokens']} drafted), "
            f"tokens_per_tick={sp['tokens_per_decode_tick']}"
        )
    if paged:
        st = router.engine_stats() if router is not None else engine.stats()
        hit_rate = (
            st["prefix_hit_tokens"] / st["prefix_lookup_tokens"]
            if st["prefix_lookup_tokens"] else 0.0
        )
        print(
            f"paged pool: prefix_hit_rate={hit_rate:.3f} "
            f"blocks_evicted={st['blocks_evicted']} "
            f"prefill_tokens={st['prefill_tokens_computed']}/"
            f"{st['prefill_tokens_offered']}"
        )
        if kv_host_mb:
            print(
                f"host KV tier: spilled={st.get('blocks_spilled', 0)} "
                f"restored={st.get('blocks_restored', 0)} "
                f"dropped={st.get('host_dropped_blocks', 0)} "
                f"resident={st.get('host_blocks', 0)} blocks"
            )
    if role_slots is not None:
        st = router.engine_stats() if router is not None else engine.stats()
        print(
            f"disagg: {st.get('handoffs', 0)} prefill->decode handoff(s), "
            f"roles {role_slots[0]}p+{role_slots[1]}d"
        )
    logger.log({"mode": "serve", **{
        k: v for k, v in summary.items() if not isinstance(v, dict)
    }})
    if serve_policy is not None:
        ps = serve_policy.snapshot()
        print(
            f"priority: admitted_by_class={ps['admitted_by_class']} "
            f"boosted={ps['boosted_admissions']}"
        )
    if slo_policy is not None:
        red = slo_policy.snapshot()["alerts"]
        print(
            f"slo: {red['transitions']} alert transition(s), "
            f"{red['anomaly_alerts']['count']} promoted anomaly "
            f"alert(s); active: {slo_policy.active_alerts or 'none'}"
        )
    if spans is not None:
        spans.close()
        print(
            f"trace: {spans.recorded} spans recorded "
            f"({spans.sampled_out} sampled out at rate "
            f"{spans.sample_rate}); export with "
            f"tools/trace_export.py"
        )
    if emitter is not None:
        emitter.summary(serve=summary)
        emitter.close()
    print("serving finished")
    print(f"elapsed time: {elapsed:.2f}s")
    return summary


def _compile_summary() -> dict:
    """The process's compile events (utils/compile_cache.py) as totals,
    totals per phase, and the five longest single events."""
    events = compile_events()
    phases: dict = {}
    for e in events:
        key = e["phase"] or "none"
        if "epoch" in e:
            key += f":{e['epoch']}"
        phases.setdefault(key, []).append(e)
    longest = sorted(events, key=lambda e: -e["seconds"])[:5]
    return {
        **compile_totals(events),
        "by_phase": {k: compile_totals(v) for k, v in phases.items()},
        "longest": [
            {k: e[k] for k in ("what", "fun_name", "seconds", "phase")}
            for e in longest
        ],
    }


def _gauge_mosaic_kernels(emitter, program: str, kernels: dict) -> None:
    """``mosaic_custom_calls[program=..]`` (all kernels of one compiled
    program) and one ``[program=..,kernel=..]`` gauge per kernel name."""
    emitter.gauge(
        f"mosaic_custom_calls[program={program}]", sum(kernels.values())
    )
    for kernel, n in kernels.items():
        emitter.gauge(
            f"mosaic_custom_calls[program={program},kernel={kernel}]", n
        )


def _probe_compiled_cost(trainer, batches, mesh, sequence_parallel, emitter):
    """AOT-lower the train step on the first batch and emit one
    ``compiled_cost`` event (FLOPs / bytes accessed / collective census
    from the compiled program — the MFU numerator telemetry_report divides
    by the measured step time).  Costs one extra compile of the step, paid
    only under --metrics-dir; the peeked batch is chained back."""
    import itertools

    from ..obs import step_cost_report
    from ..obs.cost import scope_census
    from ..ops.causal_conv import conv_plans_traced
    from ..ops.grouped_matmul import grouped_plans_traced
    from ..ops.pallas_attention import flash_visited_pair_share
    from ..ops.ssd import ssd_plans_traced
    from ..parallel.sharding import shard_batch

    # Bind the iterator ONCE and chain onto it — peeking via a fresh
    # iter() each time would restart a re-iterable source and double-run
    # the first batch (the call sites all pass one-shot iterators today,
    # but this must stay correct if one ever passes the loader itself).
    batches = iter(batches)
    first = next(batches, None)
    if first is None:
        return batches
    with mesh:
        sharded = shard_batch(
            first, mesh, sequence_sharded=sequence_parallel > 1
        )
        # Where the batch actually landed: which devices hold a shard, and
        # of what shape (the multi-chip check reads this, not the mesh).
        leaf = next(iter(sharded.values()))
        emitter.emit("record", {
            "record": "batch_placement",
            "devices": sorted(s.device.id for s in leaf.addressable_shards),
            "shard_shape": list(leaf.addressable_shards[0].data.shape),
            "global_shape": list(leaf.shape),
        })
        try:
            compiled = trainer.train_step.lower(
                trainer.state, sharded
            ).compile()
            report = step_cost_report(compiled)
            emitter.emit("compiled_cost", report)
            # Instructions by scope, of the compile the step then loads: what
            # a --profile-dir trace's events can be laid against.
            emitter.emit("record", {
                "record": "step_scopes", "scopes": scope_census(compiled.as_text()),
            })
            _gauge_mosaic_kernels(
                emitter, "train_step", report.get("mosaic_kernels", {})
            )
            for kernel, share in flash_visited_pair_share().items():
                emitter.gauge(
                    f"flash_visited_pair_share[kernel={kernel}]", share
                )
            for gauge, traced in (("ssd_plan", ssd_plans_traced), ("conv_plan", conv_plans_traced),
                                  ("grouped_plan", grouped_plans_traced)):
                for kind, sites in traced().items():
                    emitter.gauge(f"{gauge}[kind={kind}]", sites)
            # Feed the live MFU gauge: the probe's compiled FLOPs + peak
            # over the trainer's rolling step-time window (obs/live.py).
            trainer.step_flops = report.get("flops")
            trainer.peak_flops = report.get("peak_flops")
        except Exception as e:  # never fail the run for accounting
            emitter.emit("compiled_cost", {"error": str(e)})
    return itertools.chain([sharded], batches)


def _run_epochs(
    trainer, logger, cache, loader, batch_size, start_epoch, epochs,
    steps_per_epoch, profile_dir, eval_loader, eval_steps, eval_step, mesh,
    sequence_parallel, ckpt_mgr, emitter=None, skip_steps=0, ledger=None,
):
    probed = False
    for epoch in range(start_epoch, epochs):
        if cache is not None:
            batches = cache.batches(epoch, batch_size)
        else:
            loader.set_epoch(epoch)
            batches = iter(loader)
        # Deterministic mid-epoch resume: drop the batches the interrupted
        # run already consumed (the epoch-seeded order replays them
        # identically), capped at the same absolute per-epoch bound, so
        # the resumed step sequence bitwise-matches the uninterrupted one.
        skip = skip_steps if epoch == start_epoch else 0
        if skip or steps_per_epoch is not None:
            import itertools

            batches = itertools.islice(batches, skip, steps_per_epoch)
        if emitter is not None and emitter.enabled and not probed:
            # The AOT probe is an eager lower+compile of the step: a
            # compile-category interval on the ledger (the first dispatch
            # then hits the compile cache, so the probe IS the compile).
            with (
                ledger.bracket("compile") if ledger is not None
                else contextlib.nullcontext()
            ), compile_phase("startup/step"):
                batches = _probe_compiled_cost(
                    trainer, batches, mesh, sequence_parallel, emitter
                )
            probed = True
        if profile_dir and epoch == 0:
            from ..utils.profiling import trace

            with trace(profile_dir):
                summary = trainer.run_epoch(batches, epoch=epoch)
        else:
            summary = trainer.run_epoch(batches, epoch=epoch)
        logger.log(summary)
        if eval_loader is not None:
            from ..parallel.sharding import shard_batch

            totals, n_batches = {}, 0
            eval_batches = iter(eval_loader)
            if eval_steps is not None:
                import itertools

                eval_batches = itertools.islice(eval_batches, eval_steps)
            from ..utils.supervisor import Heartbeat

            eval_hb = Heartbeat.from_env()
            with mesh:
                for eb in eval_batches:
                    if eval_hb is not None:
                        eval_hb.beat()
                    em = eval_step(trainer.state, shard_batch(
                        eb, mesh, sequence_sharded=sequence_parallel > 1
                    ))
                    for k, v in em.items():
                        totals[k] = totals.get(k, 0.0) + float(v)
                    n_batches += 1
            if n_batches:
                logger.log({
                    "epoch": epoch,
                    **{f"eval_{k}": v / n_batches for k, v in totals.items()},
                })
        if ckpt_mgr is not None:
            # Async: staging is synchronous, disk serialization overlaps
            # the next epoch; the caller's finally commits the final save.
            with (
                ledger.bracket("ckpt_save") if ledger is not None
                else contextlib.nullcontext()
            ):
                ckpt_mgr.save(trainer.state)


if __name__ == "__main__":
    main()
