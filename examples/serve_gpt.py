"""Serving demo: offline batch mode through the continuous-batching
engine.

No reference analogue — apex is training-only — but the ROADMAP north
star serves heavy traffic, and this is the smallest end-to-end slice of
that: a file of requests (one JSON object per line) flows through
``apex_tpu.serving``'s slot engine, each request decoded with its own
sampling params and stop token, outputs token-identical to a solo
``gpt.generate`` call per request (the engine's oracle test pins this).

Request-file line format (all but ``id``/``prompt`` optional; ``stop``
is a list of stop TOKEN sequences, matched host-side on the streamed
tail with the matched tokens trimmed)::

  {"id": "r0", "prompt": [17, 4, 99], "max_tokens": 16,
   "temperature": 0.8, "top_k": 40, "top_p": 0.95, "seed": 7,
   "eos_token_id": 50256, "stop": [[11, 12]]}

HTTP front end (``apex_tpu.serving.api``): ``--api-port N`` serves the
OpenAI surface (``/v1/chat/completions``, ``/v1/completions`` with SSE
streaming, ``/v1/models``, ``/healthz``) after the batch drains, for
``--api-linger`` seconds (0 = until Ctrl-C). Chat prompts are
byte-level, so give the engine prompt room::

  PYTHONPATH=. JAX_PLATFORMS=cpu \
  python examples/serve_gpt.py --num-requests 0 --api-port 8000 \
    --max-prompt-len 64 --max-seq-len 128
  curl -N localhost:8000/v1/chat/completions -d '{
    "messages": [{"role": "user", "content": "hi"}],
    "max_tokens": 16, "stream": true}'

Run (CPU simulation; omit --requests for a synthetic trace):
  PYTHONPATH=. JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/serve_gpt.py --tp 2 --slots 2

Paged KV cache + chunked prefill (``--page-size``/``--max-pages``/
``--prefill-chunk``): a fixed-size page pool with per-slot block
tables replaces the one-contiguous-stripe-per-slot layout (short
requests stop stranding a full horizon; prefix-template hits share
pages copy-on-write), and prompts longer than one chunk admit in
chunk-sized slices interleaved with decode waves — the synthetic
trace gains a long-prompt line so both paths actually run::

  PYTHONPATH=. JAX_PLATFORMS=cpu \
  python examples/serve_gpt.py --slots 4 --max-prompt-len 32 \
    --page-size 8 --prefill-chunk 16 --num-requests 8

KV oversubscription (``apex_tpu.serving.hostswap``): ``--host-swap``
adds a host-RAM page tier under the device pool — an idle
conversation parks (its pages gather out through compiled swap
programs to pinned host buffers, its slot and HBM pages free up) and
resumes later bit-identically, so far more conversations stay
resident per chip than the pool holds; under ``PagesExhausted``
pressure the scheduler preempts the lowest-priority tenant's pages
(WFQ-aware, replayed through fault-replay on re-admission, streams
still bit-identical). ``--resume-policy swap|recompute|auto`` picks
scatter-back vs replay-from-snapshot (auto prices it from measured
swap cost). The demo parks every conversation mid-stream and resumes
it::

  PYTHONPATH=. JAX_PLATFORMS=cpu \\
  python examples/serve_gpt.py --slots 4 --page-size 8 \\
    --max-pages 10 --host-swap --num-requests 8

Observability (``apex_tpu.telemetry``): ``--metrics-port N`` serves
``/metrics`` (Prometheus text), ``/healthz`` (live-wired to the
scheduler's health state machine: 200 ok/degraded, 503
draining/failed), and ``/vars`` (JSON incl. span + recompile state)
from a background thread for the life of the process — scrape while it
serves, or add ``--metrics-linger S`` to keep the endpoint up after the
batch drains. ``--span-trace out.json`` writes the per-request span
timeline as Chrome-trace JSON (open in Perfetto next to a
``profiler.trace`` device capture).

Self-tuning (``apex_tpu.serving.tuner``): ``--autotune`` turns the
hand-set serving knobs into measured choices — a scheduler-owned
controller tunes ``decode_chunk`` / ``pipeline_depth`` /
``max_admit_batch`` / ``spec_k`` online from per-chunk
tokens-per-second EWMAs, switching only among pre-warmed compiled
variants (every declared candidate compiles at warmup; the recompile
guard stays flat), with every probe/switch/freeze a flight-recorder
event replayable from a post-mortem bundle. Composes with
``--fault-plan``: the controller hard-freezes to the base operating
point through rebuild/replay brackets::

  PYTHONPATH=. JAX_PLATFORMS=cpu \
  python examples/serve_gpt.py --num-requests 8 --max-tokens 24 \
    --autotune "decode_chunk=1,2,4;pipeline_depth=1,2"

SLO observatory (``apex_tpu.telemetry.slo``): ``--slo SPEC`` declares
latency objectives — ``SPEC`` is a comma list of
``pQQ:metric:threshold_s[:tenant]`` objectives over ``ttft`` /
``token_latency`` / ``queue_wait`` / ``e2e`` — and the scheduler then
feeds streaming quantile sketches from its existing timings, runs
multi-window burn-rate alerting against the declared error budgets,
and prints sketch-backed p50/p95/p99 plus per-objective budget status
at exit (with ``--metrics-port``, ``/slo`` serves the live snapshot
and ``serving_slo_*`` gauges ride ``/metrics``)::

  PYTHONPATH=. JAX_PLATFORMS=cpu \
  python examples/serve_gpt.py --num-requests 8 \
    --slo "p99:ttft:0.2,p95:e2e:1.0"

Chaos (``apex_tpu.serving.resilience``): ``--fault-plan SPEC`` injects
deterministic faults at the engine seams for manual recovery drills —
``SPEC`` is ``random:SEED[:N]`` or a comma list of
``point:index:kind[:arg]``, e.g. ``"fetch:2:nan:1,dispatch:5:error"``.
Interrupted requests are replayed/retried; the run prints what fired
and the final health state.

Fleet (``apex_tpu.serving.fleet``): ``--replicas N`` serves the trace
through a health-aware Router over N engine replicas, replica ``i`` on
devices ``[i*tp, (i+1)*tp)`` (too few devices is an error) — submits
placed on the best replica, failover + rolling restarts built in. Kill one
mid-burst and watch every stream complete anyway (the router fails the
interrupted requests over with their emitted prefixes; streams stay
bit-identical)::

  PYTHONPATH=. JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
  python examples/serve_gpt.py --replicas 2 --kill-replica 1@4 \
    --num-requests 8

``--kill-replica i@t`` terminally fails replica ``i`` at its ``t``-th
decode dispatch (a deterministic ``FleetFaultPlan.kill`` drill); the
run prints the fleet summary, per-replica health, and any fleet
incident manifest written next to the replica's own post-mortem
bundle (``--bundle-dir``).

Black box (``apex_tpu.telemetry.flightrec``): ``--bundle-dir DIR``
arms the always-on flight recorder and auto-dumps a self-contained
post-mortem bundle there on any fault detection / watchdog trip /
guard alarm / terminal failure; ``SIGUSR1`` (and ``GET
/debug/bundle`` on the metrics port) dump one on demand, and
``/debug/events?n=K`` tails the live event log. Replay an incident
exactly — or render its timeline with no jax installed::

  python -m apex_tpu.telemetry.replay incidents/bundle-0000-* \
      [--report]

Durable serving (``apex_tpu.serving.journal``): ``--journal-dir DIR``
arms the write-ahead request journal — every submit and every emitted
token is durable at the step boundary, ``SIGTERM`` drains and seals
the journal (a ``SIGKILL`` or power loss merely leaves a torn tail
the next open repairs), and rerunning with the SAME dir resumes every
unfinished stream exactly where it stopped, bit-identical to a run
that was never interrupted::

  PYTHONPATH=. JAX_PLATFORMS=cpu \
  python examples/serve_gpt.py --num-requests 8 --journal-dir wal &
  sleep 20 && kill -TERM %1; wait          # or kill -9: same recovery
  PYTHONPATH=. JAX_PLATFORMS=cpu \
  python examples/serve_gpt.py --num-requests 8 --journal-dir wal
"""

import argparse
import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from apex_tpu import checkpoint as ckpt
from apex_tpu import mesh as mx
from apex_tpu.models import gpt
from apex_tpu.serving import Request, SamplingParams
from apex_tpu.serving.engine import Engine, EngineConfig
from apex_tpu.serving.scheduler import Scheduler

from gpt_train import PRESETS  # examples/ sibling: one model table


def load_requests(path, vocab_size):
    reqs = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            d = json.loads(line)
            bad = [t for t in d["prompt"] if not 0 <= int(t) < vocab_size]
            if bad:
                raise ValueError(
                    f"request {d.get('id', i)}: prompt tokens {bad} "
                    f"outside vocab [0, {vocab_size})")
            sp = SamplingParams(
                temperature=d.get("temperature", 0.0),
                top_k=d.get("top_k", 0), top_p=d.get("top_p", 1.0),
                seed=d.get("seed"))
            stop = d.get("stop")
            reqs.append(Request(
                str(d.get("id", f"r{i}")), list(d["prompt"]),
                max_tokens=int(d.get("max_tokens", 16)), sampling=sp,
                eos_token_id=d.get("eos_token_id"),
                stop=[[int(t) for t in s] for s in stop]
                if stop else None))
    return reqs


def synthetic_requests(n, prompt_len, max_tokens, vocab_size,
                       prefix=None, long_prompt_len=0, tenants=None,
                       adapters=0):
    """Seeded stand-in trace: half greedy, half sampled; every third
    request carries a stop sequence (trimmed emission when it fires).
    With ``prefix`` (a pooled template's token list), every other
    request's prompt starts with it — the many-users-one-template
    workload prefix reuse exists for. With ``long_prompt_len > 0``,
    every fourth request (offset 1, so it never collides with a
    prefix row) carries a prompt of that length — the long-admission
    traffic chunked prefill (``--prefill-chunk``) interleaves with
    decode waves instead of stalling everyone's TTFT on. ``tenants``
    (a list of tenant ids) and ``adapters`` (registered LoRA adapter
    count) spread the trace round-robin across tenant identities and
    adapter rows — the many-fine-tunes-one-engine workload the
    tenancy subsystem exists for (adapter-carrying rows skip the
    shared prefix: pooled prefixes are base-weight K/V)."""
    reqs = []
    for i in range(n):
        adapter = (i % (adapters + 1)) if adapters else 0
        tenant = tenants[i % len(tenants)] if tenants else "default"
        if long_prompt_len and i % 4 == 1:
            tail = [int(t) for t in jax.random.randint(
                jax.random.PRNGKey(2000 + i), (long_prompt_len,), 0,
                vocab_size)]
        else:
            tail = [int(t) for t in jax.random.randint(
                jax.random.PRNGKey(1000 + i),
                (1 + (prompt_len + i) % prompt_len,), 0, vocab_size)]
        prompt = (list(prefix) + tail[:2]) \
            if prefix and i % 2 == 0 and not adapter else tail
        sp = (SamplingParams(temperature=0.9, top_k=20, seed=i)
              if i % 2 else SamplingParams())
        stop = [[(17 * i + 3) % vocab_size,
                 (17 * i + 4) % vocab_size]] if i % 3 == 0 else None
        reqs.append(Request(f"r{i}", prompt, max_tokens=max_tokens,
                            sampling=sp, stop=stop, tenant=tenant,
                            adapter=adapter))
    return reqs


def main():
    from apex_tpu._capabilities import enable_compilation_cache
    enable_compilation_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS),
                    help="model widths (examples/gpt_train.py's table); "
                    "tiny serves in float32 — the CPU smokes pin token "
                    "parity at that size — the full-width presets in "
                    "bfloat16")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-prompt-len", type=int, default=16)
    ap.add_argument("--max-seq-len", type=int, default=48)
    ap.add_argument("--requests", help="JSONL request file (see module "
                    "docstring); synthetic trace if omitted")
    ap.add_argument("--num-requests", type=int, default=6,
                    help="synthetic-trace size when --requests is omitted")
    ap.add_argument("--max-tokens", type=int, default=8,
                    help="synthetic-trace token budget per request")
    ap.add_argument("--decode-chunk", type=int, default=1,
                    help="tokens per compiled decode dispatch "
                    "(gpt.decode_steps): amortises dispatch latency; "
                    "token streams are identical at any setting")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="decode chunks kept in flight by the "
                    "scheduler (Engine.step_async): 1 = serial loop, "
                    "2+ overlaps host event processing with device "
                    "decode; token streams are identical at any depth")
    ap.add_argument("--ckpt", help=".atck from examples/gpt_train.py "
                    "(--preset tiny); random init if omitted")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics /healthz /vars on this port "
                    "(0 = ephemeral, printed at startup)")
    ap.add_argument("--api-port", type=int, default=None,
                    help="serve the OpenAI-compatible front end "
                    "(apex_tpu.serving.api) on this port after the "
                    "batch drains (0 = ephemeral, printed at startup)")
    ap.add_argument("--api-linger", type=float, default=0.0,
                    help="keep the API endpoint up this many seconds "
                    "(0 = until Ctrl-C)")
    ap.add_argument("--metrics-linger", type=float, default=0.0,
                    help="keep the metrics endpoint up this many "
                    "seconds after the batch drains")
    ap.add_argument("--span-trace", metavar="PATH", default=None,
                    help="write the per-request span timeline as "
                    "Chrome-trace JSON (view in Perfetto)")
    ap.add_argument("--bundle-dir", metavar="DIR", default=None,
                    help="arm the flight recorder and auto-dump "
                    "post-mortem bundles here on fault/watchdog/alarm "
                    "(SIGUSR1 or GET /debug/bundle dump on demand; "
                    "python -m apex_tpu.telemetry.replay replays one)")
    ap.add_argument("--journal-dir", metavar="DIR", default=None,
                    help="arm the durable write-ahead request journal "
                    "(apex_tpu.serving.journal): every submit and "
                    "emitted token is made durable at the fetch "
                    "boundary, SIGTERM drains + seals the journal, "
                    "and rerunning with the SAME dir resumes every "
                    "unfinished stream bit-identically (single "
                    "replica only; fleets journal per replica via "
                    "Router.restart(journal_dir=...))")
    ap.add_argument("--fault-plan", metavar="SPEC", default=None,
                    help="inject deterministic faults at the engine "
                    "seams: 'random:SEED[:N]' or a comma list of "
                    "point:index:kind[:arg] (see "
                    "apex_tpu.serving.resilience.parse_fault_plan); "
                    "with --replicas > 1 it applies to replica 0")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a fleet Router over this many "
                    "engine replicas (health-weighted routing, "
                    "deterministic failover, rolling restarts); 1 = "
                    "the plain single-engine scheduler")
    ap.add_argument("--kill-replica", metavar="I@T", default=None,
                    help="fleet chaos drill: terminally fail replica "
                    "I at its T-th decode dispatch "
                    "(FleetFaultPlan.kill) and show every stream "
                    "complete anyway via failover; needs "
                    "--replicas >= 2")
    ap.add_argument("--autotune", metavar="SPEC", nargs="?",
                    const="default", default=None,
                    help="self-tuning runtime (apex_tpu.serving.tuner):"
                    " tune serving knobs online across pre-warmed "
                    "compiled variants. SPEC is a ';'-separated ladder "
                    "list, e.g. 'decode_chunk=4,8,16;"
                    "pipeline_depth=1,2,3;spec_k=0,3' (each ladder "
                    "must contain the knob's configured base value); "
                    "bare --autotune derives default ladders from "
                    "--decode-chunk/--pipeline-depth/--spec-k. Every "
                    "candidate compiles at warmup "
                    "(EngineConfig.decode_chunks/spec_ks), switching "
                    "never recompiles, every decision is a flight-"
                    "recorder event, and the controller hard-freezes "
                    "during --fault-plan rebuilds/replay")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft this many tokens "
                    "per wave from a device-side n-gram drafter and "
                    "verify them in one batched target forward "
                    "(gpt.decode_steps_spec); the scheduler's "
                    "acceptance-EWMA payoff gate flips between the "
                    "spec and plain compiled variants, and token "
                    "streams are bit-identical either way (0 = off)")
    ap.add_argument("--kv-cache-dtype", default="auto",
                    choices=("auto", "bf16", "int8", "fp8"),
                    help="KV-cache storage: int8/fp8 store quantized "
                    "K/V with per-head per-position fp32 scales "
                    "(~2x bf16 / ~4x f32 fewer cache bytes per slot)")
    ap.add_argument("--prefix-template", metavar="IDS", action="append",
                    default=None,
                    help="comma-separated token ids of a shared prompt "
                    "prefix to pool (repeatable): prompts starting "
                    "with it admit by pooled-K/V copy + tail-only "
                    "prefill; synthetic traces prepend the first "
                    "template to half the prompts")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV cache: tokens per page (0 = the "
                    "contiguous one-stripe-per-slot layout). A short "
                    "request then pins only the pages its prompt + "
                    "budget need instead of a full max-seq-len "
                    "stripe, and prefix-template hits share the "
                    "template's pages copy-on-write; token streams "
                    "are bit-identical either way")
    ap.add_argument("--max-pages", type=int, default=0,
                    help="pages in the global pool (paged mode; 0 = "
                    "auto-size so every slot fits a worst-case "
                    "request). Set lower to oversubscribe — admission "
                    "then backpressures when the pool runs dry "
                    "instead of stranding idle capacity")
    ap.add_argument("--host-swap", action="store_true",
                    help="host-RAM page tier under the device pool "
                    "(needs --page-size): idle conversations park to "
                    "pinned host buffers through compiled swap "
                    "programs and resume bit-identically, so the "
                    "chip holds far more conversations than its "
                    "pages; page pressure preempts the lowest-"
                    "priority tenant (WFQ-aware) instead of just "
                    "backpressuring. The demo parks every "
                    "conversation mid-stream and resumes it")
    ap.add_argument("--resume-policy", default="auto",
                    choices=("auto", "swap", "recompute"),
                    help="how a parked conversation comes back: "
                    "'swap' scatters the host payload into fresh "
                    "pages, 'recompute' replays from the emitted-"
                    "prefix snapshot, 'auto' (default) prices swap-in "
                    "against replay from measured swap cost")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: prompts longer than this "
                    "admit in chunk-sized slices interleaved with "
                    "decode waves, so a long admission stops stalling "
                    "other streams' TTFT (must be a prompt bucket "
                    "dividing --max-prompt-len; 0 = monolithic "
                    "admission). The synthetic trace gains a "
                    "long-prompt line (every 4th request) to "
                    "exercise it")
    ap.add_argument("--adapters", type=int, default=0,
                    help="register this many seeded LoRA adapters "
                    "into the engine's static pool "
                    "(EngineConfig.adapter_slots) and spread the "
                    "synthetic trace round-robin across them + the "
                    "base model — many fine-tunes, one compiled "
                    "batch, zero recompiles")
    ap.add_argument("--tenant-weights", metavar="SPEC", default=None,
                    help="tenant fair-share weights, e.g. 'a:3,b:1' — "
                    "the scheduler's weighted-fair queueing converges "
                    "per-tenant served-token shares to this ratio "
                    "under contention; the synthetic trace spreads "
                    "requests round-robin over the named tenants")
    ap.add_argument("--slo", metavar="SPEC", default=None,
                    help="declare latency SLOs (apex_tpu.telemetry."
                    "slo): a comma list of pQQ:metric:threshold_s"
                    "[:tenant] objectives, e.g. 'p99:ttft:0.2,"
                    "p95:e2e:1.0' (metrics: ttft, token_latency, "
                    "queue_wait, e2e). The scheduler feeds streaming "
                    "quantile sketches + burn-rate error-budget "
                    "machines and the run prints sketch percentiles "
                    "and per-objective budget status at exit")
    ap.add_argument("--tenant-rate", metavar="SPEC", default=None,
                    help="per-tenant token budgets (tokens/s), e.g. "
                    "'a:50': a submit over budget is rejected with a "
                    "retry-after (the API maps it to 429) while other "
                    "tenants are untouched")
    args = ap.parse_args()

    def parse_tenant_spec(spec):
        out = {}
        for part in spec.split(","):
            name, _, val = part.partition(":")
            if not name.strip() or not val:
                raise SystemExit(
                    f"bad tenant spec {part!r} (format name:value,...)")
            out[name.strip()] = float(val)
        return out

    tenancy_cfg = None
    tenant_names = None
    if args.tenant_weights or args.tenant_rate:
        from apex_tpu.serving.tenancy import TenancyConfig

        weights = parse_tenant_spec(args.tenant_weights or "") \
            if args.tenant_weights else {}
        rates = parse_tenant_spec(args.tenant_rate or "") \
            if args.tenant_rate else {}
        tenancy_cfg = TenancyConfig(weights=weights, rates=rates)
        tenant_names = sorted(set(weights) | set(rates)) or None
        print(f"tenancy: weights={weights} rates={rates}")

    slo_cfg = None
    if args.slo:
        from apex_tpu.telemetry.slo import SLOConfig, parse_objective

        try:
            slo_cfg = SLOConfig(objectives=tuple(
                parse_objective(part)
                for part in args.slo.split(",") if part.strip()))
        except ValueError as e:
            raise SystemExit(f"--slo: {e}")
        print("slo objectives: "
              + ", ".join(o.key() for o in slo_cfg.objectives))

    cfg = gpt.GPTConfig(
        remat=False, kv_cache_dtype=args.kv_cache_dtype,
        compute_dtype=(jnp.float32 if args.preset == "tiny"
                       else jnp.bfloat16), **PRESETS[args.preset])
    if args.replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    devices = jax.devices()
    if args.replicas * args.tp > len(devices):
        raise SystemExit(
            f"--replicas {args.replicas} x --tp {args.tp} needs "
            f"{args.replicas * args.tp} devices and {len(devices)} are "
            f"visible: every replica runs on tp devices of its own")
    # tp-only meshes: decode state is replicated over dp/pp, so an
    # engine takes exactly tp devices (build_mesh would default dp to
    # fill); replica i takes the i-th group of tp
    meshes = [mx.build_mesh(
        tp=args.tp, devices=devices[i * args.tp:(i + 1) * args.tp])
        for i in range(args.replicas)]
    if args.ckpt:
        from apex_tpu.amp import ScalerConfig
        from apex_tpu.models import training
        from apex_tpu.optimizers import fused_adam
        init_fn, _ = training.make_train_step(
            cfg, meshes[0], fused_adam(1e-4, layout="tree"),
            ScalerConfig(enabled=False))
        params = ckpt.load_checkpoint(
            args.ckpt, init_fn(jax.random.PRNGKey(0))).params
    else:
        params = gpt.init(cfg, jax.random.PRNGKey(0))

    if args.kill_replica and args.replicas < 2:
        raise SystemExit("--kill-replica needs --replicas >= 2 (a "
                         "fleet of one has nowhere to fail over)")
    fault_plan = None
    if args.fault_plan:
        from apex_tpu.serving.resilience import parse_fault_plan

        fault_plan = parse_fault_plan(args.fault_plan)
        print(f"fault plan: {[s.describe() for s in fault_plan.specs]}")
    kill_plan = None
    if args.kill_replica:
        from apex_tpu.serving.resilience import FleetFaultPlan

        victim, at = args.kill_replica.split("@")
        kill_plan = FleetFaultPlan.kill(int(victim), args.replicas,
                                        at=int(at))
        print(f"fleet kill drill: {kill_plan.describe()}")
    templates = [[int(t) for t in spec.split(",")]
                 for spec in (args.prefix_template or ())]
    tuner_cfg = None
    decode_chunks = spec_ks = None
    if args.autotune is not None:
        from apex_tpu.serving.tuner import KNOBS, TunerConfig

        if args.autotune == "default":
            ladders = {
                "decode_chunk": tuple(sorted(
                    {args.decode_chunk, 2 * args.decode_chunk})),
                "pipeline_depth": tuple(sorted(
                    {1, args.pipeline_depth, args.pipeline_depth + 1})),
            }
            if args.spec_k > 0:
                ladders["spec_k"] = (0, args.spec_k)
        else:
            ladders = {}
            for part in args.autotune.split(";"):
                knob, _, vals = part.partition("=")
                knob = knob.strip()
                if knob not in KNOBS or not vals:
                    raise SystemExit(
                        f"--autotune: bad ladder {part!r} (knobs: "
                        f"{', '.join(KNOBS)}; format knob=v1,v2,...)")
                ladders[knob] = tuple(int(v) for v in vals.split(","))
        tuner_cfg = TunerConfig(**ladders)
        # every declared device-variant candidate becomes a compiled,
        # warmed step variant — the tuner only ever switches among
        # warm programs
        decode_chunks = ladders.get("decode_chunk")
        sk = tuple(sorted(k for k in ladders.get("spec_k", ()) if k))
        spec_ks = sk or None
        print(f"autotune: {ladders}")
    if args.host_swap and not args.page_size:
        raise SystemExit("--host-swap needs --page-size (the host "
                         "tier pages a paged pool)")
    ecfg = EngineConfig(
        slots=args.slots, max_prompt_len=args.max_prompt_len,
        max_seq_len=args.max_seq_len, decode_chunk=args.decode_chunk,
        prefix_pool_slots=len(templates), spec_k=args.spec_k,
        page_size=args.page_size, num_pages=args.max_pages,
        prefill_chunk=args.prefill_chunk,
        host_swap=args.host_swap, resume_policy=args.resume_policy,
        decode_chunks=decode_chunks, spec_ks=spec_ks,
        adapter_slots=args.adapters + 1 if args.adapters else 0)

    def replica_plan(i):
        if kill_plan is not None:
            return kill_plan[i]
        return fault_plan if i == 0 else None

    # compile every program (init/step/retire + each (bucket, k)
    # admission variant + prefix pool inserts/extends) before the first
    # request — admission never traces mid-serve, and recompile_guard
    # could be armed right here
    engines = []
    for i in range(args.replicas):
        # each replica holds its own copy of the weights on its devices
        # (left where init put them, every call would move them again)
        placed = jax.device_put(params, jax.tree.map(
            lambda spec: NamedSharding(meshes[i], spec),
            gpt.param_specs(cfg),
            is_leaf=lambda x: isinstance(x, PartitionSpec)))
        e = Engine(cfg, placed, meshes[i], ecfg,
                   fault_plan=replica_plan(i))
        e.warmup()
        engines.append(e)
    engine = engines[0]
    long_len = 0
    if args.prefill_chunk and not args.requests:
        # a long-prompt line in the synthetic trace: longer than one
        # chunk (so it actually admits chunked) and capped to the
        # engine's prompt room
        long_len = min(args.max_prompt_len, 2 * args.prefill_chunk)
    reqs = (load_requests(args.requests, cfg.vocab_size) if args.requests
            else synthetic_requests(args.num_requests, 8, args.max_tokens,
                                    cfg.vocab_size,
                                    prefix=templates[0] if templates
                                    else None,
                                    long_prompt_len=long_len,
                                    tenants=tenant_names,
                                    adapters=args.adapters))

    # telemetry: spans whenever a trace is requested; the registry +
    # process-wide recompile sentinel only when there is a /metrics
    # endpoint to export them through (counters nobody can scrape are
    # pure per-token overhead); the flight recorder whenever bundles
    # OR a metrics endpoint exist (the /debug/events tail)
    registry = spans = server = recorder = None
    if args.span_trace or args.metrics_port is not None:
        from apex_tpu.telemetry import SpanRecorder

        spans = SpanRecorder()
    if args.metrics_port is not None:
        from apex_tpu.telemetry import Registry

        registry = Registry()
        engine.recompile_sentinel(registry=registry)
    if args.bundle_dir is not None or args.metrics_port is not None:
        from apex_tpu.telemetry import FlightRecorder

        recorder = FlightRecorder()

    # offline batch mode submits everything up front — size the queue to
    # the trace instead of dying on backpressure at the default 256
    bundle_meta = ({"params": {"ckpt": args.ckpt}} if args.ckpt
                   else {"params": {"init_seed": 0}})
    journaled_ids = set()
    if args.journal_dir is not None and args.replicas > 1:
        raise SystemExit(
            "--journal-dir journals the single-replica path only; "
            "fleets journal per replica and recover through "
            "Router.restart(i, journal_dir=...)")
    if args.replicas > 1:
        from apex_tpu.serving.fleet import Router
        from apex_tpu.serving.resilience import ResilienceConfig

        # per-engine serving metrics would collide name-for-name in
        # one registry, so the fleet registry carries the router's
        # per-replica-labeled serving_fleet_* surface instead; the
        # shared recorder gives ONE merged incident timeline. The
        # kill drill needs retry headroom (see FleetFaultPlan.kill).
        # fleet tenancy split: WFQ weights apply per replica, RATE
        # limits apply at the router's ingress (one fleet-wide bucket
        # per tenant — per-replica buckets would multiply the cap by
        # the replica count)
        rep_tenancy = fleet_tenancy = None
        if tenancy_cfg is not None:
            from apex_tpu.serving.tenancy import TenancyConfig

            if dict(tenancy_cfg.weights):
                rep_tenancy = TenancyConfig(
                    weights=tenancy_cfg.weights)
            if dict(tenancy_cfg.rates):
                fleet_tenancy = TenancyConfig(rates=tenancy_cfg.rates)
        replica_scheds = [
            Scheduler(e, max_queue=max(256, len(reqs)), spans=spans,
                      pipeline_depth=args.pipeline_depth,
                      recorder=recorder, bundle_dir=args.bundle_dir,
                      bundle_meta=bundle_meta, tuner=tuner_cfg,
                      tenancy=rep_tenancy, slo=slo_cfg,
                      resilience=ResilienceConfig(max_retries=8))
            for e in engines]
        sched = Router(replica_scheds, registry=registry,
                       recorder=recorder, bundle_dir=args.bundle_dir,
                       tenancy=fleet_tenancy)
        for t in templates:  # every replica serves the hit
            sched.register_prefix(t)
        for i in range(args.adapters):
            # fleet-wide: same ids mean the same weights on every
            # replica, so failover streams stay bit-identical
            sched.register_adapter(seed=100 + i)
        bundle_sched = replica_scheds[0]   # SIGUSR1 / /debug/bundle
    else:
        journal = None
        if args.journal_dir is not None:
            from apex_tpu.serving.journal import Journal

            # opening repair-scans: a torn tail from a crash is
            # truncated at the last complete record before append
            journal = Journal(args.journal_dir)
            resume_seq = journal.seq
        sched = Scheduler(engine, max_queue=max(256, len(reqs)),
                          registry=registry, spans=spans,
                          pipeline_depth=args.pipeline_depth,
                          recorder=recorder, bundle_dir=args.bundle_dir,
                          tuner=tuner_cfg, tenancy=tenancy_cfg,
                          slo=slo_cfg, journal=journal,
                          # params provenance: telemetry.replay rebuilds
                          # the model from a bundle with this
                          bundle_meta=bundle_meta)
        for t in templates:  # after warmup (which resets the pool)
            engine.register_prefix(t)
        for i in range(args.adapters):
            sched.register_adapter(seed=100 + i)
        if journal is not None and resume_seq:
            # warm restart: resubmit every unfinished journaled stream
            # with its emitted prefix (it continues bit-identically),
            # and keep finished ids out of this run's trace
            from apex_tpu.serving.journal import (replay_into,
                                                  replay_state,
                                                  scan_journal)

            journaled_ids = set(replay_state(
                scan_journal(args.journal_dir)[0]).requests)
            report = replay_into(sched, args.journal_dir)
            print(f"journal: resumed {report.requests} unfinished "
                  f"request(s) from {args.journal_dir} "
                  f"({report.adapters} adapters, {report.prefixes} "
                  f"prefixes replayed)")
        bundle_sched = sched
    if args.bundle_dir is not None:
        import signal

        # SIGUSR-style on-demand dump: kill -USR1 <pid>. A disk error
        # here must not take down the serving loop the handler
        # interrupted (same policy as the scheduler's auto-dump path).
        def _dump_on_signal(*_):
            try:
                print(f"bundle: {bundle_sched.dump_bundle('sigusr1')}")
            except OSError as e:
                print(f"bundle dump failed: {e}")

        if hasattr(signal, "SIGUSR1"):
            signal.signal(signal.SIGUSR1, _dump_on_signal)
        print(f"black box armed: bundles -> {args.bundle_dir} "
              f"(SIGUSR1 dumps on demand)")
    shutdown = {"requested": False}
    if args.journal_dir is not None:
        import signal

        # graceful shutdown: the handler only sets a flag — the serve
        # loop breaks at the next STEP boundary, where the journal's
        # fetch-boundary commit has already made every emitted token
        # durable (same policy as the SIGUSR1 handler: no real work
        # inside a signal frame)
        def _on_sigterm(*_):
            shutdown["requested"] = True

        if hasattr(signal, "SIGTERM"):
            signal.signal(signal.SIGTERM, _on_sigterm)
        print(f"durable journal armed: {args.journal_dir} (SIGTERM "
              f"drains + seals; rerun with the same --journal-dir to "
              f"resume unfinished streams)")
    if args.metrics_port is not None:
        from apex_tpu.telemetry import start_metrics_server

        # /healthz answers from the scheduler's live health machine
        # (200 ok/degraded, 503 draining/failed)
        server = start_metrics_server(
            registry, port=args.metrics_port, spans=spans,
            sentinel=engine.recompile_sentinel(),
            health=sched.health.healthz, recorder=recorder,
            bundle_trigger=(
                (lambda: bundle_sched.dump_bundle("http"))
                if args.bundle_dir is not None else None),
            slo=((sched.slo_status if args.replicas > 1
                  else bundle_sched.slo.status)
                 if slo_cfg is not None else None))
        print(f"metrics: {server.url}/metrics  /healthz  /vars  "
              f"/debug/events"
              + ("  /slo" if slo_cfg is not None else ""))
    from apex_tpu.serving.tenancy import TenantThrottled

    throttled = []
    for r in reqs:
        if r.request_id in journaled_ids:
            continue  # resumed (or already finished) by the journal
        try:
            sched.submit(r)
        except TenantThrottled as e:
            # the offline-demo spelling of the API's 429: report and
            # move on — other tenants' requests are untouched
            throttled.append(r.request_id)
            print(f"request {r.request_id} throttled "
                  f"(tenant {e.tenant!r}, retry in "
                  f"{e.retry_after_s:.1f}s)")
    if args.host_swap and args.replicas == 1:
        # the park-and-resume demo: tick a couple of chunks, park
        # every running conversation (its user walked away — pages
        # swap out to the host tier, the slot frees), show the host
        # tier holding them, then resume; streams stay bit-identical
        for _ in range(2):
            sched.step()
        for rid in sorted(a.request.request_id
                          for a in sched.active.values()):
            sched.pause(rid)
        parked = list(sched.parked_requests)
        if parked:
            print(f"parked {len(parked)} conversation(s) to host RAM "
                  f"({args.resume_policy} resume): {parked}")
            print(f"host tier: " + json.dumps(
                {k: round(v, 1)
                 for k, v in engine.host_tier_stats().items()}))
            for rid in parked:
                sched.resume(rid)
    if args.journal_dir is not None:
        # step loop instead of run_until_idle so SIGTERM can break at
        # a step boundary — everything emitted so far is already
        # durable (the journal commits at every fetch boundary)
        while not sched.idle() and not shutdown["requested"]:
            sched.step()
        if shutdown["requested"]:
            live = (len(sched.active) + len(sched.queue)
                    + len(sched.parked_requests))
            sched.journal.close()
            if args.bundle_dir is not None:
                try:
                    print(f"bundle: {sched.dump_bundle('sigterm')}")
                except OSError as e:
                    print(f"bundle dump failed: {e}")
            print(f"sigterm: drained at a step boundary with "
                  f"{live} stream(s) unfinished — journal sealed; "
                  f"rerun with --journal-dir {args.journal_dir} "
                  f"to resume them bit-identically")
        else:
            sched.journal.close()
    else:
        sched.run_until_idle()
    for r in reqs:
        if r.request_id in throttled:
            continue
        c = sched.completions.get(r.request_id)
        if c is None:
            continue  # interrupted by SIGTERM — journaled, resumable
        print(f"request {c.request_id} [{c.finish_reason}] "
              f"{list(r.prompt)} -> {c.tokens}")
    print("served " + json.dumps(
        {k: round(v, 3) for k, v in sched.summary().items()}))
    if (tenancy_cfg is not None or args.adapters) \
            and args.replicas == 1:
        print("tenants " + json.dumps(sched.tenant_summary()))
    if tuner_cfg is not None and args.replicas == 1:
        s = sched.summary()
        point = {name: int(s[f"tuner_{name}"])
                 for name, _ in tuner_cfg.ladders()
                 if f"tuner_{name}" in s}
        print(f"autotune: state={s['tuner_state']:.0f} "
              f"probes={s['tuner_probes']:.0f} "
              f"switches={s['tuner_switches']:.0f} incumbent={point}")
    if slo_cfg is not None:
        # sketch-backed exit report: percentiles per metric, then each
        # objective's budget verdict (a final evaluation first, so a
        # run shorter than the eval cadence still gets a verdict)
        mon = (sched.slo if args.replicas == 1
               else bundle_sched.slo)
        for m in mon.machines.values():
            m.evaluate(mon.clock())
        for metric in ("ttft", "token_latency", "queue_wait", "e2e"):
            pct = mon.percentiles(metric)
            if not pct.get("count"):
                continue
            print(f"slo {metric}: p50={pct['p50_ms']:.2f}ms "
                  f"p95={pct['p95_ms']:.2f}ms "
                  f"p99={pct['p99_ms']:.2f}ms "
                  f"(n={pct['count']:.0f})")
        for key, m in mon.machines.items():
            st = m.status()
            print(f"slo {key}: state={st['state']} "
                  f"budget_remaining={st['budget_remaining']:.4f} "
                  f"good={st['good']:.0f} bad={st['bad']:.0f}")
        if args.replicas > 1:
            for metric in ("ttft", "e2e"):
                pct = sched.fleet_percentiles(metric)
                if pct.get("count"):
                    print(f"slo fleet {metric}: "
                          f"p99={pct['p99_ms']:.2f}ms "
                          f"(n={pct['count']:.0f}, pooled across "
                          f"{len(sched.replicas)} replicas)")
    if fault_plan is not None:
        print(f"chaos: {len(fault_plan.injected)} fault(s) fired "
              f"({[s.describe() for s in fault_plan.injected]}), "
              f"health={sched.health.state}")
    if kill_plan is not None:
        status, body = sched.health.healthz()
        print(f"fleet after kill drill: {len(kill_plan.injected)} "
              f"fault(s) fired, /healthz {status} {body.strip()!r}")
        for rep in sched.replicas:
            print(f"  replica {rep.index}: state={rep.state} "
                  f"health={rep.health_state} routed={rep.routed} "
                  f"bundles={rep.sched.bundles_written}")
        if sched.incidents_written:
            print(f"  fleet incident manifests: "
                  f"{sched.incidents_written}")
    bundles = getattr(sched, "bundles_written", None)
    if bundles:
        print(f"post-mortem bundles: {bundles} — replay "
              f"with `python -m apex_tpu.telemetry.replay <bundle>`")
    if args.span_trace:
        with open(args.span_trace, "w") as f:
            json.dump(spans.to_chrome_trace(), f)
        print(f"span trace: {args.span_trace} "
              f"({spans.summary()['events']} events)")
    if args.api_port is not None:
        import time

        from apex_tpu.serving.api import start_api_server

        # the ApiServer's driver thread takes over the (now idle)
        # scheduler; the main thread just waits out the linger
        api = start_api_server(sched, port=args.api_port,
                               registry=registry)
        print(f"api: {api.url}/v1/chat/completions  /v1/completions  "
              f"/v1/models  /healthz")
        try:
            if args.api_linger > 0:
                time.sleep(args.api_linger)
            else:
                while True:
                    time.sleep(3600)
        except KeyboardInterrupt:
            pass
        api.stop()
    if server is not None:
        if args.metrics_linger > 0:
            import time

            print(f"metrics endpoint lingering {args.metrics_linger}s "
                  f"at {server.url}")
            time.sleep(args.metrics_linger)
        server.stop()


if __name__ == "__main__":
    main()
