"""Serving driver: ``python -m repro.launch.serve --arch llama3_8b --smoke``
or, spec-first, ``python -m repro.launch.serve --config spec.json``.

The pipeline is constructed from a declarative ``PipelineSpec`` either loaded
from ``--config`` (JSON) or mapped from the legacy CLI flags (``--arch``,
``--index``, ``--quant``, ...), so both paths exercise the same registry
``build(spec)`` entry point.  Drive modes:

* ``sync``   — the original offline replay (one op at a time, back-to-back);
* ``open``   — open-loop load generation (Poisson/bursty/uniform arrivals at
               ``--target-qps``) through the continuous-batching executor;
* ``closed`` — closed-loop with ``--concurrency`` outstanding requests.

``--stage-pipeline`` additionally runs the workload's query stream through
the per-stage pipelined ``StagedExecutor`` (stage N on batch i+1 while stage
N+1 runs batch i) and prints per-stage busy/idle/occupancy.

``--elastic`` (open/closed modes) swaps the backend for the
``ElasticExecutor``: per-stage replica pools driven by an
``AutoscaleController`` that scales replicas/batches toward the bottleneck
and walks the ``nprobe``/``rerank_k`` quality ladder under SLO pressure.
``--json-out`` writes the machine-readable run document (summary, per-stage
occupancy table, scaling events, knob timeline) for benchmarks and CI.

``--scenario NAME`` runs a registered benchmark scenario
(``repro.scenarios``) instead of assembling one from flags: the scenario
fully defines the arrival process, op mix, SLO, autoscale block and seed.
``--scenario-sim`` switches to the wall-clock-free deterministic replay
(the golden-trace mode); ``--scenario list`` prints the catalog.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.core.pipeline import PipelineConfig
from repro.core.registry import build
from repro.core.spec import GenSpec, PipelineSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.metrics.quality import evaluate_traces
from repro.monitor.monitor import MonitorConfig, ResourceMonitor
from repro.obs import (MetricsRegistry, Tracer, VirtualClock, WallClock,
                       attach_pipeline, write_chrome_trace, write_jsonl)
from repro.serving.arrival import ArrivalConfig
from repro.serving.autoscale import AutoscaleConfig, AutoscaleController
from repro.serving.batcher import BatchPolicy
from repro.serving.elastic import ElasticExecutor
from repro.serving.harness import ServingConfig, ServingHarness
from repro.serving.staged import StagedExecutor
from repro.workload.corpus import CorpusConfig, SyntheticCorpus
from repro.workload.generator import WorkloadConfig, WorkloadGenerator
from repro.workload.runner import gold_chunks_for, run_workload


def spec_from_args(args) -> PipelineSpec:
    """Map the legacy flag set onto a PipelineSpec (back-compat path)."""
    pcfg = PipelineConfig(
        index_type=args.index, quant=args.quant, retrieve_k=8, rerank_k=3,
        gen_batch=args.batch,
        llm="model" if args.arch else "extractive", llm_arch=args.arch,
        llm_smoke=args.smoke, max_new_tokens=args.max_new)
    spec = PipelineSpec.from_config(pcfg)
    if args.arch:
        # the serving driver always ran its generator with a short prompt
        spec.llm.options["max_prompt"] = 128
    return spec


def write_trace(path: str, tracer, registry=None) -> None:
    """Emit the Chrome/Perfetto ``trace_event`` JSON plus a line-delimited
    sibling (``<path minus .json>.jsonl``) for downstream tooling."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    write_chrome_trace(path, tracer, registry)
    stem = path[:-5] if path.endswith(".json") else path
    write_jsonl(stem + ".jsonl", tracer, registry)
    print(f"wrote {path} ({len(tracer)} trace events) and {stem}.jsonl")


def run_scenario(args) -> None:
    """Drive one registered scenario (live or deterministic-sim mode) and
    print/emit the unified scenario report."""
    from repro.scenarios import ScenarioRunner, get_scenario, scenario_names
    if args.scenario == "list":
        for name in scenario_names():
            print(name, "-", get_scenario(name).description)
        return
    spec = get_scenario(args.scenario)
    if args.scenario_scale != 1.0:
        spec = spec.scaled(args.scenario_scale)
    if args.seed is not None:
        spec = spec.replace(seed=args.seed)
    runner = ScenarioRunner(spec)
    tracer = None
    if args.trace_out:
        # sim spans land at explicit virtual times (bit-deterministic);
        # live spans ride the run-relative wall clock
        tracer = Tracer(clock=VirtualClock() if args.scenario_sim
                        else WallClock())
    report = (runner.simulate(tracer=tracer) if args.scenario_sim
              else runner.serve(tracer=tracer))
    s = report.summary
    print(f"scenario {spec.name} ({report.mode}): "
          f"{int(s.get('n_queries', 0))} queries / "
          f"{int(s.get('n_mutations', 0))} mutations, seed {spec.seed}")
    print(f"latency p50/p95/p99 (ms): {s.get('p50_latency_ms', 0.0):.1f} / "
          f"{s.get('p95_latency_ms', 0.0):.1f} / "
          f"{s.get('p99_latency_ms', 0.0):.1f}")
    print(f"SLO {spec.slo_ms:.0f} ms: attainment "
          f"{s.get('slo_attainment', 0.0):.3f}, goodput "
          f"{s.get('goodput_qps', 0.0):.2f} QPS, quality-aware goodput "
          f"{s.get('quality_goodput_qps', 0.0):.2f} QPS "
          f"(quality weight {s.get('quality_weight_mean', 1.0):.3f})")
    print(f"scaling events: {len(report.scaling_events)}, knob moves: "
          f"{len(report.knob_timeline)}, deterministic replay: "
          f"{report.deterministic_replay}")
    if spec.faults.enabled:
        ev = report.fault_events
        n_retires = sum(1 for e in report.scaling_events
                        if e["kind"] == "retire")
        print(f"chaos: {sum(1 for e in ev if e['action'] == 'inject')} "
              f"faults injected, "
              f"{sum(1 for e in ev if e['action'] == 'respawn')} respawns, "
              f"{n_retires} straggler retires; availability "
              f"{s.get('availability', 1.0):.3f}, error rate "
              f"{s.get('error_rate', 0.0):.3f} "
              f"({int(s.get('n_failed', 0))} failed / "
              f"{int(s.get('n_retried', 0))} retried)")
    print("quality:", {k: round(v, 3) for k, v in report.quality.items()})
    if report.trace_decomposition:
        parts = [f"{c} {v.get('p95_ms', 0.0):.2f}"
                 for c, v in report.trace_decomposition.items()]
        print("critical path p95 (ms):", ", ".join(parts))
    if tracer is not None:
        registry = MetricsRegistry()
        registry.absorb_stage_rows(report.stage_report, t=0.0)
        registry.absorb_scale_events(report.scaling_events)
        write_trace(args.trace_out, tracer, registry)
    if args.json_out:
        os.makedirs(os.path.dirname(args.json_out) or ".", exist_ok=True)
        with open(args.json_out, "w") as f:
            json.dump(report.to_dict(), f, indent=2, sort_keys=True)
        print(f"wrote {args.json_out}")


def main(argv=None) -> int:
    """Returns the exit status: non-zero when any request failed."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="",
                    help="PipelineSpec JSON; overrides the legacy flags")
    ap.add_argument("--arch", default="",
                    help="generation backbone (legacy flags path)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--docs", type=int, default=64)
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--index", default="ivf", choices=["flat", "ivf"])
    ap.add_argument("--quant", default="none", choices=["none", "sq8", "pq"])
    ap.add_argument("--update-frac", type=float, default=0.1)
    ap.add_argument("--distribution", default="uniform",
                    choices=["uniform", "zipfian"])
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--monitor-out", default="")
    # continuous-batching generation engine (token-level scheduling)
    ap.add_argument("--gen-engine", action="store_true",
                    help="serve generation through the token-level "
                         "continuous-batching engine (model llm only)")
    ap.add_argument("--gen-slots", type=int, default=4,
                    help="KV-cache slot pool size for --gen-engine")
    ap.add_argument("--gen-chunk", type=int, default=32,
                    help="chunked-prefill granularity for --gen-engine")
    ap.add_argument("--gen-admission", default="fcfs",
                    choices=["fcfs", "sjf"],
                    help="slot admission policy for --gen-engine")
    # serving-mode flags
    ap.add_argument("--mode", default="sync",
                    choices=["sync", "open", "closed"])
    ap.add_argument("--stage-pipeline", action="store_true",
                    help="also run the query stream through the per-stage "
                         "pipelined executor and print stage occupancy")
    ap.add_argument("--target-qps", type=float, default=20.0,
                    help="offered load for --mode open")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="latency SLO (default: the spec's autoscale block "
                         "when elastic, else 500)")
    ap.add_argument("--concurrency", type=int, default=4,
                    help="in-flight cap for --mode closed")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "bursty", "uniform", "diurnal"])
    ap.add_argument("--ramp-period-s", type=float, default=8.0,
                    help="diurnal arrivals: one trough→peak→trough period")
    ap.add_argument("--ramp-amplitude", type=float, default=0.8,
                    help="diurnal arrivals: rate swing around the mean")
    ap.add_argument("--batch-timeout-ms", type=float, default=20.0,
                    help="continuous-batching coalesce deadline")
    ap.add_argument("--priority", default="fifo",
                    choices=["fifo", "query_first", "mutation_first"])
    # elastic serving flags
    ap.add_argument("--elastic", action="store_true",
                    help="serve through per-stage replica pools with the "
                         "occupancy-driven autoscaler (open/closed modes)")
    ap.add_argument("--max-replicas", type=int, default=0,
                    help="replica cap per stage (0 = spec autoscale block)")
    ap.add_argument("--autoscale-interval-ms", type=float, default=0.0,
                    help="controller cadence (0 = spec autoscale block)")
    ap.add_argument("--json-out", default="",
                    help="write the run document (summary, per-stage "
                         "occupancy table, scaling events) as JSON")
    ap.add_argument("--trace-out", default="",
                    help="record per-request spans and write a Chrome/"
                         "Perfetto trace_event JSON (plus a .jsonl sibling); "
                         "with --scenario-sim the trace is bit-deterministic")
    # scenario suite (repro.scenarios): named, seeded workload scenarios
    ap.add_argument("--scenario", default="",
                    help="run a registered benchmark scenario by name "
                         "('list' prints the catalog); overrides the "
                         "flag-assembled workload")
    ap.add_argument("--scenario-sim", action="store_true",
                    help="run the scenario as the wall-clock-free "
                         "deterministic replay instead of live serving")
    ap.add_argument("--scenario-scale", type=float, default=1.0,
                    help="corpus/stream size multiplier for --scenario")
    # default None so run_scenario can tell "--seed 0" from "not given"
    # (a scenario's own seed must only be overridden explicitly)
    ap.add_argument("--seed", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.scenario:
        run_scenario(args)
        return 0
    if args.seed is None:
        args.seed = 0
    if args.target_qps <= 0:
        ap.error("--target-qps must be > 0")
    if args.concurrency < 1:
        ap.error("--concurrency must be >= 1")
    if not args.config and not args.arch:
        ap.error("need --config spec.json or --arch <backbone>")
    if args.elastic and args.mode == "sync":
        ap.error("--elastic needs --mode open or closed")

    spec = (PipelineSpec.from_file(args.config) if args.config
            else spec_from_args(args))
    if args.gen_engine:
        if spec.llm.component != "model":
            ap.error("--gen-engine needs the 'model' llm "
                     "(--arch or a spec with llm.component == 'model')")
        spec = spec.replace(gen=GenSpec(
            enabled=True, slots=args.gen_slots, chunk_tokens=args.gen_chunk,
            admission=args.gen_admission))
    # --elastic forces it; otherwise the spec's autoscale block opts in
    elastic_on = args.elastic or (args.mode != "sync"
                                  and spec.autoscale.enabled)
    slo_ms = (args.slo_ms if args.slo_ms is not None
              else spec.autoscale.slo_ms if elastic_on else 500.0)
    pipe = build(spec)
    tracer = registry = None
    if args.trace_out:
        tracer = Tracer(clock=WallClock())
        registry = MetricsRegistry(clock=tracer.clock)
        if not elastic_on:
            # lock-step / staged paths: batch-level stage spans; the elastic
            # executor records richer per-item spans itself (never both)
            attach_pipeline(tracer, pipe)
        if hasattr(pipe.db, "tracer"):
            pipe.db.tracer = tracer
        eng = getattr(pipe.llm, "engine", None)
        if eng is not None:
            eng.tracer = tracer
    monitor = ResourceMonitor(MonitorConfig(out_path=args.monitor_out)).start()
    monitor.add_gauge("db_live", lambda: pipe.db.stats()["live"])
    if hasattr(pipe.db, "gauges"):   # sharded backend: per-shard balance
        monitor.add_gauges(pipe.db.gauges())

    corpus = SyntheticCorpus(CorpusConfig(n_docs=args.docs))
    t0 = time.perf_counter()
    n_chunks = pipe.index_documents(corpus.all_documents())
    print(f"indexed {args.docs} docs -> {n_chunks} chunks "
          f"in {time.perf_counter() - t0:.1f}s")

    wcfg = WorkloadConfig(
        query_frac=1.0 - args.update_frac, update_frac=args.update_frac,
        distribution=args.distribution, n_requests=args.requests,
        seed=args.seed)

    json_doc = {"mode": args.mode, "elastic": elastic_on,
                "seed": args.seed}

    if args.mode == "sync":
        res = run_workload(pipe, corpus, wcfg, query_batch=args.batch)
        print(f"served {args.requests} requests: {res.qps:.2f} QPS")
        print("quality:", {k: round(v, 3) for k, v in res.quality.items()})
        json_doc["qps"] = res.qps
        json_doc["quality"] = res.quality
    else:
        # warm the jit caches so compile time doesn't pollute the tail
        pipe.query(["warmup query"])
        pipe.traces.clear()
        if hasattr(getattr(pipe.llm, "stats", None), "reset"):
            pipe.llm.stats.reset()      # the warm-up is not a request
        scfg = ServingConfig(
            arrival=ArrivalConfig(
                mode=args.mode, process=args.arrival,
                target_qps=args.target_qps, n_requests=args.requests,
                concurrency=args.concurrency,
                ramp_period_s=args.ramp_period_s,
                ramp_amplitude=args.ramp_amplitude, seed=args.seed),
            policy=BatchPolicy(max_batch=args.batch,
                               max_wait_s=args.batch_timeout_ms / 1e3,
                               priority=args.priority),
            slo_ms=slo_ms, evaluate=True)
        executor = controller = None
        if elastic_on:
            executor = ElasticExecutor(
                pipe, replicas=spec.stage_replicas(),
                batch_sizes=spec.stage_batch_sizes(),
                default_batch=args.batch,
                max_replicas=args.max_replicas
                or spec.autoscale.max_replicas,
                tracer=tracer)
            acfg = AutoscaleConfig.from_spec(
                spec.autoscale, base_nprobe=executor.knobs["nprobe"],
                base_rerank_k=executor.knobs["rerank_k"],
                base_max_new=executor.knobs.get("max_new", 0))
            acfg.max_replicas = executor.max_replicas
            acfg.slo_ms = slo_ms
            if args.autoscale_interval_ms > 0:
                acfg.interval_s = args.autoscale_interval_ms / 1e3
            controller = AutoscaleController(acfg, executor=executor)
        harness = ServingHarness(pipe, corpus, wcfg, scfg,
                                 executor=executor, tracer=tracer)
        monitor.add_gauges(harness.gauges())
        if controller is not None:
            controller.start()
        try:
            res = harness.run()
        finally:
            if controller is not None:
                controller.stop()
        s = res.summary
        if args.mode == "open":
            print(f"offered {s.get('offered_qps', 0.0):.2f} QPS "
                  f"({args.arrival}), achieved "
                  f"{s.get('achieved_qps', 0.0):.2f} QPS")
        else:
            print(f"closed-loop concurrency={args.concurrency}: "
                  f"achieved {s.get('achieved_qps', 0.0):.2f} QPS "
                  f"(peak in-flight {res.peak_in_flight})")
        # .get defaults: a query-free workload (--update-frac 1.0), or one
        # whose every request failed, has no rates or percentiles to report
        print(f"latency p50/p95/p99 (ms): {s.get('p50_latency_ms', 0.0):.1f} / "
              f"{s.get('p95_latency_ms', 0.0):.1f} / "
              f"{s.get('p99_latency_ms', 0.0):.1f}")
        print(f"queue wait p50/p95 (ms): {s.get('p50_queue_wait_ms', 0.0):.1f} / "
              f"{s.get('p95_queue_wait_ms', 0.0):.1f}; "
              f"mean batch {s.get('mean_batch_size', 1.0):.2f} "
              f"(peak queue depth {res.peak_queue_depth})")
        print(f"SLO {slo_ms:.0f} ms: attainment "
              f"{s.get('slo_attainment', 0.0):.3f}, goodput "
              f"{s.get('goodput_qps', 0.0):.2f} QPS")
        print("quality:", {k: round(v, 3) for k, v in res.quality.items()})
        json_doc["summary"] = s
        json_doc["quality"] = res.quality
        if executor is not None:
            rows = [st.row() for st in executor.stats]
            json_doc["stage_report"] = rows
            json_doc["scaling_events"] = controller.event_dicts()
            json_doc["knob_timeline"] = controller.knob_timeline()
            json_doc["final_knobs"] = dict(executor.knobs)
            json_doc["mean_write_batch"] = (
                sum(executor.write_batches) / len(executor.write_batches)
                if executor.write_batches else 0.0)
            print(f"elastic: {len(controller.events)} scaling events, "
                  f"final knobs {executor.knobs}")
            for row in rows:
                print(f"  {row['stage']:12s} replicas {row['replicas']:.0f}  "
                      f"occupancy {row['occupancy']:.2f}  "
                      f"queue_depth_max {row['queue_depth_max']:.0f}  "
                      f"mean batch {row['mean_batch']:.1f}")

    if args.stage_pipeline:
        # replay the workload's query stream through the pipelined stage
        # graph: stage N on batch i+1 while stage N+1 runs batch i
        reqs = [r for r in WorkloadGenerator(wcfg, corpus).requests()
                if r.op == "query"]
        golds = [gold_chunks_for(pipe.db, r.gold_doc_id, r.answer)
                 for r in reqs]
        if tracer is not None:
            for st in pipe.stages:   # staged emits per-item spans itself
                st.tracer = None
        staged = StagedExecutor(pipe, default_batch=args.batch,
                                tracer=tracer)
        monitor.add_gauges(staged.gauges())
        pipe.traces.clear()
        sres = staged.run([r.question for r in reqs],
                          ground_truth=[r.answer for r in reqs],
                          gold_chunks=golds)
        print(f"stage-pipeline: {len(reqs)} queries at "
              f"{sres.throughput_qps:.2f} QPS (wall {sres.wall_s:.2f}s)")
        for row in sres.report():
            print(f"  {row['stage']:12s} busy {row['busy_s']:.3f}s  "
                  f"idle {row['idle_s']:.3f}s  stall {row['stall_s']:.3f}s  "
                  f"occupancy {row['occupancy']:.2f}  "
                  f"mean batch {row['mean_batch']:.1f}")
        quality = evaluate_traces(sres.traces, pipe.db)
        print("stage-pipeline quality:",
              {k: round(v, 3) for k, v in quality.items()})
        json_doc["stage_pipeline"] = {
            "throughput_qps": sres.throughput_qps, "wall_s": sres.wall_s,
            "report": sres.report(), "quality": quality}

    # capability check, not attribute faith: backends without generation
    # metrics (e.g. ExtractiveLLM) still get an (empty) gen block in the
    # JSON document instead of an AttributeError
    llm_stats = getattr(pipe.llm, "stats", None)
    gen_block = (llm_stats.summary()
                 if hasattr(llm_stats, "summary") else {})
    json_doc["gen"] = gen_block
    if gen_block:
        print("gen stats:", {k: round(v, 4) for k, v in gen_block.items()})
    print("stage breakdown (s):",
          {k: round(v, 3) for k, v in pipe.breakdown().items()})
    monitor.stop()
    if tracer is not None:
        # one unified timeline: monitor samples, stage occupancy, gen
        # stats and scaling events land next to the request spans
        registry.absorb_monitor(monitor)
        if gen_block:
            registry.absorb_gen_stats(gen_block, t=tracer.now())
        if args.mode != "sync" and executor is not None:
            registry.absorb_stage_rows([st.row() for st in executor.stats],
                                       t=tracer.now())
            registry.absorb_scale_events(controller.event_dicts())
        write_trace(args.trace_out, tracer, registry)

    if args.json_out:
        json_doc["stage_breakdown"] = pipe.breakdown()
        json_doc["db"] = pipe.db.stats()
        os.makedirs(os.path.dirname(args.json_out) or ".", exist_ok=True)
        with open(args.json_out, "w") as f:
            json.dump(json_doc, f, indent=2, sort_keys=True)
        print(f"wrote {args.json_out}")
    n_failed = int(json_doc.get("summary", {}).get("n_failed", 0))
    if n_failed:
        # no faults are injected on this path: a failure is a real error
        print(f"FAILED: {n_failed} request(s) failed (tracebacks above)")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
