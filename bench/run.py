"""Run one benchmark cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``): weights from the seed on the device, the
policy, a warm-up trace through the engine that runs every executable shape
the window can use, then the cell's own lead-in (open loop) or fill (closed
loop). The window then runs for ``--seconds`` on the engine clock; nothing
compiles inside it (the count is printed). After the window: the device's
peak memory is read, the program's state is freed, and a float32 reference
rebuilt from the seed checks a seeded sample of the served requests.

``--trace 1`` records a device trace of the window and reports the cell's
per-layer metrics; ``--trace 0`` reports its end-to-end metrics. The last
line of standard output is one JSON object; the numbers compared for
``correct`` are printed with their limits as the last lines of standard
error and under the result's last key.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import probe, spec, traffic  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
KERNELS = ("rap_paged_decode_attention",)
SPAN_PREFIX = "bench."


def log(record: Dict[str, Any]) -> None:
    print(json.dumps(record), flush=True)


class Compiles:
    """Counts lowerings (each new executable this process builds, from
    the persistent cache or not) and persistent-cache misses."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.lowerings = 0
        self.misses = 0
        self._lower_event = dispatch.JAXPR_TO_MLIR_MODULE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if event == self._lower_event:
            self.lowerings += 1

    def _ev(self, event, **kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self):
        return self.lowerings, self.misses


# JAX's monitoring listeners cannot be removed, so one counter serves every
# run a process makes
_COMPILES: Optional[Compiles] = None


class Window:
    """The ``on_tick`` hook that opens, traces and closes the window.

    Closed loop (``backlog``): opens at the first tick at which every
    request that stands for the loop's steady state (``in_flight``) has
    finished its chunked prefill, or, without such requests, at the first
    tick with requests decoding and none in prefill (the fill is over); at
    close every request still in flight is cancelled, which is not a
    failure. With ``trace`` the profiler runs between the marks
    ``trace_open`` and ``trace_close``, taken just inside the window, and
    the device readers count the work between those two. Open loop
    (``poisson``): opens at ``lead_in_s`` on the engine clock; after the
    close the run continues until every request scheduled in the window
    has ended, or ``drain_cap_s`` has passed, when the rest is cancelled
    (a window request cancelled then has failed)."""

    def __init__(self, mix, seconds: float, arrivals: Dict[str, float],
                 trace: bool, compiles: Compiles, executor):
        arr = mix["arrivals"]
        self.process = arr["process"]
        self.lead_in = float(arr.get("lead_in_s", 0.0))
        self.drain_cap = float(arr.get("drain_cap_s", 0.0))
        self.seconds = float(seconds)
        self.arrivals = arrivals
        self.rids = list(arrivals)
        self.live = traffic.in_flight_rids(mix)
        self.trace = trace
        self.compiles = compiles
        self.executor = executor
        self.state = "before"
        self.marks: Dict[str, Dict[str, Any]] = {}

    def _mark(self, engine, name: str) -> None:
        self.marks[name] = {
            "t": probe.now(engine), "wall": time.perf_counter(),
            "launch_s": float(self.executor.launch_s),
            "progress": probe.progress(engine),
            "compiles": self.compiles.snap()}

    def __call__(self, engine) -> None:
        t = probe.now(engine)
        if self.state == "before":
            if self.process == "backlog" and self.live:
                prog = probe.progress(engine)
                ready = all(prog.get(r, (0, 0))[0] == -1 for r in self.live)
            elif self.process == "backlog":
                running, prefilling = probe.counts(engine)
                ready = running > 0 and prefilling == 0
            else:
                ready = t >= self.lead_in
            if ready:
                self._mark(engine, "open")
                self.state = "open"
                if self.trace:
                    self._start_trace()
                    self._mark(engine, "trace_open")
            return
        if self.state == "open":
            if t >= self.marks["open"]["t"] + self.seconds:
                if self.trace:
                    self._mark(engine, "trace_close")
                    self._stop_trace()
                self._mark(engine, "close")
                if self.process == "backlog":
                    self._cancel_all(engine)
                    self.state = "done"
                else:
                    self.state = "draining"
            return
        if self.state == "draining":
            t1 = self.marks["close"]["t"]
            done = probe.finished(engine)
            window = self.window_rids()
            if all(r in done for r in window) or t >= t1 + self.drain_cap:
                self._mark(engine, "drained")
                self._cancel_all(engine)
                self.state = "done"

    def window_rids(self) -> List[str]:
        """Closed loop: the requests served in the window (admitted or
        delivering tokens in it). Open loop: the requests scheduled to
        arrive in it."""
        po = self.marks["open"]["progress"]
        if self.process == "backlog":
            pc = self.marks["close"]["progress"]
            return [rid for rid in pc if pc[rid] != po.get(rid, (0, 0))]
        t0, t1 = self.marks["open"]["t"], self.marks["close"]["t"]
        return [rid for rid, a in self.arrivals.items() if t0 <= a < t1]

    def _cancel_all(self, engine) -> None:
        for rid in self.rids:
            engine.cancel(rid)

    def _start_trace(self) -> None:
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)

    def _stop_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()


def annotate(obj, names: List[str]) -> None:
    """Wrap ``obj``'s methods in host spans named ``bench.<method>`` so the
    trace can name what the host did in each device idle gap."""
    import jax

    for name in names:
        fn = getattr(obj, name)

        def wrapped(*a, __fn=fn, __n=SPAN_PREFIX + name, **kw):
            with jax.profiler.TraceAnnotation(__n):
                return __fn(*a, **kw)
        setattr(obj, name, wrapped)


class Context:
    """What a metric reader sees (``bench/metrics/<name>.py``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _request_work(ctx, a: str, b: str):
    """(decode tokens, Σ decode context, prefill tokens, Σ prefill
    context) delivered between marks ``a`` and ``b``."""
    from bench import flops
    pa, pb = ctx.marks[a]["progress"], ctx.marks[b]["progress"]
    dec = ctx_dec = pre = ctx_pre = 0.0
    for rid, (pre_b, tok_b) in pb.items():
        S = ctx.prompt_len[rid]
        pre_a, tok_a = pa.get(rid, (0, 0))
        pre_a = S if pre_a < 0 else pre_a
        pre_b = S if pre_b < 0 else pre_b
        dec += max(tok_b - max(tok_a, 1), 0)
        ctx_dec += flops.sum_ctx(S, tok_a, tok_b)
        pre += max(pre_b - pre_a, 0)
        ctx_pre += flops.sum_prefill_ctx(pre_a, pre_b)
    return dec, ctx_dec, pre, ctx_pre


def delivered(ctx, a: str, b: str) -> int:
    pa, pb = ctx.marks[a]["progress"], ctx.marks[b]["progress"]
    return sum(tok_b - pa.get(rid, (0, 0))[1]
               for rid, (_, tok_b) in pb.items())


def check_devices(chips: int):
    """The accelerator the cell needs, or an error: no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX sees "
                         f"{devices[0].platform}); the benchmark runs only "
                         f"on the chip")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             budget: Optional[float] = None, kv_dtype: Optional[str] = None,
             peaks: Optional[Dict[str, Any]] = None,
             control: bool = False) -> Dict[str, Any]:
    """One run of ``cell``; returns the result record (the last line).
    ``kv_dtype`` overrides the mix's KV precision; ``control`` also reads
    the fp8 control on the same sample (``bench/control.py``)."""
    import jax
    from repro.runtime import EngineRequest
    from repro.runtime.engine import enable_compile_cache
    from bench import check, serve
    global _COMPILES
    cache_dir = enable_compile_cache()
    if _COMPILES is None:
        _COMPILES = Compiles()
    compiles = _COMPILES
    dev = jax.devices()[0]
    peaks = peaks or _peaks(dev.device_kind)
    e = cell.mix["engine"]
    slots = int(e["slots"])
    if budget is None:
        limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
        if limit <= 0:
            raise SystemExit("bench: the device reports no bytes_limit")
        budget = serve.device_budget(e, limit)
    log({"phase": "start", "workload": cell.name, "seed": seed,
         "seconds": seconds, "trace": int(trace), "device_kind":
         dev.device_kind, "budget_bytes": budget,
         "compile_cache_dir": cache_dir})
    split = {"start_s": time.perf_counter() - T_START}
    t = time.perf_counter()
    engine, executor, model, controller = serve.build(cell, seed, budget,
                                                      kv_dtype=kv_dtype)
    split["init_s"] = time.perf_counter() - t
    t = time.perf_counter()
    serve.warm_policy(controller)
    split["policy_s"] = time.perf_counter() - t
    warm = traffic.warmup_requests(e, executor.decode_buckets,
                                   engine.cfg.decode_horizon,
                                   model.cfg.vocab_size)
    wrep = engine.run([EngineRequest(rid=r.rid, prompt=r.prompt,
                                     arrival_t=r.arrival_s,
                                     max_new=r.max_new) for r in warm])
    bad = [r.rid for r in wrep.results if r.status != "done"]
    if bad:
        raise RuntimeError(f"warm-up requests not served: {bad[:5]}")
    t_upd = time.perf_counter()
    serve.warm_updates(executor, slots)
    serve.release_pool(engine, executor)
    split["warm_updates_s"] = time.perf_counter() - t_upd
    split["warmup_s"] = time.perf_counter() - t
    reqs = traffic.generate(cell.mix, seed, seconds, model.cfg.vocab_size)
    if trace:
        annotate(executor, ["decode_launch", "decode_finish",
                            "prefill_step"])
        annotate(engine.policy, ["observe"])
        annotate(engine.scheduler, ["schedule"])
    win = Window(cell.mix, seconds, {r.rid: r.arrival_s for r in reqs},
                 trace, compiles, executor)
    t_run = time.perf_counter()
    rep = engine.run([EngineRequest(rid=r.rid, prompt=r.prompt,
                                    arrival_t=r.arrival_s, max_new=r.max_new)
                      for r in reqs], on_tick=win)
    if "close" not in win.marks:
        raise RuntimeError("the run ended before the window closed: too "
                           "few requests for the window")
    mem_peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))
    split["fill_s"] = win.marks["open"]["wall"] - t_run
    setup_s = win.marks["open"]["wall"] - T_START
    results = {r.rid: r for r in rep.results}
    t_open, t_close = win.marks["open"]["t"], win.marks["close"]["t"]
    window_rids = win.window_rids()
    c_open, c_close = (win.marks["open"]["compiles"],
                       win.marks["close"]["compiles"])
    inside = lambda t: t_open <= t < t_close  # noqa: E731
    window_line = {
        "phase": "window", "engine_s": t_close - t_open,
        "wall_s": win.marks["close"]["wall"] - win.marks["open"]["wall"],
        "requests_in_window": len(window_rids),
        "finished_in_window": sum(1 for r in rep.results if r.status == "done"
                                  and inside(r.finished_t)),
        "admitted_in_window": sum(1 for r in rep.results
                                  if inside(r.admitted_t)),
        "compiles_in_window": c_close[0] - c_open[0],
        "cache_misses_in_window": c_close[1] - c_open[1],
        "setup_split_s": split, "setup_s": setup_s,
        "memory_peak_bytes": mem_peak,
        "engine_compile_events": rep.compile_events,
        "preempted": rep.preempted_count, "spilled_mb": rep.spilled_mb}
    log(window_line)
    ctx = Context(cell=cell, config=cell.config, mix=cell.mix, report=rep,
                  results=results, marks=win.marks, window_rids=window_rids,
                  prompt_len={r.rid: r.prompt.shape[1] for r in reqs},
                  setup_s=setup_s, peaks=peaks, trace=None)
    ctx.delivered = lambda a, b: delivered(ctx, a, b)
    ctx.work = lambda a, b: _request_work(ctx, a, b)
    if trace and dev.platform == "tpu":
        from bench import trace_reduce
        pd = trace_reduce.load(trace_reduce.latest_xplane(TRACE_DIR))
        ctx.trace = trace_reduce.reduce(pd, kernels=KERNELS,
                                        span_prefixes=(SPAN_PREFIX,))
        del pd
    names = cell.per_layer if trace else cell.end_to_end
    out_metrics = {}
    for m in names:
        v = spec.metric_module(m["name"]).compute(ctx)
        if v is None:
            continue
        if not math.isfinite(v):
            raise RuntimeError(f"{m['name']} is not finite: the window's "
                               f"tail holds failed requests")
        out_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    attempted = len(window_rids)
    if win.process == "backlog":
        failed = sum(1 for rid in window_rids
                     if results.get(rid) is not None
                     and results[rid].status == "rejected")
    else:
        failed = sum(1 for rid in window_rids
                     if results.get(rid) is None
                     or results[rid].status != "done")
    sample = check.sample(cell, seed, reqs, results, window_rids,
                          win.process)
    serve.release_pool(engine, executor)
    win.executor = None
    del engine, executor, model, controller, rep, wrep
    ctx.results = ctx.report = None
    gc.collect()
    verdict = check.compare(cell, seed, sample)
    for name, item in verdict["compared"].items():
        print(f"compared {name}: {item['value']} limit {item['limit']}",
              file=sys.stderr, flush=True)
    record = {"correct": verdict["correct"], "attempted": attempted,
              "failed": failed, "metrics": out_metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": mem_peak}}
    if trace and ctx.trace is not None:
        record["device"]["busy_s"] = ctx.trace["busy_s"]
        record["device"]["window_s"] = ctx.trace["window_s"]
        record["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    if control:
        cv = check.compare(cell, seed, sample, control=True)
        record["control"] = {"correct": cv["correct"],
                             "compared": cv["compared"]}
    record["compared"] = verdict["compared"]
    return record


def _peaks(kind: str) -> Dict[str, Any]:
    table = spec.load_json(os.path.join(ROOT, "bench", "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    check_devices(cell.chips)
    record = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
