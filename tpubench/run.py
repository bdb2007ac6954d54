"""``python -m tpubench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell, one JSON object on the last line.

One process holds the cell's chips: it draws the weights from the seed,
builds the engine and the HTTP daemon the way ``tpushare-serve`` does,
checks the engine's logits against the reference of the configuration's
family (``tpubench/families/``), starts the
load generator as a child that never touches JAX, and reduces what came
back. No chip, too few chips, or a device that is not in the peak
table: a non-zero exit and no result. ``--rehearse`` (a flag of this
harness, not of the program) runs the same path at a toy width on
whatever backend there is; its line says ``platform: cpu`` and is never
a measurement.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()        # before the heavy imports: set-up's zero

import argparse                     # noqa: E402
import dataclasses                  # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import subprocess                   # noqa: E402
import sys                          # noqa: E402
import threading                    # noqa: E402
from typing import Any, Dict, List, Optional    # noqa: E402

from tpubench import metrics, spec  # noqa: E402

TRACE_SLICE_S = 3.0


def log(msg: str) -> None:
    print(f"[tpubench +{time.monotonic() - T_PROCESS:6.1f}s] {msg}",
          flush=True)


@dataclasses.dataclass
class Context:
    """What one run gathered; the readers' only argument."""
    cell: spec.Cell
    window_s: float
    client: Dict[str, Any]
    stats_before: Dict[str, Any]
    stats_after: Dict[str, Any]
    stats_samples: List[Dict[str, Any]]
    device: Dict[str, Any]
    peaks: Optional[Dict[str, Any]]
    trace: Optional[Dict[str, Any]]


class Watch(threading.Thread):
    """The parent's only work during the window: /stats at its start and
    end, and in a traced run a sample a second and the profiler's slice
    in the middle."""

    def __init__(self, engine, t0: float, window_s: float,
                 trace_dir: Optional[str]):
        super().__init__(daemon=True)
        self.engine, self.t0, self.window_s = engine, t0, window_s
        self.trace_dir = trace_dir
        self.before: Dict[str, Any] = {}
        self.after: Dict[str, Any] = {}
        self.samples: List[Dict[str, Any]] = []
        self.error: Optional[str] = None

    def _sleep_until(self, t: float) -> None:
        d = t - time.monotonic()
        if d > 0:
            time.sleep(d)

    def run(self) -> None:
        try:
            self._sleep_until(self.t0)
            self.before = self.engine.stats()
            end = self.t0 + self.window_s
            if self.trace_dir:
                import jax
                t_on = self.t0 + (self.window_s - TRACE_SLICE_S) / 2
                t_off = t_on + TRACE_SLICE_S
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0    # spans, not every call
                opts.host_tracer_level = 2
                opts.enable_hlo_proto = False
                tracing = traced = False
                k = 1
                while self.t0 + k < end:
                    self._sleep_until(self.t0 + k)
                    self.samples.append(self.engine.stats())
                    now = time.monotonic()
                    if not traced and not tracing and now >= t_on:
                        jax.profiler.start_trace(self.trace_dir,
                                                 profiler_options=opts)
                        tracing = True
                    elif tracing and now >= t_off:
                        jax.profiler.stop_trace()
                        tracing, traced = False, True
                    k += 1
                if tracing:
                    jax.profiler.stop_trace()
            self._sleep_until(end)
            self.after = self.engine.stats()
        except Exception as e:              # reported, and fails the run
            self.error = f"{type(e).__name__}: {e}"


def built_in_window(compiles: List[tuple], t0: float, seconds: float
                    ) -> Dict[str, int]:
    """Programs compiled, or loaded from the persistent cache, inside
    [t0, t0 + seconds), by name: each is a shape the warm-up missed."""
    out: Dict[str, int] = {}
    for t, _, name in compiles:
        if t0 <= t < t0 + seconds:
            out[name] = out.get(name, 0) + 1
    return out


def device_report(devices) -> Dict[str, Any]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


def run_window(schedule, engine, port: int, seconds: float, traffic,
               out_dir: str, trace_dir: Optional[str]):
    """One window of one schedule against the running daemon: the child
    generates the load, the Watch thread reads /stats (and traces)."""
    sched_path = os.path.join(out_dir, "schedule.json")
    with open(sched_path, "w") as f:
        json.dump(schedule, f)
    res_path = os.path.join(out_dir, "client.json")
    child = subprocess.Popen(
        [sys.executable, "-m", "tpubench.loadgen",
         "--schedule", sched_path, "--out", res_path,
         "--port", str(port), "--window-s", str(seconds),
         "--warm-s", str(traffic["warm_s"]),
         "--grace-s", str(traffic["grace_s"])],
        cwd=spec.ROOT, stdout=subprocess.PIPE, text=True)
    try:
        line = child.stdout.readline()
        if not line.startswith("T0 "):
            raise RuntimeError(f"load generator said {line!r}")
        t0 = float(line.split()[1])
        log(f"shapes warmed; window opens in {t0 - time.monotonic():.1f}s")
        watch = Watch(engine, t0, seconds, trace_dir)
        watch.start()
        rc = child.wait(timeout=seconds + traffic["warm_s"]
                        + traffic["grace_s"] + 600)
        watch.join(timeout=60)
        if rc != 0:
            raise RuntimeError(f"load generator exited {rc}")
        if watch.error or watch.is_alive():
            raise RuntimeError(f"stats/trace thread: {watch.error}")
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    with open(res_path) as f:
        return json.load(f), watch, t0


def sweep(a, cell, gen, engine, port: int, out_dir: str, vocab: int) -> int:
    """The knee, found once when the cell is defined: several fixed rates
    against one engine, a window each. A rate is sustained when nothing
    was refused, every request of the window finished, and no backlog
    was left at the window's end. Prints a row per rate, no result."""
    traffic = cell.traffic
    for i, rate in enumerate(float(x) for x in a.sweep.split(",")):
        schedule = gen.generate(
            traffic["params"], a.seed + i, vocab, window_s=a.seconds,
            warm_s=traffic["warm_s"], rate_rps=rate, engine=cell.engine)
        if i:
            schedule["shapes"] = []
        result, watch, _ = run_window(schedule, engine, port, a.seconds,
                                      traffic, out_dir, None)
        c = metrics.end_to_end(result["rows"], loop=schedule["loop"],
                               window_s=a.seconds, vocab=vocab)
        st0, st1 = watch.before, watch.after
        row = {"rate_rps": rate, "attempted": c["attempted"],
               "failed": c["failed"],
               "rejected": st1["rejected"] - st0["rejected"],
               "queue_depth_end": st1["queue_depth"],
               "active_slots_end": st1["active_slots"],
               "tick_ms": 1e3 * a.seconds / max(1, st1["work_ticks"]
                                                - st0["work_ticks"]),
               **{k: v for k, v in c["values"].items() if v is not None}}
        log("SWEEP " + json.dumps(row))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths of the same family, any backend; "
                         "never a measurement")
    ap.add_argument("--sweep", default="",
                    help="rates (requests/s, comma-separated) to try in "
                         "turn against one engine; prints a row for each "
                         "and no result: how a cell's rate is found")
    a = ap.parse_args(argv)
    cell = spec.load_cell(a.workload, rehearse=a.rehearse)
    vocab = cell.config["vocab_size"]
    traffic = cell.traffic
    out_dir = os.path.join(spec.ROOT, "tpubench_out",
                           cell.name + (".trace" if a.trace else "")
                           + (".rehearse" if a.rehearse else ""))
    os.makedirs(out_dir, exist_ok=True)

    # The schedule, from the seed; numpy only, before JAX is touched.
    gen = spec.generator(traffic["generator"])
    schedule = gen.generate(
        traffic["params"], a.seed, vocab, window_s=a.seconds,
        warm_s=traffic["warm_s"], rate_rps=cell.rate_rps, engine=cell.engine)
    log(f"cell {cell.name}: {len(schedule['main'])} main, "
        f"{len(schedule['warm'])} warm, {len(schedule['shapes'])} shape "
        f"requests; loop {schedule['loop']}, rate {cell.rate_rps}, "
        f"clients {schedule['clients']}; engine {cell.engine}")

    import jax
    if not a.rehearse or jax.default_backend() != "cpu":
        # The program's helper: JAX_COMPILATION_CACHE_DIR where it is set,
        # else the fixed <checkout>/.jax_cache/chip.
        from tpushare.utils.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        # Every entry, however small: the admit path's per-length slices
        # compile in a tenth of a second each and there are hundreds; from
        # the cache they load in milliseconds (as tests/conftest.py does).
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if not a.rehearse:
        if devices[0].platform != "tpu":
            print(f"tpubench: no accelerator (JAX platform "
                  f"{devices[0].platform!r}); no result", file=sys.stderr)
            return 4
        if len(devices) < cell.chips:
            print(f"tpubench: cell {cell.name} needs {cell.chips} chips, "
                  f"JAX sees {len(devices)}; no result", file=sys.stderr)
            return 4
    from tpubench import peaks as peak_table
    peaks = None if a.rehearse else peak_table.peaks_for(devices[0].device_kind)
    used = devices[:cell.chips]

    # Every program built or fetched from the persistent cache: (when it
    # ended, seconds it took, what). Inside the window both mean a shape
    # the warm-up did not touch.
    compiles: List[tuple] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, dur, **kw: compiles.append(
            (time.monotonic(), dur, kw.get("fun_name") or event))
        if "backend_compile" in event or "cache_retrieval" in event else None)

    from tpubench import system
    sut = system.build(cell, a.seed, log)
    engine = sut["engine"]
    httpd = None
    try:
        correct = system.check_correct(cell, sut, a.seed, log)
        system.warm(cell, engine)
        if a.trace:
            system.annotate(engine)
        from tpushare.cli.serve import serve
        # The handler's own deadline (300 s by default) must outlast the
        # set-up and the window: the background stream spans both.
        httpd = serve(engine, port=0, timeout_s=3600.0)
        port = httpd.server_address[1]
        if a.sweep:
            return sweep(a, cell, gen, engine, port, out_dir, vocab)
        result, watch, t0 = run_window(
            schedule, engine, port, a.seconds, traffic, out_dir,
            os.path.join(out_dir, "trace") if a.trace else None)
        trace_dir = watch.trace_dir
        device = device_report(used)
    finally:
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        engine.stop()

    client = metrics.end_to_end(result["rows"], loop=schedule["loop"],
                                window_s=a.seconds, vocab=vocab)
    in_window = built_in_window(compiles, t0, a.seconds)
    n_in_window = sum(in_window.values())
    setup_s = t0 - T_PROCESS
    log(f"samples {client['samples']}; attempted {client['attempted']}, "
        f"failed {client['failed']} {client['failed_examples']}")
    log(f"programs compiled or loaded: {len(compiles)} in all; inside the "
        f"window {n_in_window} {in_window}. A window that built a program "
        f"measured the compiler: its line says correct false")
    if result["ran_out_of_schedule"]:
        log("WARNING: the closed loop ran out of generated requests; "
            "raise n_main / n_docs_main in the traffic file")
    log(f"client values {client['values']}")
    st0, st1 = watch.before, watch.after
    log("stats delta " + json.dumps({
        k: st1[k] - st0[k] for k in sorted(st1)
        if isinstance(st1[k], (int, float)) and not isinstance(st1[k], bool)
        and isinstance(st0.get(k), (int, float)) and st1[k] != st0[k]}))

    red = None
    if a.trace:
        from tpubench.readers import trace as trace_reader
        path = trace_reader.find(trace_dir)
        if path:
            red = trace_reader.reduce(trace_reader.load(path))
            log(f"trace {path} ({os.path.getsize(path)} bytes): "
                + json.dumps(red))
        else:
            log("trace: no xplane file was written")
    ctx = Context(cell=cell, window_s=a.seconds, client=client,
                  stats_before=st0, stats_after=st1,
                  stats_samples=watch.samples, device=device, peaks=peaks,
                  trace=red)

    bench = spec.benchmark()
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    out: Dict[str, Any] = {}
    if a.trace:
        for name in cell.per_layer:
            lm = spec.layer_metric(name)
            v = spec.reader(lm["reader"]).read(ctx, **lm.get("args", {}))
            if v is not None:
                out[name] = {"value": v, "unit": units[name]}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
    else:
        for name in cell.end_to_end:
            v = setup_s if name == "setup_s" else client["values"][name]
            if v is None:
                raise RuntimeError(f"no sample for {name}")
            out[name] = {"value": v, "unit": units[name]}
    # ``compiled_in_window`` is for the reader of the ledger (the driver
    # ignores the key); the verdict carries it too, because a window in
    # which a shape was new timed the compiler and not the cell. (A
    # rehearsal times nothing, and its short background stream ends
    # inside its window, so it is not held to this.)
    line = {"correct": bool(correct["ok"])
            and (a.rehearse or n_in_window == 0),
            "attempted": client["attempted"], "failed": client["failed"],
            "metrics": out, "device": device,
            "compiled_in_window": n_in_window}
    if a.trace and red is not None:
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    # Every number ``correct`` rests on beside its limit, last on the line
    # and last on standard error: what the driver's record keeps of a run
    # that is not correct.
    line["compared"] = dict(system.compared(correct), compiled_in_window={
        "value": n_in_window, "limit": None if a.rehearse else 0})
    for name, c in line["compared"].items():
        print(f"tpubench compared {name}: {c['value']} (limit "
              f"{c['limit']})", file=sys.stderr, flush=True)
    with open(os.path.join(out_dir, "last_line.json"), "w") as f:
        json.dump(line, f)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
