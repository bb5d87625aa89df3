"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<mix>.json``); the mix names its driver
(``chipbench/drivers/<driver>.py``), and every per-layer metric is read by
``chipbench/metrics/<metric>.py``.  So a cell, a configuration, a mix, a
driver or a metric is added as files and entries, with no edit here.

The run sets up (plans, compiles, makes its payload on the device from
``--seed`` and runs every program once), measures for ``--seconds``,
and then checks what the timed programs produced against a plain
reference.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` also traces a few seconds after the window and reports
its per-layer metrics.  The last line of standard output is one JSON
object.  Without a TPU, with fewer chips than the cell asks for, or with
a slab data plane other than ``"pallas"``, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import tempfile
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:          # run as a script: ``chipbench`` is ROOT's
    sys.path.insert(0, ROOT)

from chipbench import routing  # noqa: E402

TRACE_SECONDS = 2.0


def process_age_s() -> float:
    """Seconds since this process started."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T0


_T0 = time.perf_counter()


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's entry, configuration, traffic and metric entries."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; one of {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return {"cell": cell,
            "config": _json(os.path.join(ROOT, config["file"])),
            "traffic": _json(os.path.join(HERE, "traffic",
                                          cell["traffic"] + ".json")),
            "end_to_end": e2e, "per_layer": layer}


def driver_module(name: str):
    return importlib.import_module(f"chipbench.drivers.{name}")


def metric_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"),
        os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache() -> None:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``), keeping every program."""
    import jax

    from repro.compile_cache import enable_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs that JAX had to compile or load from the persistent
    cache: every miss of its in-memory cache.  None may fall in the
    window."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def trace_segment(fn, host_names) -> dict:
    """Run ``fn`` under the profiler and reduce its trace to the window
    of the host span ``chipbench_segment``."""
    import jax

    from chipbench import trace_reduce

    with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as d:
        jax.profiler.start_trace(d)
        try:
            with jax.profiler.TraceAnnotation("chipbench_segment"):
                fn()
        finally:
            jax.profiler.stop_trace()
        device, host = trace_reduce.read_xplane(
            trace_reduce.find_xplane(d),
            ("chipbench_segment",) + tuple(host_names))
    seg = [(s, e) for n, s, e in host if n == "chipbench_segment"]
    if len(seg) != 1 or not device:
        raise RuntimeError(f"trace holds {len(seg)} segment spans and "
                           f"{len(device)} device planes")
    host = [h for h in host if h[0] != "chipbench_segment"]
    return trace_reduce.reduce(device, seg[0], host)


def run_cell(spec: dict, devices, seed: int, seconds: float, trace: bool,
             *, control: str | None = None, log=print) -> dict:
    """Set up, measure, trace and check one cell on ``devices``; return the
    result line as a dict.  ``control`` computes the reference in that
    type instead of the cell's: the run has to come out not correct."""
    from chipbench import roofline, trace_reduce

    cfg, traffic = spec["config"], spec["traffic"]
    drv_mod = driver_module(traffic["driver"])
    drv = drv_mod.Driver(cfg, traffic, devices, seed, trace=trace)
    compiles = CompileCounter()
    setup_s = process_age_s()
    log(f"set-up: {setup_s:.3f} s", file=sys.stderr)

    times = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        ts = time.perf_counter()
        drv.step(len(times))
        times.append(time.perf_counter() - ts)
    window_s = time.perf_counter() - t0
    in_window = compiles.n
    log(f"window: {len(times)} units in {window_s:.4f} s, "
        f"{in_window} programs compiled or loaded inside it",
        file=sys.stderr)
    ctx = SimpleNamespace(layer=drv.layer_context(), traces={},
                          peaks=None, window_s=window_s, times=times)

    result_device = {"platform": devices[0].platform,
                     "kind": devices[0].device_kind, "count": len(devices)}
    breakdown = None
    if trace:
        ctx.peaks = roofline.peaks(devices[0].device_kind)
        units = int(TRACE_SECONDS * len(times) / window_s)
        for label, (fn, draws) in drv.segments(units).items():
            red = trace_segment(fn, ("plan", "launch", "wait"))
            ctx.traces[label] = {"reduction": red, "draws": draws}
        lib = ctx.traces["lib"]["reduction"]
        result_device["busy_s"] = trace_reduce.mean_over_chips(lib["busy_s"])
        result_device["window_s"] = lib["window_s"]
        breakdown = {"device_ops": trace_reduce.top(lib["ops_s"]),
                     "idle_gaps": trace_reduce.top(lib["idle_s"])}

    result_device["memory_peak_bytes"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices)

    if trace:
        metrics = {}
        for m in spec["per_layer"]:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = drv.end_to_end(window_s, times)
        values["setup_s"] = setup_s
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}

    drv.free()
    t_check = time.perf_counter()
    verdict = drv.check(precision=control)
    log(f"check: {time.perf_counter() - t_check:.3f} s, "
        f"{compiles.n - in_window} programs compiled or loaded",
        file=sys.stderr)
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in verdict["checks"].items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    out = {"correct": correct, "attempted": len(times),
           "failed": verdict["wrong"], "metrics": metrics,
           "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def prepare_program(dataplane: str = "pallas") -> None:
    """Import the program from this checkout and select its data plane."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"no program at {src}: run from a checkout of the "
                         "repository")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.core import jax_collectives as jc

    jc.set_dataplane(dataplane)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None, choices=sorted(routing.LOWER),
                    help="compute the reference in this type (the control "
                    "run, which has to come out not correct)")
    args = ap.parse_args(argv)

    spec = load_cell(args.workload)
    prepare_program()
    import jax

    from repro.core import jax_collectives as jc

    enable_compile_cache()
    if jc.dataplane() != "pallas":
        print(f"data plane is {jc.dataplane()!r}, not 'pallas'",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform!r} devices",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"{args.workload} needs {chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    out = run_cell(spec, devices[:chips], args.seed, args.seconds,
                   bool(args.trace), control=args.control)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
