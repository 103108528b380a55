#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is the file that entry names, its traffic mix is
``bench/traffic/<traffic>.json``, whose ``runner`` key names the module in
``bench/runners/`` that drives the program, and each per-layer metric is
read by ``bench/metrics/<metric name>.py``.  A later cell adds files and a
``BENCHMARK.json`` entry; nothing here needs an edit.

A run loads, warms up (set-up), measures for ``--seconds`` (with
``--trace 1`` a traced window of its own), reads the device's peak memory,
frees the program's state, checks what the window produced against the
plain reference, and prints one JSON line last on standard output.  It
exits non-zero, printing no result, when JAX sees no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(SystemExit):
    pass


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """Everything ``BENCHMARK.json`` and the cell's files say about it."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(metric):
        return "workloads" not in metric or name in metric["workloads"]

    limits = json.loads(
        (root / "bench" / "limits" / f"{name}.json").read_text())["limits"]
    return {"root": root, "cell": cell, "config": config,
            "traffic": traffic, "limits": limits,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def start_jax(chips: int) -> list:
    """Turn on the compile cache inside the checkout and return the
    cell's devices; leave with ``NoChip`` when there is no TPU or too few
    chips."""
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU, but JAX's platform is "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts XLA compilations (JAX's backend-compile event) from now."""
    _count = 0
    _registered = False

    def __init__(self):
        import jax
        if not CompileCounter._registered:
            def listener(event, duration_secs, **_):
                if event == "/jax/core/compile/backend_compile_duration":
                    CompileCounter._count += 1
            jax.monitoring.register_event_duration_secs_listener(listener)
            CompileCounter._registered = True
        self.start = CompileCounter._count

    @property
    def count(self) -> int:
        return CompileCounter._count - self.start


def span(name: str):
    """A host span on the profiler's clock around one call into the
    program; the trace reduction names idle gaps after it."""
    import jax
    from tracereduce import SPAN_PREFIX
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def per_layer_values(spec: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell, read by
    ``bench/metrics/<name>.py``; a reader that finds nothing to read
    returns None and its metric is left out."""
    out = {}
    for m in spec["per_layer"]:
        reader = load_module(spec["root"] / "bench" / "metrics"
                             / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, traced: bool, *,
             devices=None, spec=None, control: bool = False,
             t_start: float = T_START):
    """One run of one cell; returns the result dict (the last line).
    ``devices`` skips the look for a chip and ``spec`` replaces what
    ``load_cell`` reads (both for tests on the CPU).  ``control`` judges
    the control (the reference in bfloat16 in the program's place) instead
    of the program (``bench/calibrate.py``)."""
    spec = spec or load_cell(name)
    cell = spec["cell"]
    if devices is None:
        devices = start_jax(cell["chips"])
    import jax
    import peaks as peak_table
    from checks import judge
    from tracereduce import Trace

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices())}
    bench = spec["root"] / "bench"
    mod = load_module(bench / "runners" / f"{spec['traffic']['runner']}.py")
    runner = mod.Cell(spec["config"], spec["traffic"], seed, devices)
    runner.setup()
    setup_s = time.perf_counter() - t_start

    tr = None
    window_s = seconds
    if traced:
        window_s = min(seconds, spec["traffic"].get("trace_seconds", seconds))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans only, no per-call trace
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    compiles = CompileCounter()
    try:
        stats = runner.window(window_s, span)
    finally:
        if traced:
            jax.profiler.stop_trace()
    stats["compiles_in_window"] = compiles.count
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in devices]
    device["memory_peak_bytes"] = max(mem)
    if traced:
        tr = Trace.from_dir(str(TRACE_DIR))
        ids = [d.id for d in devices]
        used = tr.device_ids(ids)
        device["busy_s"] = sum(tr.busy_s(d) for d in used) / len(used)
        device["window_s"] = tr.window_s()
    ctx = {"trace": tr, "window": stats, "config": spec["config"],
           "traffic": spec["traffic"],
           "peaks": peak_table.peaks(device["kind"]) if traced else None,
           "devices": [d.id for d in devices], "memory_peak": mem}
    if traced:
        metrics = per_layer_values(spec, ctx)
    else:
        metrics = {}
        values = dict(runner.end_to_end(stats), setup_s=setup_s)
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    breakdown = None
    if tr is not None:
        used = tr.device_ids([d.id for d in devices])
        breakdown = {"device_ops": tr.top_ops(used),
                     "idle_gaps": tr.idle_gaps(used[0])}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    runner.release()
    gc.collect()
    try:
        readings = runner.control() if control else runner.check()
    except Exception:               # a check that cannot run is a failure
        traceback.print_exc()
        readings = []
    checks = judge(readings, spec["limits"])
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    out = {"correct": correct, "attempted": stats["attempted"],
           "failed": stats["failed"], "metrics": metrics, "device": device,
           "compiles_in_window": stats["compiles_in_window"]}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["readings"] = readings
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(e, file=sys.stderr)
        return 3
    with contextlib.suppress(BrokenPipeError):
        for k, c in out["checks"].items():
            print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
                  file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
