"""Run one benchmark cell once on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the checkout's root. The cell (``BENCHMARK.json``) names a
configuration, a traffic mix and its chips (one node a chip where it asks
for several); see ``bench/spec.py`` for where each piece lives. One run:

  1. set-up: builds the program's executor and its state from the seed,
     compiles the cell's one superstep shape (JAX's compile cache is kept
     in ``<checkout>/.jax_cache``), makes the token pool, and dispatches
     the first superstep, whose result the correctness check keeps;
  2. the window: whole supersteps until ``--seconds`` have passed (with
     ``--trace 1`` under the profiler, keeping each superstep's metrics);
     each superstep's wall time is taken, and the slowest is printed
     beside the median;
  3. reads the device's peak memory; with ``--trace 1`` reduces the trace
     (busy time, ops, idle gaps, device seconds per named scope of the
     compiled module, ``bench/scopes.py``) and the program's per-round
     counters summed over the window into the summary the per-layer
     readers (``bench/metrics``) get; frees the program's state, runs the
     plain reference over the first superstep and compares.

The last line of standard output is the result as one JSON object; the
numbers compared, with their limits, are the last lines of standard error
and the result's last key. A host whose JAX finds no TPU, or fewer chips
than the cell asks for, exits 1 and prints no result.
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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(ROOT, "bench_out")


class NoChip(Exception):
    pass


def setup_paths() -> None:
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def use_compile_cache() -> None:
    """JAX's persistent compile cache in ``<checkout>/.jax_cache``: a fixed
    path, since the path is part of what makes a later run find an entry,
    handed to the program too (``JAX_COMPILATION_CACHE_DIR``)."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # no size cap: a capped cache evicts by access-time files and drops
    # entries; the benchmark's few programs are written once per checkout
    jax.config.update("jax_compilation_cache_max_size", -1)
    # the key leaves metadata out by default: an executable that an older
    # program compiled without the named scopes would be loaded in place of
    # this one, and a traced run would read no scope
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def find_chips(chips: int):
    """The cell's first ``chips`` TPU devices."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (devices: {devices[0].platform} x "
                     f"{len(devices)}); the benchmark never falls back")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def run_cell(bench, cell_name: str, seed: int, seconds: float, trace: bool,
             *, require_chip: bool = True, t_start: float = T_START,
             log=print) -> dict:
    """One run of one cell; returns the result object. ``require_chip``
    False lets a test drive the rest of a run on the CPU."""
    import jax
    import numpy as np

    from bench import check, scopes, trace as trace_lib
    from bench.flops import model_flops_per_token
    from bench.program import (Program, first_steps, round_metrics,
                               run_window)
    from bench.reference.dfl import Reference
    from bench.reference.model import dtype_of, seed_key
    from bench.traffic import generator

    cell = bench.cell(cell_name)
    marks = [("start", t_start)]

    def mark(name):
        marks.append((name, time.perf_counter()))

    chips = int(cell["chips"])
    devices = find_chips(chips) if require_chip else jax.devices()[:chips]
    mark("devices")
    peaks = bench.peaks(devices[0].device_kind) if trace else None
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    model = config["model"]

    # -- set-up -----------------------------------------------------------
    steps = int(bench.limits(cell_name).get("steps", 1))
    program = Program(config, traffic, devices)
    mark("program")
    if program.mesh is not None:
        log("ring order (node: device id, coords): " + ", ".join(
            f"{i}: {d.id} {getattr(d, 'coords', None)}"
            for i, d in enumerate(program.mesh.devices.flat)),
            file=sys.stderr)
    pool = generator.make_pool(traffic, model["vocab_size"], seed)
    mark("pool")
    state = program.build_state(seed)
    jax.block_until_ready(state)
    mark("state")
    program.warm(state, program.batches(pool[0]))
    mark("compile")
    state, prog = first_steps(program, state, pool, steps, seed)
    mark("first_steps")
    compiles = program.executor.compile_count
    setup_s = time.perf_counter() - t_start
    log("set-up (s): " + ", ".join(
        f"{b[0]} {b[1] - a[1]:.3f}" for a, b in zip(marks, marks[1:])),
        file=sys.stderr)

    # -- window -----------------------------------------------------------
    trace_dir = os.path.join(OUT_DIR, f"trace-{cell_name}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_lib.WINDOW):
        win = run_window(program, state, pool, seconds, first=steps)
        jax.block_until_ready(win.state)
    if trace:
        jax.profiler.stop_trace()
    state, wall, done, losses = win.state, win.wall, win.done, win.losses
    slow = max(range(done), key=win.step_s.__getitem__)
    log(f"superstep wall (s): median {np.median(win.step_s):.4f}, slowest "
        f"{win.step_s[slow]:.4f} (superstep {slow} of {done})",
        file=sys.stderr)
    if program.executor.compile_count != compiles:
        raise RuntimeError(
            f"{program.executor.compile_count - compiles} superstep "
            "compile(s) inside the window")
    tokens = done * program.tokens_per_superstep()
    hbm = program.hbm_bytes()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    log(f"superstep memory_analysis: {hbm}; device peak_bytes_in_use "
        f"{peak}", file=sys.stderr)
    finite = all(math.isfinite(v) for v in losses + prog["loss"])
    leaves = [(int(np.prod(shape)),
               dtype_of(dt, model).itemsize)
              for shape, dt, _ in (program.specs[k] for k in program.names)]
    summary = None
    if trace:
        hlo = program.compiled.as_text()
        kernels = trace_lib.kernel_names(hlo)
        ops, spans = trace_lib.load(trace_dir)
        summary = trace_lib.reduce_events(ops, spans, kernels)
        summary.update(scopes.reduce_scopes(ops, spans,
                                            scopes.scope_paths(hlo), kernels))
        del hlo, ops, spans
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(scopes.tables(summary, summary.get("busy_s", 0.0)),
            file=sys.stderr)
        summary["kernels"] = {k: v for k, v in summary.get("ops", {}).items()
                              if k.endswith("kernel")}
        summary["round_metrics"] = round_metrics(win.metrics)
        log("program counters over the window: "
            + json.dumps(summary["round_metrics"]), file=sys.stderr)
        summary.update(
            wall_s=wall, tokens=tokens, rounds=done * int(
                traffic["superstep"]),
            traffic=traffic, model=model, leaves=leaves,
            chips=len(devices), peaks=peaks,
            flops_per_token=model_flops_per_token(
                model, int(traffic["seq"]),
                lambda k: bench.layer_kind("flops", k)))
    del state, win, program
    gc.collect()

    # -- correctness ------------------------------------------------------
    t_ref = time.perf_counter()
    ref = Reference(model, traffic, "float32",
                    rms_eps=config.get("reference", {}).get("rms_eps", 1e-6),
                    devices=devices)
    with jax.default_matmul_precision("highest"):
        want = ref.superstep(seed_key(seed),
                             [r for rounds in pool[:steps] for r in rounds])
    log(f"window {wall:.3f} s, {done} supersteps; reference "
        f"{time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    nums = check.numbers(prog, want)
    correct, table = check.judge(
        nums, bench.limits(cell_name).get("compared"), finite)

    # -- result -----------------------------------------------------------
    d = devices[0]
    result = {"correct": bool(correct), "attempted": done,
              "failed": 0 if finite else done, "metrics": {},
              "device": {"platform": d.platform, "kind": d.device_kind,
                         "count": len(devices), "memory_peak_bytes": peak}}
    if trace:
        result["device"]["busy_s"] = summary.get("busy_s")
        result["device"]["window_s"] = summary.get("window_s")
        for m in bench.per_layer(cell_name):
            v = bench.metric_reader(m["name"])(summary)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = trace_lib.breakdown(summary)
    else:
        e2e = {"tokens_per_s": tokens / wall,
               "hbm_peak_gb": (hbm["argument"] + hbm["output"] + hbm["temp"]
                               - hbm["alias"]) / 1e9,
               "setup_s": setup_s}
        for m in bench.end_to_end(cell_name):
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = table
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    setup_paths()
    use_compile_cache()
    try:
        from bench.spec import Bench

        bench = Bench(ROOT)
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, row in result["checks"].items():
        print(f"check {name}: {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
