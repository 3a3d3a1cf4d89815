"""Read the two ends of each correctness limit for one cell, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3

In one process (set-up is paid once): for each of ``--seeds`` seeds, the
program's first superstep against the reference's, as ``bench/run.py``
compares them (the lower readings); then for each of ``--control-seeds``
seeds, the control (the reference computed with fp8 matrix products, put
in the program's place) and the reference with each planted fault
(``half_batch``, ``no_gossip``) against the clean reference (the upper
readings). A state left unchanged reads 1 on ``update`` by construction
and is not run. Prints one JSON line per reading and a summary last: per
number the largest program reading and the smallest control and fault
readings. Each reading also lists, per moving leaf, the worst node's
``drift`` ratio (``check.drift_leaves``). Seeds are drawn from
``--base-seed``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench.run import (ROOT, find_chips, setup_paths,  # noqa: E402
                       use_compile_cache)


def readings(bench, cell_name: str, seeds, control_seeds,
             require_chip: bool = True, emit=print):
    import jax

    from bench import check
    from bench.program import Program, first_steps
    from bench.reference.dfl import Reference
    from bench.reference.model import seed_key
    from bench.traffic import generator

    cell = bench.cell(cell_name)
    chips = int(cell["chips"])
    devices = find_chips(chips) if require_chip else jax.devices()[:chips]
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    model = config["model"]
    eps = config.get("reference", {}).get("rms_eps", 1e-6)
    rows = []

    t0 = time.perf_counter()

    def record(kind, seed, nums, got, want):
        moving = check.moving_leaves(want["grad0"])
        per_leaf = check.drift_leaves(got["sample"], want["sample"],
                                      want["sample0"], moving)
        row = {"kind": kind, "seed": seed, "numbers": nums,
               "drift_leaves": {k: max(n[k] for n in per_leaf)
                                for k in moving},
               "t": round(time.perf_counter() - t0, 3)}
        rows.append(row)
        emit(json.dumps(row), flush=True)

    ref = Reference(model, traffic, "float32", rms_eps=eps, devices=devices)
    program = Program(config, traffic, devices)
    steps = int(bench.limits(cell_name).get("steps", 1))
    for i, seed in enumerate(seeds):
        pool = generator.make_pool(traffic, model["vocab_size"], seed)
        state = program.build_state(seed)
        if i == 0:
            program.warm(state, program.batches(pool[0]))
        state, prog = first_steps(program, state, pool, steps, seed)
        del state
        gc.collect()
        with jax.default_matmul_precision("highest"):
            want = ref.superstep(seed_key(seed), _rounds(pool, steps))
        record("program", seed, check.numbers(prog, want), prog, want)
    del program
    gc.collect()
    variants = [("control", Reference(model, traffic, "fp8", rms_eps=eps,
                                      devices=devices))]
    for fault in ("half_batch", "no_gossip"):
        variants.append((fault, Reference(model, traffic, "float32",
                                          faults=(fault,), rms_eps=eps,
                                          devices=devices)))
    for seed in control_seeds:
        pool = generator.make_pool(traffic, model["vocab_size"], seed)
        with jax.default_matmul_precision("highest"):
            want = ref.superstep(seed_key(seed), _rounds(pool, steps))
            for kind, other in variants:
                got = other.superstep(seed_key(seed), _rounds(pool, steps))
                record(kind, seed, check.numbers(got, want), got, want)
    summary = {}
    for row in rows:
        for name, v in row["numbers"].items():
            s = summary.setdefault(name, {})
            if row["kind"] == "program":
                s["program_max"] = max(s.get("program_max", 0.0), v)
            else:
                key = f"{row['kind']}_min"
                s[key] = min(s.get(key, float("inf")), v)
    return rows, summary


def _rounds(pool, steps):
    return [r for rounds in pool[:steps] for r in rounds]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--base-seed", type=int, default=2_147_483_000)
    args = ap.parse_args(argv)
    setup_paths()
    use_compile_cache()
    from bench.spec import Bench

    seeds = [args.base_seed + 7919 * i for i in range(args.seeds)]
    control = [args.base_seed + 104729 * (i + 1)
               for i in range(args.control_seeds)]
    _, summary = readings(Bench(ROOT), args.workload, seeds, control)
    print(json.dumps({"summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
