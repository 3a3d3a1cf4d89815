"""Compile a cell's programs for a described TPU v5e, with no chip attached,
and print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell> [--vocab N]

Compiles the superstep the window drives (the Mosaic kernels as the chip
would compile them; a cell on several chips over the topology's first
devices, one node a chip) and the reference's local step and gossip step,
each from shapes alone, and prints ``memory_analysis()`` of each in bytes
(for the superstep, per device): arguments, outputs, temporaries, aliased,
and their total; the superstep's count of collective-permutes; and the
same sizes for the program's readings of its first supersteps
(``stats``). ``--vocab`` overrides the configuration's vocabulary, to size
a cut before a chip run.
A compile that passes here is not a chip run and gives no time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench.run import ROOT, setup_paths  # noqa: E402


def sizes(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {"argument": m.argument_size_in_bytes,
           "output": m.output_size_in_bytes,
           "temp": m.temp_size_in_bytes,
           "alias": m.alias_size_in_bytes}
    out["total"] = (out["argument"] + out["output"] + out["temp"]
                    - out["alias"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)
    setup_paths()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.program import Program
    from bench.reference.dfl import Reference
    from bench.reference.model import seed_key
    from bench.spec import Bench
    from repro.kernels import registry

    registry.on_tpu = lambda: True      # compile the kernels for Mosaic
    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    if args.vocab:
        config["model"]["vocab_size"] = args.vocab
    model = config["model"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    devices = topo.devices[:int(cell["chips"])]
    one = SingleDeviceSharding(devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    program = Program(config, traffic, devices)
    key = jax.eval_shape(lambda: seed_key(1))
    w = jax.eval_shape(program.weights, key)
    state = jax.eval_shape(program._build, w, key)
    if program.executor.state_sharding is None:
        state = on_chip(state)
    else:                       # one node a chip, on the cell's mesh
        state = jax.tree_util.tree_map(
            lambda sh, sub: jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sh), sub),
            program.executor.state_sharding, state)
    k, t1, n = traffic["superstep"], traffic["tau1"], traffic["nodes"]
    b, s = traffic["batch"], traffic["seq"]
    # batches go where the superstep wants them, as ``Program.warm`` leaves
    # them, on a mesh; a one-chip superstep is pinned to the chip
    tok = jax.ShapeDtypeStruct(
        (k, t1, n, b, s), jnp.int32,
        sharding=one if program.executor.state_sharding is None else None)
    batches = {"tokens": tok, "labels": tok}
    taus = np.zeros((k, program.executor.row_width), np.int32)
    compiled = program.executor.lower_superstep(state, batches,
                                                taus).compile()
    report = {"superstep": sizes(compiled),
              "collective_permutes": compiled.as_text().count(
                  "collective-permute-start(")}
    w0 = jax.eval_shape(program.weights, key)       # where stats take it
    if program.mesh is None:
        w0 = on_chip(w0)
    report["stats"] = sizes(program._stats.lower(
        state.params, state.hat_params, w0).compile())

    ref = Reference(model, traffic, "float32")
    wr = on_chip(w)
    row = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one)
    with jax.default_matmul_precision("highest"):
        report["reference_update"] = sizes(
            ref._update.lower(wr, ref.optim.init(wr), row, row,
                              jax.ShapeDtypeStruct((s,), jnp.int32,
                                                   sharding=one)).compile())
        hats = [wr] * n if ref.compress is not None else None
        report["reference_gossip"] = sizes(
            ref._gossip.lower([wr] * n, hats).compile())
    stored = sum(int(np.prod(v.shape)) * jnp.dtype(v.dtype).itemsize
                 for v in jax.tree_util.tree_leaves(w))
    report["weights_one_node"] = stored
    print(json.dumps({"workload": args.workload,
                      "vocab": model["vocab_size"], **report}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
