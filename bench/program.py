"""The program under test, built as ``repro.launch.train.main`` builds it.

One ``RoundExecutor`` with fused dispatch, on the engine ``resolve_engine``
picks: a cell on one chip stacks its nodes there, a cell on several chips
holds one node a chip on a mesh of the cell's devices and must resolve to
the sparse engine; one stacked ``DFLState`` built by one jitted program in
the executor's state layout; batches stacked by ``stack_round_batches`` and
moved to the device by the same call ``train.py`` uses, built ahead on a
``HostPrefetcher`` thread. The weights are the benchmark's own seeded draw
(``bench.reference.model.make_weights``), placed into the program's
parameter tree by name; ``init_params`` gives only that tree's structure.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import check
from bench.reference import model as ref_model
from bench.traffic import generator


def model_config(config: Dict[str, Any]):
    """The program's ``ModelConfig``: the base arch's, with the
    configuration file's keys set (``dataclasses.replace``)."""
    from repro.configs import get_arch
    from repro.models import LayerSpec

    over = dict(config["model"])
    over["pattern"] = tuple(LayerSpec(**p) for p in over["pattern"])
    over["dtype"] = getattr(jnp, over["dtype"])
    cfg = dataclasses.replace(get_arch(config["arch"]).model,
                              name=config["name"] + "-bench", **over)
    for key, want in config["model"].items():
        got = getattr(cfg, key)
        if key == "pattern":
            got = [{"mixer": s.mixer, "ffn": s.ffn} for s in got]
        elif key == "dtype":
            got = jnp.dtype(got).name
        if got != want:
            raise ValueError(f"ModelConfig.{key} is {got!r}, the "
                             f"configuration says {want!r}")
    return cfg


def path_name(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return ".".join(parts)


class Program:
    """Everything the window drives, for one cell."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 devices: Optional[Sequence[Any]] = None):
        from repro.core import (DFLConfig, RoundExecutor, init_state,
                                make_compressor)
        from repro.core.substrate import make_mesh
        from repro.launch.steps import kernelize_compressor
        from repro.launch.train import make_optimizer, make_topology
        from repro.models import init_params, train_loss

        self.traffic = traffic
        self.cfg = cfg = model_config(config)
        n = self.n = int(traffic["nodes"])
        self.tau1, self.tau2 = int(traffic["tau1"]), int(traffic["tau2"])
        comp = traffic.get("compressor")
        comp = kernelize_compressor(
            make_compressor(comp["name"], **comp.get("kwargs", {}))
            if comp else None, bool(traffic["use_kernels"]))
        self.compressed = comp is not None
        opt = make_optimizer(traffic["optimizer"], float(traffic["lr"]))
        dcfg = DFLConfig(tau1=self.tau1, tau2=self.tau2,
                         topology=make_topology(traffic["topology"], n),
                         compression=comp, gamma=float(traffic["gamma"]))
        devices = list(devices or jax.devices()[:1])
        self.mesh = mesh = (
            make_mesh((len(devices),), ("nodes",), devices=devices)
            if len(devices) > 1 else None)
        self.executor = RoundExecutor(
            dcfg, lambda p, b, k: train_loss(p, b, cfg, k), opt,
            engine="auto", mesh=mesh, node_axes=("nodes",),
            use_kernels=bool(traffic["use_kernels"]), dynamic=True)
        if mesh is not None and self.executor.engine != "sparse":
            raise ValueError(
                f"a cell on {len(devices)} chips runs one node a chip on the "
                f"sparse engine; {n} nodes on a {traffic['topology']} "
                f"resolve to the {self.executor.engine} engine")
        # the seeded weights land where the state is built from them
        replicated = (jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()) if mesh is not None
            else None)

        model = dict(config["model"])
        self.specs = ref_model.param_specs(model)
        abstract, _ = init_params(cfg, jax.random.key(0), abstract=True)
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(abstract)
        self.names = [path_name(p) for p, _ in flat]
        for name, (_, leaf) in zip(self.names, flat):
            shape, dt, _init = self.specs.get(name, (None, None, None))
            want = (tuple(shape) if shape else None,
                    ref_model.dtype_of(dt, model) if dt else None)
            if want != (tuple(leaf.shape), jnp.dtype(leaf.dtype)):
                raise ValueError(f"parameter {name}: program has "
                                 f"{leaf.shape} {leaf.dtype}, the benchmark "
                                 f"weights {want}")
        if sorted(self.names) != sorted(self.specs):
            raise ValueError("the program's parameters and the benchmark's "
                             f"weights differ: {sorted(self.names)} vs "
                             f"{sorted(self.specs)}")
        self.weights = jax.jit(
            lambda key: ref_model.make_weights(self.specs, model, key),
            out_shardings=replicated)

        def build(flat_w, key):
            params = jax.tree_util.tree_unflatten(
                self.treedef, [flat_w[k] for k in self.names])
            return init_state(params, n, opt, key,
                              compressed=self.compressed)

        self._build = jax.jit(build,
                              out_shardings=self.executor.state_sharding)
        self._sample_idx = {k: check.sample_index(int(np.prod(
            self.specs[k][0]))) for k in self.names}
        self._stats = jax.jit(self._stats_fn)
        self.compiled = None

    # -- state --------------------------------------------------------------

    def build_state(self, seed: int):
        key = ref_model.seed_key(seed)
        w = self.weights(key)
        state = self._build(w, jax.random.fold_in(key, 1))
        del w
        return state

    def _stats_fn(self, params, hat, w0):
        """Per leaf, over the nodes (its leading axis): ||x_n - x_0||,
        max_n ||x_n - mean||, ||y_n|| (C-DFL) and the fixed samples, all in
        float32. Each node's part is reduced where it lies, so a state held
        one node a chip is never gathered; only the nodes' mean crosses
        chips."""
        def norms(v):       # [n, ...] -> [n]
            return jnp.sqrt(jnp.sum(jnp.square(v), axis=tuple(
                range(1, v.ndim))))

        def sample(v, k):
            return v.reshape(v.shape[0], -1)[:, self._sample_idx[k]].astype(
                jnp.float32)

        leaves = dict(zip(self.names, jax.tree_util.tree_leaves(params)))
        out = {"change": {}, "spread": {}, "sample": {}}
        for k, v in leaves.items():
            v32 = v.astype(jnp.float32)
            out["change"][k] = norms(v32 - w0[k].astype(jnp.float32)[None])
            out["spread"][k] = jnp.max(norms(v32 - jnp.mean(v32, axis=0)[
                None]))
            out["sample"][k] = sample(v, k)
        if hat is not None:
            hl = dict(zip(self.names, jax.tree_util.tree_leaves(hat)))
            out["hat"] = {k: norms(v.astype(jnp.float32))
                          for k, v in hl.items()}
            out["hat_sample"] = {k: sample(v, k) for k, v in hl.items()}
        return out

    def stats(self, state, seed: int) -> Dict[str, Any]:
        """The first supersteps' readings on the host, as ``check.numbers``
        takes them: per node a dict of norms (floats) or samples (float32
        arrays) by leaf, and the spread by leaf."""
        w0 = self.weights(ref_model.seed_key(seed))
        got = jax.device_get(self._stats(state.params, state.hat_params, w0))
        del w0
        out = {"spread": {k: float(v) for k, v in got.pop("spread").items()}}
        for name, by_leaf in got.items():
            out[name] = [{k: (float(v[i]) if np.ndim(v) == 1
                              else np.asarray(v[i]))
                          for k, v in by_leaf.items()} for i in range(self.n)]
        return out

    # -- batches and dispatch -------------------------------------------------

    def batches(self, rounds: List[Dict[str, np.ndarray]]):
        from repro.core import stack_round_batches

        with jax.profiler.TraceAnnotation("bench.transfer"):
            return stack_round_batches(rounds, self.tau1)

    def warm(self, state, batches) -> None:
        """Compile the superstep for this shape (``RoundExecutor.warmup``,
        as ``train.py`` does) and keep the compiled program, through the
        executor's audit hook ``lower_superstep`` at the same shapes (a
        cache hit), for its memory analysis and kernel names."""
        self.executor.warmup(state, batches)
        k = jax.tree_util.tree_leaves(batches)[0].shape[0]

        def spec(x):
            sharding = getattr(x, "sharding", None)
            if sharding is not None and len(sharding.device_set) == 1:
                sharding = None
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

        args = jax.tree_util.tree_map(
            spec, (self.executor.place(state), batches))
        self.compiled = self.executor.lower_superstep(
            *args, np.zeros((k, self.executor.row_width), np.int32)
        ).compile()

    def dispatch(self, state, batches):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            return self.executor.dispatch(state, batches, self.tau1,
                                          self.tau2)

    def hbm_bytes(self) -> Dict[str, int]:
        m = self.compiled.memory_analysis()
        return {"argument": int(m.argument_size_in_bytes),
                "output": int(m.output_size_in_bytes),
                "temp": int(m.temp_size_in_bytes),
                "alias": int(m.alias_size_in_bytes)}

    def tokens_per_superstep(self) -> int:
        return generator.tokens_per_superstep(self.traffic)


def first_steps(program: Program, state, pool, steps: int, seed: int):
    """Dispatch the first ``steps`` supersteps (pool rows 0..steps-1) through
    the window's own call and read what the correctness check compares: the
    loss of every round and ``Program.stats`` of the state they leave."""
    losses: List[float] = []
    for i in range(steps):
        batches = program.batches(pool[i])
        state, metrics = program.dispatch(state, batches)
        losses.extend(float(v) for v in np.asarray(metrics["loss"]))
        del batches, metrics
    prog = program.stats(state, seed)
    prog["loss"] = losses
    return state, prog


@dataclasses.dataclass
class Window:
    """What one measured window did: the state it leaves, its wall time
    (first batch's build to the end of the last superstep), the supersteps
    run, their per-round losses, each superstep's wall time on the host
    clock, and each superstep's metrics as the device returned them."""

    state: Any
    wall: float
    done: int
    losses: List[float]
    step_s: List[float]
    metrics: List[Dict[str, Any]]


def run_window(program: Program, state, pool, seconds: float,
               first: int = 0) -> Window:
    """Whole supersteps, at least two, while one more fits in ``seconds``
    at the mean pace so far, as ``train.py``'s loop runs them with
    ``--log-every 1``: the next superstep's batches are built and moved on
    a ``HostPrefetcher`` thread while the device runs, and the host waits
    for each superstep's loss. Each superstep's metrics stay on the
    device, to be read once the window has closed."""
    from repro.core import HostPrefetcher

    prefetch = HostPrefetcher()
    losses: List[float] = []
    step_s: List[float] = []
    kept: List[Dict[str, Any]] = []
    done = 0
    i = first
    t0 = last = time.perf_counter()
    prefetch.schedule(program.batches, pool[i % len(pool)], meta=i)
    try:
        while True:
            with jax.profiler.TraceAnnotation("bench.take"):
                batches, _ = prefetch.take()
            state, metrics = program.dispatch(state, batches)
            del batches
            i += 1
            prefetch.schedule(program.batches, pool[i % len(pool)], meta=i)
            with jax.profiler.TraceAnnotation("bench.wait"):
                loss = np.asarray(jax.block_until_ready(metrics["loss"]))
            losses.extend(float(v) for v in loss)
            kept.append(metrics)
            del metrics
            done += 1
            now = time.perf_counter()
            step_s.append(now - last)
            last = now
            wall = now - t0
            if done >= 2 and wall + wall / done > seconds:
                break
    finally:
        prefetch.close()
    return Window(state, wall, done, losses, step_s, kept)


def round_metrics(kept: List[Dict[str, Any]]) -> Dict[str, float]:
    """Every per-round metric of the program but the loss, summed over the
    rounds of the supersteps ``kept``: the program's counters, read once
    the window has closed."""
    out: Dict[str, float] = {}
    for metrics in jax.device_get(kept):
        for name, v in metrics.items():
            if name != "loss":
                out[name] = out.get(name, 0.0) + float(
                    np.sum(np.asarray(v, np.float64)))
    return out
