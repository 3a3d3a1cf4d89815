"""Plain reference of one DFL / C-DFL superstep (paper Algorithms 1, 2).

Each of the superstep's rounds runs tau1 local optimizer steps on every
node, then tau2 gossip steps X <- X C, or, with a compressor, tau2 CHOCO-G
steps (Koloskova et al. 2019, arXiv:1902.00340):

    x <- x + gamma (Y C - y)
    q  = Q(x - y)             (per leaf)
    y <- y + q

The traffic mix names the parts, each a file of its own beside this one,
found by name as the layer kinds are: the mixing matrix C,
``topology/<topology>.py`` (``matrix(n)``); the compressor Q,
``compress/<compressor name>.py`` (``compress(diff, **kwargs)``); the
optimizer, ``optim/<optimizer>.py`` (``init(w)`` and ``update(w, g,
state, traffic)``). A later mix adds its part's file and edits none.

Arithmetic is float32 (or the fp8 control, ``model.Ops``); every stored
tensor (parameters and the shared estimates y) is rounded to the dtype the
configuration states after each update, as the configuration stores it.
Each node takes its local steps on a device of its own where the cell has
several (node i on ``devices[i % len(devices)]``), one node after another
where it has one chip, so the reference fits; the gossip step runs on the
first device, with every node's tree copied there. Nothing here comes from
the program.

``faults`` plants the faults the correctness check must catch, for reading
them on the chip: "half_batch" (loss and gradient over the first half of
each sequence only) and "no_gossip" (the exchange between nodes left out).
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.check import sample_index
from bench.reference import model as ref_model
from bench.spec import load_module

HERE = os.path.dirname(os.path.abspath(__file__))

Tree = Dict[str, jax.Array]


def part(family: str, name: str):
    """The reference's part ``name`` of one family (``topology``,
    ``compress``, ``optim``): the file ``<family>/<name>.py`` here."""
    return load_module(os.path.join(HERE, family, f"{name}.py"),
                       f"bench_ref_{family}_{name}")


class Reference:
    """One cell's reference: its model, its traffic, one precision."""

    def __init__(self, model: Dict[str, Any], traffic: Dict[str, Any],
                 precision: str = "float32", faults: Sequence[str] = (),
                 rms_eps: float = 1e-6, devices: Optional[Sequence] = None):
        self.model, self.traffic = model, traffic
        self.faults = tuple(faults)
        self.ops = ref_model.Ops(precision, rms_eps)
        self.specs = ref_model.param_specs(model)
        self.n = int(traffic["nodes"])
        self.devices = list(devices or jax.devices()[:1])
        self.c = part("topology", traffic["topology"]).matrix(self.n)
        comp = traffic.get("compressor")
        self.compress = None
        if comp is not None:
            mod, kwargs = part("compress", comp["name"]), comp["kwargs"]
            self.compress = lambda diff: mod.compress(diff, **kwargs)
        self.optim = part("optim", traffic["optimizer"])
        self.gamma = float(traffic["gamma"])
        self.weights = jax.jit(
            lambda key: ref_model.make_weights(self.specs, model, key))
        self._update = jax.jit(self._node_update, donate_argnums=(0, 1))
        self._gossip = jax.jit(self._gossip_step, donate_argnums=(0, 1))
        self._change = jax.jit(self._change_norms)
        self._spread = jax.jit(self._spread_norms)
        self._norm = jax.jit(lambda t: {k: _norm(v) for k, v in t.items()})
        idx = {k: sample_index(int(np.prod(spec[0])))
               for k, spec in self.specs.items()}
        self._sample = jax.jit(lambda t: {
            k: v.reshape(-1)[idx[k]].astype(jnp.float32)
            for k, v in t.items()})

    def _store(self, name: str, v):
        return v.astype(ref_model.dtype_of(self.specs[name][1], self.model))

    # -- jitted pieces ----------------------------------------------------

    def _node_update(self, w: Tree, opt, tokens, labels, pos):
        """One local step of one node: w <- the optimizer's update, stored."""
        if "half_batch" in self.faults:
            half = tokens.shape[1] // 2
            tokens, labels, pos = tokens[:, :half], labels[:, :half], \
                pos[:half]
        w32 = {k: v.astype(jnp.float32) for k, v in w.items()}
        loss, g = jax.value_and_grad(ref_model.loss)(
            w32, tokens, labels, pos, self.model, self.ops)
        new, opt = self.optim.update(w32, g, opt, self.traffic)
        return ({k: self._store(k, new[k]) for k in w}, opt, loss,
                {k: _norm(g[k]) for k in w})

    def _mixed(self, trees: List[Tree], k: str, i: int):
        """Node i's mix of leaf k in float32: sum_j C[j, i] x_j."""
        if "no_gossip" in self.faults:
            return trees[i][k].astype(jnp.float32)
        return sum(float(self.c[j, i]) * trees[j][k].astype(jnp.float32)
                   for j in range(self.n))

    def _gossip_step(self, x: List[Tree], y):
        """One gossip step of every node: plain (``y`` None) or CHOCO-G."""
        if y is None:
            return [{k: self._store(k, self._mixed(x, k, i)) for k in x[i]}
                    for i in range(self.n)], None
        x_new = [dict() for _ in range(self.n)]
        y_new = [dict() for _ in range(self.n)]
        for k in x[0]:
            for i in range(self.n):
                x32, y32 = x[i][k].astype(jnp.float32), y[i][k].astype(
                    jnp.float32)
                xn = self._store(k, x32 + self.gamma
                                 * (self._mixed(y, k, i) - y32))
                diff = xn.astype(jnp.float32) - y32
                x_new[i][k] = xn
                y_new[i][k] = self._store(k, y32 + self.compress(diff))
        return x_new, y_new

    def _change_norms(self, x: Tree, w0: Tree):
        return {k: _norm(x[k].astype(jnp.float32) - w0[k].astype(jnp.float32))
                for k in x}

    def _spread_norms(self, nodes: List[Tree]):
        out = {}
        for k in nodes[0]:
            mean = sum(nd[k].astype(jnp.float32) for nd in nodes) / self.n
            out[k] = jnp.max(jnp.stack(
                [_norm(nd[k].astype(jnp.float32) - mean) for nd in nodes]))
        return out

    # -- one superstep ----------------------------------------------------

    def superstep(self, key, rounds: List[Dict[str, np.ndarray]]
                  ) -> Dict[str, Any]:
        """Run one superstep from the seeded weights over ``rounds`` (the
        per-round [tau1, nodes, batch, seq] token dicts the program got)
        and read what the check compares: the loss of each round (mean
        over nodes and local steps), per node and leaf the norm of the
        parameters' change and of the shared estimates, per leaf the
        nodes' largest distance from their mean, and per leaf the norm of
        node 0's first gradient."""
        home = [self.devices[i % len(self.devices)] for i in range(self.n)]
        first = self.devices[0]
        w0 = self.weights(key)
        nodes = [jax.device_put(w0, d, may_alias=False) for d in home]
        del w0
        opts = [self.optim.init(nd) for nd in nodes]
        hats = None
        if self.compress is not None:
            hats = [{k: jnp.zeros(v.shape, v.dtype, device=first)
                     for k, v in nd.items()} for nd in nodes]
        losses, grad0 = [], None
        seq = rounds[0]["tokens"].shape[-1]
        pos = [jax.device_put(np.arange(seq, dtype=np.int32), d)
               for d in home]
        for rb in rounds:
            toks, labs = rb["tokens"], rb["labels"]
            per_round = []
            # each node back on its device before any steps, so that the
            # first holds one node's copy and one step's temporaries
            nodes = [jax.device_put(nd, d) for nd, d in zip(nodes, home)]
            for t in range(toks.shape[0]):
                # dispatched node after node without a wait, so that
                # nodes on devices of their own step at the same time
                for i in range(self.n):
                    nodes[i], opts[i], loss, gn = self._update(
                        nodes[i], opts[i],
                        jax.device_put(toks[t, i], home[i]),
                        jax.device_put(labs[t, i], home[i]), pos[i])
                    per_round.append(loss)
                    if grad0 is None:
                        grad0 = gn
            losses.append(float(np.mean([float(v) for v in per_round])))
            for _ in range(int(self.traffic["tau2"])):
                nodes, hats = self._gossip(
                    [jax.device_put(nd, first) for nd in nodes], hats)
        nodes = [jax.device_put(nd, first) for nd in nodes]
        grad0 = _host(grad0)
        w0 = jax.device_put(self.weights(key), first)
        out = {"loss": losses,
               "change": [_host(self._change(nd, w0)) for nd in nodes],
               "spread": _host(self._spread(nodes)),
               "grad0": grad0,
               "sample": [jax.device_get(self._sample(nd)) for nd in nodes],
               "sample0": jax.device_get(self._sample(w0))}
        del w0
        if hats is not None:
            out["hat"] = [_host(self._norm(h)) for h in hats]
            out["hat_sample"] = [jax.device_get(self._sample(h))
                                 for h in hats]
        return out


def _norm(v):
    return jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))


def _host(tree) -> Dict[str, float]:
    return {k: float(v) for k, v in jax.device_get(tree).items()}
