"""What the correctness tests of each small cell share: ``correct``
separates sound runs from broken ones.

Sound runs of each cell are correct. The control, the plain reference
computed with fp8 matrix products and put in the program's place, is not.
Each fault a training cell can have, planted in the program underneath an
otherwise whole run, makes the run not correct: a superstep that returns
its state unchanged, half of each batch left out of the loss, and the
gossip exchange between nodes (between chips, on the sparse engine) left
out."""
import jax
import jax.numpy as jnp

from bench import check
from bench.reference.dfl import Reference
from bench.reference.model import seed_key
from bench.run import run_cell
from bench.traffic import generator

SEED = 2**31 + 11


def _quiet(*args, **kwargs):
    pass


def _run(bench, cell):
    return run_cell(bench, cell, SEED, 0.05, False, require_chip=False,
                    log=_quiet)


def control_is_not_correct(tiny_bench, cell):
    c = tiny_bench.cell(cell)
    config = tiny_bench.config(c["config"])
    traffic = tiny_bench.traffic(c["traffic"])
    pool = generator.make_pool(traffic, config["model"]["vocab_size"], SEED)
    key = seed_key(SEED)
    steps = tiny_bench.limits(cell)["steps"]
    rounds = [r for superstep in pool[:steps] for r in superstep]
    with jax.default_matmul_precision("highest"):
        want = Reference(config["model"], traffic).superstep(key, rounds)
        same = Reference(config["model"], traffic).superstep(key, rounds)
        control = Reference(config["model"], traffic,
                            "fp8").superstep(key, rounds)
    limits = tiny_bench.limits(cell)["compared"]
    assert check.judge(check.numbers(same, want), limits)[0]
    ok, table = check.judge(check.numbers(control, want), limits)
    assert not ok, table


def _unchanged(monkeypatch):
    from repro.core.executor import RoundExecutor

    real = RoundExecutor.dispatch

    def dispatch(self, state, batches, tau1, tau2):
        keep = jax.tree_util.tree_map(jnp.copy, state)
        _, metrics = real(self, state, batches, tau1, tau2)
        return keep, metrics

    monkeypatch.setattr(RoundExecutor, "dispatch", dispatch)


def _half_batch(monkeypatch):
    import repro.models

    real = repro.models.train_loss

    def train_loss(params, batch, cfg, rng=None):
        half = batch["tokens"].shape[-1] // 2
        return real(params, {k: v[..., :half] for k, v in batch.items()},
                    cfg, rng)

    monkeypatch.setattr(repro.models, "train_loss", train_loss)


def _no_gossip(monkeypatch):
    # nodes stacked on one device mix densely; one node a device exchanges
    # with its ring neighbours by collective-permute
    from repro.core import mixing

    monkeypatch.setattr(mixing, "mix_dense",
                        lambda tree, topology, edge_mask=None: tree)
    monkeypatch.setattr(mixing, "mix_ppermute_shifts",
                        lambda params, *args, **kwargs: params)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "no_gossip": _no_gossip}


def planted_fault_is_not_correct(tiny_bench, monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch)
    r = _run(tiny_bench, cell)
    assert r["correct"] is False, r["checks"]


def sound_run_is_correct(tiny_bench, cell):
    r = _run(tiny_bench, cell)
    assert r["correct"] is True, r["checks"]
