"""Smoke test of the DFL trainer on a TPU, at qwen3-1.7b's published widths.

    python chip_smoke.py              # one chip: phases A, B and C
    python chip_smoke.py --chips 4    # four chips: the one-node-per-chip ring

Everything runs in this one process, through ``repro.launch.train.main``,
the entry point a user calls. The model is qwen3-1.7b's chip share
(``--no-reduced``: every published width, 8 of its 28 layers, random
weights from a seed).

One chip:
  A. plain DFL, two nodes stacked on the chip (the dense engine);
  B. C-DFL with TopK compression and ``--use-kernels`` (the TopK mask
     kernel compiled by Mosaic);
  C. every kernel registered ``mosaic=True``, compiled for the chip, against
     its ``repro.kernels.ref`` oracle on one real-width leaf (the 2048 x 6144
     bf16 MLP weight), under ``registry.parity_suite``'s rules.

Four chips (``--chips 4``): a ring of four nodes, one per chip, on the
sparse engine, plain and with ``--compression qsgd --use-kernels`` (the
fused CHOCO kernels); then the sparse engine against the dense one on the
reduced config with the same seed, whose losses must agree within
``repro.core.sharded.ENGINE_PARITY_LOSS_ATOL``.

A training phase passes when every loss and consensus value is finite, the
first round's loss is within 1.0 of ln(vocab) (random weights predict
nearly uniformly), no superstep compiled after the warmup, and the engine
is the expected one. Compile seconds, seconds per round and peak device
memory are printed as bring-up observations, not benchmark numbers.

The last line of standard output is ``{"ok": true, "device": {...}}``. A
host where JAX finds no TPU exits non-zero at once, and so does any failed
check, before that line is printed.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")

ARCH = "qwen3-1.7b"
COMMON = ["--arch", ARCH, "--topology", "ring", "--tau1", "2", "--tau2", "2",
          "--superstep", "2", "--rounds", "4", "--batch", "1"]
REAL_WIDTH = (2048, 6144)      # qwen3-1.7b d_model x d_ff


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def tpu_devices():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU found: JAX's devices are {devices[0].platform!r} "
            f"({len(devices)}); this smoke test never falls back to them")
    return devices


def peak_bytes(devices) -> int:
    return max(d.memory_stats()["peak_bytes_in_use"] for d in devices)


def train(tag: str, argv, devices, *, engine: str, vocab: int) -> dict:
    """One ``train.main`` run; checks its history and returns it."""
    from repro.launch import train as train_lib

    hist_path = os.path.join(OUT, f"{tag}.json")
    events_path = os.path.join(OUT, f"{tag}.events.jsonl")
    for path in (hist_path, events_path):
        if os.path.exists(path):
            os.remove(path)
    print(f"== {tag}: train.main {' '.join(argv)}", flush=True)
    t0 = time.perf_counter()
    train_lib.main(argv + ["--history-out", hist_path,
                           "--telemetry-out", events_path])
    wall = time.perf_counter() - t0
    gc.collect()   # the run's state must not outlive it on the device
    with open(hist_path) as f:
        h = json.load(f)
    with open(events_path) as f:
        events = [json.loads(line) for line in f]

    rounds = int(argv[argv.index("--rounds") + 1])
    check(len(h["loss"]) == rounds,
          f"{tag}: {len(h['loss'])} rounds logged, expected {rounds}")
    for key in ("loss", "consensus_sq"):
        check(all(v is not None and math.isfinite(v) for v in h[key]),
              f"{tag}: non-finite {key}: {h[key]}")
    check(abs(h["loss"][0] - math.log(vocab)) <= 1.0,
          f"{tag}: first loss {h['loss'][0]} is not within 1.0 of "
          f"ln({vocab}) = {math.log(vocab):.4f}")
    check(h["compile_count"] == h["compile_count_warmup"],
          f"{tag}: {h['compile_count'] - h['compile_count_warmup']} "
          "superstep compile(s) after the warmup")
    check(h["engine"] == engine,
          f"{tag}: ran on the {h['engine']} engine, expected {engine}")

    compile_s = sum(e.get("dur", 0.0) for e in events
                    if e["type"] == "span" and e.get("name") == "warmup")
    print(f"{tag}: losses {h['loss']} consensus {h['consensus_sq']}")
    print(f"{tag}: engine={h['engine']} state on {h['state_devices']} "
          f"device(s); superstep compile {compile_s:.1f} s; "
          f"{sum(h['round_s']) / rounds:.3f} s/round; run wall {wall:.1f} s; "
          f"peak device bytes {peak_bytes(devices)}", flush=True)
    return h


def one_chip(devices) -> None:
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.kernels import registry

    vocab = get_arch(ARCH).chip_share.model.vocab_size
    full = COMMON + ["--no-reduced", "--nodes", "2", "--seq", "4096"]
    train("a", full, devices, engine="dense", vocab=vocab)
    train("b", full + ["--compression", "top_k", "--use-kernels"], devices,
          engine="dense", vocab=vocab)

    print("== c: kernel modes and Mosaic parity", flush=True)
    for op in registry.list_ops():
        mode = registry.resolve_mode(op.name)
        note = " (plain XLA)" if mode == "fallback" else ""
        print(f"c: kernel {op.name}: {mode}{note}")
        check(mode != "interpret", f"c: {op.name} resolved to interpret")
    mosaic = [op.name for op in registry.list_ops() if op.mosaic]
    records = registry.parity_suite(shapes=(REAL_WIDTH,),
                                    dtypes=(jnp.bfloat16,), ops=mosaic,
                                    interpret=False)
    for r in records:
        print(f"c: parity {r['op']} {r['shape']} {r['dtype']}: "
              f"max_err={r['max_err']} bitwise={r['bitwise']} ok={r['ok']}")
    bad = [r["op"] for r in records if not r["ok"]]
    check(not bad, f"c: Mosaic kernels disagree with their oracles: {bad}")
    check(sorted(r["op"] for r in records) == sorted(mosaic),
          "c: not every Mosaic op was checked")
    print(f"c: peak device bytes {peak_bytes(devices)}", flush=True)


def four_chips(devices) -> None:
    from repro.configs import get_arch
    from repro.core.sharded import ENGINE_PARITY_LOSS_ATOL

    check(len(devices) == 4, f"--chips 4 needs 4 TPU devices, found "
                             f"{len(devices)}")
    arch = get_arch(ARCH)
    full = COMMON + ["--no-reduced", "--nodes", "4", "--seq", "4096",
                     "--engine", "sparse"]
    for tag, extra in (("ring", []),
                       ("ring_qsgd", ["--compression", "qsgd",
                                      "--use-kernels"])):
        h = train(tag, full + extra, devices, engine="sparse",
                  vocab=arch.chip_share.model.vocab_size)
        check(h["state_devices"] == 4,
              f"{tag}: the state lives on {h['state_devices']} device(s), "
              "not on all 4")

    reduced = COMMON + ["--reduced", "--nodes", "4", "--seq", "256"]
    vocab = arch.reduced.vocab_size
    sparse = train("parity_sparse", reduced + ["--engine", "sparse"],
                   devices, engine="sparse", vocab=vocab)
    dense = train("parity_dense", reduced + ["--engine", "dense"], devices,
                  engine="dense", vocab=vocab)
    diffs = [a - b for a, b in zip(sparse["loss"], dense["loss"])]
    diff = max(abs(d) for d in diffs)
    print(f"parity: sparse - dense loss per round {diffs}; max {diff} "
          f"(limit {ENGINE_PARITY_LOSS_ATOL})")
    check(diff < ENGINE_PARITY_LOSS_ATOL,
          f"parity: sparse and dense losses differ by {diff}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A-C on one chip; 4: the sparse ring "
                         "across four chips and its dense comparison")
    args = ap.parse_args(argv)
    try:
        devices = tpu_devices()
        d = devices[0]
        print(f"device: platform={d.platform} kind={d.device_kind} "
              f"count={len(devices)} "
              f"bytes_limit={d.memory_stats()['bytes_limit']}", flush=True)
        check(os.path.isdir(os.path.join(ROOT, "src", "repro")),
              f"the repro package is not at {ROOT}/src")
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro.launch.compile_cache import enable_compile_cache

        print(f"compile cache: {enable_compile_cache()}")
        os.makedirs(OUT, exist_ok=True)
        if args.chips == 4:
            four_chips(devices)
        else:
            one_chip(devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": d.platform,
                                 "kind": d.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
