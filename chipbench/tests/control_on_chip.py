#!/usr/bin/env python3
"""The control of `correct`, read on the chip at a cell's own size:

    chiprun -- python3 chipbench/tests/control_on_chip.py --workload <cell> --seeds 1 2 3

For each seed it puts the reference in the program's place, computed in the
nearest precision below the one the configuration states (fp8 operands for a
bfloat16 configuration, bfloat16 for a float32 one), and prints the numbers
the cell's comparison would read for it, beside the cell's limits: the
control has to fail at least one.  It also prints the same numbers for the
reference computed AT the configuration's precision, as a calibration of what
that precision alone costs.  The program's own (sound) readings come from
runs of chipbench/run.py, which prints every number it compares.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

BELOW = {"float32": "bf16", "bfloat16": "fp8"}
AT = {"float32": "f32", "bfloat16": "bf16"}


def train_control(loaded, seed, generator):
    import jax

    from chipbench.reference import qrnn_ref as ref
    from chipbench.runners import train
    from deeprest_tpu.config import ModelConfig, TrainConfig

    model = dict(loaded["config"]["model"])
    model["quantiles"] = tuple(model["quantiles"])
    mcfg = ModelConfig(**model)
    key_seed = seed % (2 ** 31 - 1)
    tcfg = TrainConfig(seed=key_seed, **loaded["config"].get("train", {}))
    raw = generator.generate(loaded["mix"]["params"], seed, model)
    batches = train.check_batches(raw, tcfg,
                                  train.check_starts(raw, tcfg, seed))
    key = jax.random.PRNGKey(key_seed)
    shape = (mcfg.num_metrics, mcfg.feature_dim, mcfg.hidden_size,
             len(mcfg.quantiles))
    out = {}
    runs = {}
    for precision in ("f32", AT[mcfg.compute_dtype], BELOW[mcfg.compute_dtype]):
        if precision not in runs:
            runs[precision] = ref.train_three_steps(
                ref.init_params(key, *shape), batches, key_seed,
                mcfg.quantiles, mcfg.dropout_rate, precision)
    for precision, numbers in runs.items():
        if precision != "f32":
            out[precision] = train.compare(numbers, runs["f32"])
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    from deeprest_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    import importlib

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from chipbench import run

    loaded = run.load_cell(args.workload)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    generator = importlib.import_module(
        f"chipbench.generators.{loaded['mix']['generator']}")
    control = {"train": train_control}[loaded["mix"]["runner"]]
    for seed in args.seeds:
        t0 = time.perf_counter()
        numbers = control(loaded, seed, generator)
        for precision, vals in numbers.items():
            fails = [k for k, lim in loaded["limits"].items()
                     if not vals[k] <= lim]
            print(f"CONTROL {args.workload} seed {seed} reference in "
                  f"{precision}: {json.dumps(vals)} limits "
                  f"{json.dumps(loaded['limits'])} fails {fails}", flush=True)
        print(f"  ({time.perf_counter() - t0:.1f} s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
