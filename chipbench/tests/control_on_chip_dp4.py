#!/usr/bin/env python3
"""The readings behind the limits of a ``train_mesh`` cell, on the chips at
the cell's own size, many seeds through ONE trainer:

    chiprun --chips 4 -- python3 chipbench/tests/control_on_chip_dp4.py \\
        --workload tenk-train-live4k-dp4 --seeds 1 2 3 ... \\
        --control-seeds 1 2 3 [--profile SEED]

A whole run of ``chipbench/run.py`` on four chips costs four chips' time for
its set-up, its window and its reference; the comparison that decides
`correct` needs none of the window.  So one process builds the cell's
``Trainer`` under the configuration's mesh as ``runners/train_mesh.py``
builds it and, a seed at a time, makes the corpus, stages it on that
trainer (a restage: the table of another seed's live call paths, the one
compiled superstep), starts from a fresh state (``init_state``'s own lines
with the reference's seeded weights in the place of ``model.init``'s: its
jitted ``tx.init`` and ``pin_state``), takes the three checked steps through
the window's own superstep with the runner's ``check_plans``, frees the
state and runs the plain one-device float32 reference on the global batch.
It prints the numbers the cell's comparison would read, beside its limits:

- ``SOUND``: the program as it is, every seed of ``--seeds``;
- ``LEFT_OUT``: the broken reduce the configuration's guarantee names, the
  seeds of ``--control-seeds``: the same three steps with the LAST chip's
  32 windows given weight 0 in the plan, so every update is Adam on the
  mean over three chips' rows and the fourth's never reach it (a reduce that
  lost one participant).  It has to fail at least one limit on every seed;
- ``DROPPED``: the other broken path the guarantee names, the seeds of
  ``--dropped-seeds``, last (it replaces two names of
  ``deeprest_tpu.train.trainer`` for the rest of the process):
  ``control_on_chip_live4k.most_hit_half_only``, the program made to stage
  a table of the most-hit half of its live call paths under the mesh.  It
  has to fail at least one limit on every seed.

``--profile SEED`` then runs that seed's corpus as the window does (a
warm-up epoch, four timed epochs, ``Trainer.profile_epoch``) and prints the
table by scope, the collectives' instructions of the dispatched program and
the set-up table.  The fp8 control of the cell runs on ONE chip:
``control_on_chip_mesh.py --workload <cell>``.  Every line also goes to
``chiprun_out/control_dp4.jsonl``.  ``DP4_TINY=1`` rehearses the control
flow on the CPU's virtual devices at a toy size.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out")


def _tiny(loaded: dict) -> None:
    loaded["config"]["model"].update(
        feature_dim=512, num_metrics=5, hidden_size=16,
        compute_dtype="float32")
    loaded["config"]["train"].update(batch_size=16, window_size=8,
                                     sparse_nnz_cap=8)
    loaded["mix"]["params"].update(buckets=400, hot_paths=200, nnz_lo=2,
                                   nnz_hi=6, day=100)


class Cell:
    """The cell's trainer under its mesh, and what a seed needs of it."""

    def __init__(self, workload: str):
        import importlib

        import jax

        from chipbench import run
        from deeprest_tpu.config import (
            Config, MeshConfig, ModelConfig, TrainConfig,
        )

        self.jax = jax
        self.loaded = run.load_cell(workload)
        if os.environ.get("DP4_TINY"):
            _tiny(self.loaded)
        cfg = self.loaded["config"]
        self.model = dict(cfg["model"])
        self.model["quantiles"] = tuple(self.model["quantiles"])
        self.mcfg = ModelConfig(**self.model)
        self.train = cfg.get("train", {})
        self.config = Config(model=self.mcfg,
                             train=TrainConfig(seed=0, **self.train),
                             mesh=MeshConfig(**cfg["mesh"]))
        if len(jax.devices()) < self.config.mesh.size:
            raise SystemExit(f"{len(jax.devices())} devices, the mesh "
                             f"{cfg['mesh']} asks for {self.config.mesh.size}")
        self.dims = (self.mcfg.num_metrics, self.mcfg.feature_dim,
                     self.mcfg.hidden_size, len(self.mcfg.quantiles))
        self.generator = importlib.import_module(
            f"chipbench.generators.{self.loaded['mix']['generator']}")
        self.trainer = self.placement = self.rng_placement = None

    def corpus(self, seed: int):
        """(raw, bundle, starts, tcfg, key) of a seed, as the runner's
        phases 1 and 2 make them."""
        import numpy as np

        from chipbench.runners import train_mesh as runner
        from deeprest_tpu.config import FeaturizeConfig, TrainConfig
        from deeprest_tpu.data.featurize import CallPathSpace, FeaturizedData
        from deeprest_tpu.train.data import prepare_dataset

        key_seed = seed % (2 ** 31 - 1)
        tcfg = TrainConfig(seed=key_seed, **self.train)
        raw = self.generator.generate(self.loaded["mix"]["params"], seed,
                                      self.model)
        space = CallPathSpace(config=FeaturizeConfig(
            hash_features=True, capacity=self.mcfg.feature_dim)).freeze()
        data = FeaturizedData(
            traffic=raw["traffic"], resources=raw["resources"],
            invocations={"general": np.ones(len(raw["traffic"]), np.float32)},
            space=space)
        bundle = prepare_dataset(data, tcfg)
        starts = runner.check_starts(raw, tcfg, seed, bundle)
        return raw, bundle, starts, tcfg, self.jax.random.PRNGKey(key_seed)

    def fresh_state(self, bundle, key_seed: int, key):
        """A state as ``init_state(sample, seed=key_seed)`` returns it with
        the reference's seeded weights installed, made without the un-jitted
        ``model.init`` (11 s under the mesh): ``init_state``'s last three
        lines on the seeded weights.  The first call builds the trainer and
        runs ``init_state`` once, for the leaves' placement."""
        from chipbench.reference import qrnn_ref as ref
        from deeprest_tpu.train.trainer import Trainer, TrainState

        jax, jnp = self.jax, self.jax.numpy
        if self.trainer is None:
            self.trainer = Trainer(self.config, bundle.feature_dim,
                                   bundle.metric_names)
            state = jax.block_until_ready(self.trainer.init_state(
                self.trainer.sample_input(bundle), seed=key_seed))
            self.placement = {k: v.sharding for k, v in state.params.items()}
            self.rng_placement = state.rng.sharding
            del state
        seeded = ref.init_params(key, *self.dims)
        params = {k: jax.device_put(seeded[k], self.placement[k])
                  for k in self.placement}
        del seeded
        _, train_rng = jax.random.split(jax.random.PRNGKey(key_seed))
        state = self.trainer._pin_state(TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=jax.jit(self.trainer.tx.init)(params),
            rng=jax.device_put(train_rng, self.rng_placement)))
        return jax.block_until_ready(state)

    def three_steps(self, state, staged, bundle, starts, key,
                    leave_out_last_chip: bool) -> dict:
        """The runner's phase 3 on ``state``: the program's numbers."""
        from chipbench.reference import qrnn_ref as ref
        from chipbench.runners import train_mesh as runner
        from deeprest_tpu.parallel.distributed import stage_plan

        jax, jnp = self.jax, self.jax.numpy
        trainer = self.trainer
        b = self.config.train.batch_size
        num_steps = -(-bundle.num_train_windows // b)
        plans = runner.check_plans(trainer, starts, num_steps)
        if leave_out_last_chip:
            share = b // int(trainer.mesh.shape["data"])
            for _, weights in plans:
                weights[..., -share:] = 0.0
        plans = [stage_plan(trainer.mesh, *plan) for plan in plans]
        state, losses0 = trainer._superstep(state, *staged, *plans[0], 0)
        grad_norm = {
            k: float(jnp.sqrt(jnp.sum(jnp.square(v)))) / (1 - ref.ADAM["b1"])
            for k, v in state.opt_state[0].mu.items()}
        state, losses1 = trainer._superstep(state, *staged, *plans[1], 0)
        start = ref.init_params(key, *self.dims)
        delta = {k: float(v) for k, v in ref.leaf_norms(
            {k: state.params[k] - start[k] for k in start}).items()}
        same = all(map(_same_on_every_chip, (
            state.params["gru_fwd_w_ih"], state.params["head_w"],
            state.opt_state[0].nu["gru_bwd_w_ih"])))
        out = {"losses": [float(losses0[0]), float(losses1[0]),
                          float(losses1[1])],
               "grad_norm": grad_norm, "delta_norm": delta,
               "steps_counted": int(state.step),
               "same_on_every_chip": same}
        del state, plans, start
        return out


def _same_on_every_chip(leaf) -> bool:
    """Every chip's copy of a replicated leaf against the first's, bit for
    bit, compared on the first chip."""
    import jax
    import jax.numpy as jnp

    first, *others = leaf.addressable_shards
    return all(bool(jnp.array_equal(
        first.data, jax.device_put(s.data, first.device))) for s in others)


def say(kind, workload, seed, what, numbers, limits, extra) -> list:
    fails = [k for k, lim in limits.items() if not numbers[k] <= lim]
    line = {"kind": kind, "workload": workload, "seed": seed, "what": what,
            "fails": fails, **numbers, **extra}
    print(f"{kind} {workload} seed {seed} {what}: {json.dumps(numbers)} "
          f"limits {json.dumps(limits)} fails {fails} {json.dumps(extra)}",
          flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "control_dp4.jsonl"), "a") as fh:
        fh.write(json.dumps(line) + "\n")
    return fails


WHAT = {
    "SOUND": "program",
    "LEFT_OUT": "program with the last chip's rows left out of the mean",
    "DROPPED": "program with the less-hit half of the live call paths left "
               "out of its table",
}


def one_seed(cell: Cell, workload, seed, kinds, limits) -> bool:
    from chipbench.reference import qrnn_ref as ref
    from chipbench.runners import train_mesh as runner

    jax = cell.jax
    t0 = time.perf_counter()
    raw, bundle, starts, tcfg, key = cell.corpus(seed)
    t_corpus = time.perf_counter() - t0
    runs = {}
    for kind in kinds:
        state = cell.fresh_state(bundle, tcfg.seed, key)
        staged = cell.trainer.stage_dataset(bundle)
        jax.block_until_ready(staged)
        runs[kind] = cell.three_steps(state, staged, bundle, starts, key,
                                      kind == "LEFT_OUT")
        del state, staged
        gc.collect()
    form = runner._gauge("deeprest_train_projection_columns")
    t1 = time.perf_counter()
    reference = ref.train_three_steps(
        ref.init_params(key, *cell.dims),
        runner.check_batches(raw, tcfg, starts), tcfg.seed,
        cell.mcfg.quantiles, cell.mcfg.dropout_rate, "f32")
    t_ref = time.perf_counter() - t1
    ok = True
    for kind, program in runs.items():
        numbers = runner.compare(program, reference)
        extra = {"steps_counted": program["steps_counted"],
                 "same_on_every_chip": program["same_on_every_chip"],
                 "projection_columns": form,
                 "seconds": round(time.perf_counter() - t0, 1),
                 "corpus_s": round(t_corpus, 1), "reference_s": round(t_ref, 1)}
        fails = say(kind, workload, seed, WHAT[kind], numbers, limits, extra)
        sound = (not fails and program["steps_counted"] == 3
                 and program["same_on_every_chip"])
        ok &= sound if kind == "SOUND" else bool(fails)
    return ok


def profile(cell: Cell, workload: str, seed: int) -> None:
    """The window's epochs on one seed's corpus, then the table by scope."""
    import numpy as np

    from chipbench.runners import train_mesh as runner
    from deeprest_tpu.obs import profiler, setup, spans

    jax = cell.jax
    raw, bundle, starts, tcfg, key = cell.corpus(seed)
    del raw
    state = cell.fresh_state(bundle, tcfg.seed, key)
    was, spans.RECORDER.enabled = spans.RECORDER.enabled, True
    spans.RECORDER.clear()
    try:
        staged = cell.trainer.stage_dataset(bundle)
    finally:
        spans.RECORDER.enabled = was
    stage_tags = [s.tags for s in spans.RECORDER.snapshot()
                  if s.name == "train.stage"]
    rng = np.random.default_rng(seed + 2)
    trainer = cell.trainer
    state, _ = trainer.train_epoch(state, bundle, rng, staged=staged)
    times = []
    for _ in range(4):
        t = time.perf_counter()
        state, _ = trainer.train_epoch(state, bundle, rng, staged=staged)
        times.append(time.perf_counter() - t)
    steps = len(trainer._last_epoch_losses)
    with tempfile.TemporaryDirectory(prefix="dp4-profile-") as tmp:
        state, table = trainer.profile_epoch(state, bundle, rng, staged, tmp)
    epoch_tags = [s.tags for s in spans.RECORDER.snapshot()
                  if s.name == "train.epoch"]
    text = trainer._dispatched_program_text(state)
    instructions = [line.strip()[:260] for line in text.splitlines()
                    if (m := profiler._INSTRUCTION.match(line))
                    and profiler.collective_kind(m["opcode"])]
    line = {"kind": "PROFILE", "workload": workload, "seed": seed,
            "steps": steps, "epoch_s": [round(x, 4) for x in times],
            "steps_per_s": steps / float(np.median(times)),
            "stage_span_tags": stage_tags, "epoch_span_tags": epoch_tags,
            **{name: runner._gauge(f"deeprest_train_{name}") for name in (
                "collective_bytes", "projection_columns", "optimizer_rows",
                "program_bytes", "device_bytes")},
            "collective_instructions": instructions,
            "memory": {str(d.id): (d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0) for d in jax.devices()}}
    print("PROFILE " + json.dumps(line), flush=True)
    print(profiler.format_table(table), flush=True)
    print(setup.format_setup(table["setup"]), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"profile_{workload}.json"), "w") as fh:
        json.dump({"line": line, "table": table}, fh, indent=1, default=str)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--dropped-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--profile", type=int, default=None)
    args = ap.parse_args()
    from deeprest_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = Cell(args.workload)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()),
          flush=True)
    limits = cell.loaded["limits"]
    bad = []
    controls = set(args.control_seeds)
    for seed in list(args.seeds) + [s for s in args.control_seeds
                                    if s not in args.seeds]:
        kinds = ("SOUND", "LEFT_OUT") if seed in controls else ("SOUND",)
        if not one_seed(cell, args.workload, seed, kinds, limits):
            bad.append(seed)
    if args.profile is not None:
        profile(cell, args.workload, args.profile)
    if args.dropped_seeds:
        from chipbench.tests import control_on_chip_live4k

        control_on_chip_live4k.most_hit_half_only()
        for seed in args.dropped_seeds:
            if not one_seed(cell, args.workload, seed, ("DROPPED",), limits):
                bad.append(seed)
    if bad:
        print(f"NOT AS IT SHOULD BE on seeds {bad}: a sound run out of its "
              "limits, or a control inside them", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
