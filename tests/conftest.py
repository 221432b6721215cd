"""Test env: force CPU with 8 virtual devices so sharding tests run anywhere.

Must run before the first ``import jax`` anywhere in the test process
(SURVEY.md §4: CPU device-mesh simulation stands in for the reference's
absent distributed tests).
"""

import os

# Overwrite, not setdefault: the tests run on the CPU wherever they are run,
# also on a machine whose JAX would otherwise take an accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

from deeprest_tpu.compile_cache import configure_compile_cache

# Persistent compilation cache: the suite is dominated by XLA compiles of
# repeated shapes (every Trainer() re-jits the same step); caching them on
# disk cuts re-runs by minutes.  Keyed by jax version + backend + flags
# internally, so stale hits are not a correctness concern.
configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import numpy as np
import pytest

from deeprest_tpu.data.schema import Bucket, MetricSample, Span
from deeprest_tpu.train import kept


# Folding G window batches into the rows of one dot changes nothing in the
# algebra: every row still contracts the same K values with the same
# weights.  Whether it changes the BITS is up to the backend's matmul.  The
# XLA:CPU of jaxlib 0.9 hands f32 dots to the YNNPACK library, which picks
# its micro-kernel, and with it the order of the accumulation over K, from
# the row count: [24, K] x [K, N] and its [8, K] slice differ in the last
# bit (max 6e-8 here; `XLA_FLAGS=--xla_cpu_experimental_ynn_fusion_type=`
# puts Eigen back and the difference is 0.0 — checked under jax 0.9.0,
# PR 21; which shapes differ also depends on the host's CPU features).
# So a comparison across row counts is held to a few ulp of the
# reference's largest magnitude, measured at <= 2; chip_smoke.py prints
# the same difference for the compiled kernel on the chip.
FOLD_ULPS = 4


def assert_fold_equal(actual, desired):
    actual, desired = np.asarray(actual), np.asarray(desired)
    atol = FOLD_ULPS * np.finfo(np.float32).eps * float(
        np.max(np.abs(desired)))
    np.testing.assert_allclose(actual, desired, rtol=0.0, atol=atol)


# The superstep's executable is kept beside the compilation cache under a
# key made of the program's FILES (deeprest_tpu/train/kept.py), which cannot
# see a function a test replaced at run time; and the suite shares one cache
# directory on purpose.  So every test gets a store of its own, empty when it
# starts: it traces what it patched, the suite does not depend on its order,
# and nothing is left under `.jax_cache/deeprest-kept/`.  Outside every test
# (a fixture of a wider scope that dispatches) there is no store at all.
STORE_DIR_OF_THE_PROGRAM = kept.store_dir       # beside the compile cache


@pytest.fixture(scope="session", autouse=True)
def _no_kept_store_outside_a_test():
    kept.store_dir = lambda: None
    yield
    kept.store_dir = STORE_DIR_OF_THE_PROGRAM


@pytest.fixture(autouse=True)
def _kept_store_of_its_own(monkeypatch, tmp_path):
    monkeypatch.setattr(kept, "store_dir",
                        lambda: str(tmp_path / kept.SUBDIR))


@pytest.fixture
def hand_clock(monkeypatch):
    """``obs.metrics.Stopwatch``'s clock, moved by hand: ``now[0] += s``."""
    from types import SimpleNamespace

    from deeprest_tpu.obs import metrics as obs_metrics

    now = [100.0]
    monkeypatch.setattr(obs_metrics, "time",
                        SimpleNamespace(perf_counter=lambda: now[0]))
    return now


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy tests — live-cluster e2e, multihost, jit-compile-heavy "
        "model/training paths.  Quick tier: `pytest -m 'not slow'` "
        "(~100 s measured); full suite runs everything.",
    )


def _span(component, operation, *children):
    return Span(component=component, operation=operation, children=list(children))


def make_toy_buckets(num_buckets: int = 3, seed: int = 0) -> list[Bucket]:
    """A small corpus shaped like the documented raw-data contract
    (reference: resource-estimation/README.md:29-63): a write path with
    fan-out and a flat read path, with per-bucket metric series."""
    rng = np.random.default_rng(seed)
    buckets = []
    for t in range(num_buckets):
        n_compose = int(rng.integers(1, 4))
        n_read = int(rng.integers(1, 4))
        traces = []
        for i in range(n_compose):
            compose = _span(
                "gateway", "/compose",
                _span("compose-svc", "/compose",
                      _span("text-svc", "/decode"),
                      _span("store-svc", "/store",
                            _span("store-db", "/insert")),
                      *([_span("media-svc", "/upload")] if (t + i) % 2 == 0 else [])),
            )
            traces.append(compose)
        for _ in range(n_read):
            traces.append(
                _span("gateway", "/read",
                      _span("timeline-svc", "/read",
                            _span("store-svc", "/find")))
            )
        metrics = [
            MetricSample("gateway", "cpu", 0.5 + 0.1 * n_compose + 0.05 * n_read),
            MetricSample("gateway", "memory", 0.8 + 0.01 * t),
            MetricSample("store-db", "wiops", 100.0 * n_compose),
        ]
        buckets.append(Bucket(metrics=metrics, traces=traces))
    return buckets


@pytest.fixture
def toy_buckets() -> list[Bucket]:
    return make_toy_buckets()


def make_series_buckets(num_buckets: int, seed: int = 0) -> list[Bucket]:
    """A longer corpus with traffic-correlated resource values, long enough
    for windowed training (used by trainer/e2e tests)."""
    rng = np.random.default_rng(seed)
    buckets = []
    for t in range(num_buckets):
        load = 2.0 + np.sin(2 * np.pi * t / 24.0) + rng.uniform(-0.2, 0.2)
        n_compose = max(0, int(rng.poisson(load)))
        n_read = max(0, int(rng.poisson(2 * load)))
        traces = [
            _span("gateway", "/compose",
                  _span("store-svc", "/store", _span("store-db", "/insert")))
            for _ in range(n_compose)
        ] + [
            _span("gateway", "/read", _span("store-svc", "/find"))
            for _ in range(n_read)
        ]
        metrics = [
            MetricSample("gateway", "cpu",
                         10.0 * n_compose + 3.0 * n_read + rng.normal(0, 0.5)),
            MetricSample("store-db", "wiops",
                         25.0 * n_compose + rng.normal(0, 1.0)),
        ]
        buckets.append(Bucket(metrics=metrics, traces=traces))
    return buckets
