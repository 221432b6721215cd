"""Importable-by-name factory for ProcessReplica tests: the spawned
worker builds its own tiny Predictor stack from this module (the spec's
``sys_path`` carries the tests directory into the child)."""

import numpy as np

F, E, H, W = 6, 3, 8, 8


class SlowBackend:
    """build_tiny wrapped with a fixed per-call delay — gives the chaos
    tests a window to SIGKILL a worker MID-request (and the deadline
    tests a predict that reliably outlives a short timeout).  Metadata
    and batcher attachment delegate to the inner stack."""

    def __init__(self, inner, delay_s: float):
        self._inner = inner
        self.delay_s = float(delay_s)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def predict_series(self, traffic, integrate=True):
        import time

        time.sleep(self.delay_s)
        return self._inner.predict_series(traffic, integrate=integrate)

    def predict_series_many(self, series_list, integrate=True):
        import time

        time.sleep(self.delay_s)
        return self._inner.predict_series_many(series_list,
                                               integrate=integrate)


def build_slow(delay_s: float = 1.0, scale: float = 1.0, ladder=(8,)):
    return SlowBackend(build_tiny(scale=scale, ladder=tuple(ladder)),
                       delay_s)


def build_tiny(scale: float = 1.0, ladder=(8,), f: int = F, e: int = E,
               h: int = H, w: int = W, quant: str = "off"):
    """A random-init predictor at unit min/max stats; the same seed, so the
    same tree at the same widths whatever ``quant`` serves it as."""
    import jax

    from deeprest_tpu.config import ModelConfig
    from deeprest_tpu.data.windows import MinMaxStats
    from deeprest_tpu.models.qrnn import QuantileGRU
    from deeprest_tpu.serve import Predictor

    mc = ModelConfig(feature_dim=f, num_metrics=e, hidden_size=h,
                     dropout_rate=0.0)
    model = QuantileGRU(config=mc)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, w, f), np.float32),
                        deterministic=True)["params"]
    if scale != 1.0:
        params = jax.tree.map(lambda a: a * scale, params)
    return Predictor(
        params, mc,
        x_stats=MinMaxStats(min=np.float32(0.0), max=np.float32(1.0)),
        y_stats=MinMaxStats(min=np.zeros((e,), np.float32),
                            max=np.ones((e,), np.float32)),
        metric_names=[f"c{i}_cpu" for i in range(e)],
        window_size=w, ladder=tuple(ladder), quant=quant)
