#!/usr/bin/env python3
"""ISSUE 53's probe, run ON THE CHIP by PR 53 (readings: PERF.md section 6):

    chiprun -- python3 chipbench/tests/memory_on_chip.py

A jitted program of known temporaries (``memory_analysis().temp_size_in_bytes``) must move
``run.MemoryWatch``'s reading by its temporaries within 2% and the allocator's ``peak_bytes_in_use`` by
next to nothing; it prints what is reserved when TWO programs are loaded (their sum, or the largest),
what deleting an executable does to the reservation, whether ``common.harness_only`` raises when a
program of the harness's own sets the peak by its reservation alone, and whether a device filled until
less than the temporaries is free refuses the program (then the reservation is real HBM)."""
import gc
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
from chipbench.common import harness_only
from chipbench.run import MemoryWatch
dev = jax.devices()[0]
print("device", dev.platform, dev.device_kind, flush=True)
if dev.platform != "tpu":
    raise SystemExit(f"no accelerator: platform {dev.platform!r}")
def show(tag):
    s = dev.memory_stats() or {}
    print(tag, {k: s.get(k) for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved", "peak_bytes_reserved", "bytes_limit")}, flush=True)
def chain(x):
    a = jnp.cumsum(x, axis=0)
    b = jnp.cumsum(a[::-1] * 1.5, axis=1)
    c = jnp.cumsum(b[:, ::-1] + a, axis=2)
    return (a[0] + b[1] + c[2]).sum() + (a * b * c).mean()
watch = MemoryWatch(jax.devices)
x = jnp.ones((128, 1024, 1024), jnp.float32).block_until_ready()      # 0.5 GiB
before = watch.sample("x held"); show("x held")
big = jax.jit(chain).lower(x).compile()
t_big = big.memory_analysis().temp_size_in_bytes
after_compile = watch.sample("big compiled"); show("big compiled (not run)")
print("result", float(big(x)), flush=True)
after = watch.sample("big ran"); show("big ran")
print(f"WATCH MOVED BY {after - before} for temporaries {t_big}: ratio {(after - before) / t_big:.4f}; by compile alone {after_compile - before}", flush=True)
y = jnp.ones((32, 1024, 1024), jnp.float32).block_until_ready()
small = jax.jit(chain).lower(y).compile()
t_small = small.memory_analysis().temp_size_in_bytes
print("result", float(small(y)), flush=True)
show(f"small ran too (temporaries {t_small}): reserved = sum {t_big + t_small} or largest {t_big}?")
watch.sample("small ran")
print("result", float(big(x)), flush=True); show("big ran again")
del big; gc.collect(); show("big deleted")
print("result", float(small(y)), flush=True); show("small ran after big deleted")
del small; gc.collect(); show("both deleted")
print("watch high", watch.high(), "limit", (dev.memory_stats() or {}).get("bytes_limit"), flush=True)
# a program of the harness's own, larger than anything loaded so far: its reservation alone must fail the run
ctx = types.SimpleNamespace(memory_peak_bytes=lambda: watch.sample("memory_peak_bytes"))
z = jnp.ones((256, 1024, 1024), jnp.float32).block_until_ready()      # 1 GiB
watch.sample("z held")
try:
    with harness_only(ctx, "a program of the harness's own"):
        huge = jax.jit(chain).lower(z).compile()
        print("result", float(huge(z)), "temporaries", huge.memory_analysis().temp_size_in_bytes, flush=True)
    print("HARNESS_ONLY DID NOT RAISE; watch high", watch.high(), flush=True)
except RuntimeError as e:
    print("HARNESS_ONLY RAISED:", e, flush=True)
show("after the harness's own program")
del huge, z; gc.collect()
# a device filled until less than the temporaries is free: does the program still load and run?
big = jax.jit(chain).lower(x).compile()
limit, fill = dev.memory_stats()["bytes_limit"], []
while True:
    free = limit - dev.memory_stats()["bytes_in_use"]
    if free < t_big // 2 + (64 << 20) or free < (1 << 30):
        break
    fill.append(jnp.zeros((min(free - t_big // 2, 1 << 30) // 4,), jnp.float32).block_until_ready())
show(f"filled with {len(fill)} arrays; temporaries {t_big}")
try:
    print("result when filled", float(big(x)), flush=True)
    print("RAN WHEN FILLED: the temporaries are not taken from what the allocator counts", flush=True)
except Exception as e:   # noqa: BLE001 - whatever the runtime raises is the reading
    print("FAILED WHEN FILLED:", type(e).__name__, str(e)[:600], flush=True)
