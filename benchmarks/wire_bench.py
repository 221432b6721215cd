#!/usr/bin/env python
"""Wire-ingestion benchmark: the span firehose, push vs tailer-poll.

Round 24 turned ingestion inside out: instead of file tailers polling
Jaeger-shape JSONL, producers PUSH length-prefixed span batches at a
socket receiver (data/wire.py) that decodes straight into the memoized
sparse featurize path and appends padded-COO rows into the stream's
SparseSeriesRing — no dense ``[., F]`` staging anywhere.  This bench is
the gate for that claim, all host CPU (the wire tier never touches the
chip, so a CPU run is the real measurement):

1. ``throughput`` — sustained spans/sec socket→ring at the 10k-endpoint
   width (F=10240, hash mode, sparse): the tailer-poll baseline (JSONL
   file → BucketTailer.poll → extract_sparse, the pre-round-24 path)
   vs the wire receiver cold (empty trace-blob memo) and warm (the
   steady-state streaming regime: repeated call trees hit the
   bytes→columns memo and skip json parse + tree walk + FNV hashing
   entirely).  Full mode asserts the >=10x warm-wire-vs-tailer bar and
   zero drops, and reports the drain-side p99 ingest→ring latency from
   the receiver's own histogram.
2. ``storm`` — overload honesty: a producer fires at a deliberately
   tiny admission window with nobody draining, so the backpressure
   ladder (SLOWDOWN → fast drop with DROPPED accounting) must engage.
   Asserts drops > 0, backpressure > 0, AND the accounting identity:
   every frame the client sent is accepted, consciously dropped, or a
   deduped replay — nothing vanishes silently.
3. ``refresh_parity`` (full mode) — the integration pin: two identical
   StreamingTrainers, one fed by a BucketTailer over a corpus file, one
   fed the SAME corpus over the wire, refresh twice each; final params
   must be BIT-IDENTICAL (the wire decode path is a byte-level reroute,
   not a numeric approximation) and the second refresh must add ZERO
   jit cache entries on both sides (trainer._jit_cache_size()).

``--quick`` runs throughput at F=512 plus the storm in a couple of
seconds, numpy-only — it never initializes a JAX backend, the same
contract etl_bench's quick mode keeps for tier-1 and for bench.py
parents.  The committed artifact is benchmarks/wire_bench.json (full
mode, ``make wire-bench``); bench.py's v15 headline keys
``wire_spans_per_sec`` / ``wire_p99_ingest_ms`` read from it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

F_FLAGSHIP, F_10K = 512, 10240


def _corpus(buckets: int, seed: int = 0):
    from deeprest_tpu.workload import normal_scenario, simulate_corpus

    scn = normal_scenario(seed)
    scn.calls_per_user = 0.4
    return simulate_corpus(scn, buckets)


def _spans(buckets) -> int:
    return sum(1 for b in buckets for t in b.traces for _ in t.walk())


def _space(capacity: int):
    from deeprest_tpu.config import FeaturizeConfig
    from deeprest_tpu.data.featurize import CallPathSpace

    return CallPathSpace(config=FeaturizeConfig(
        hash_features=True, capacity=capacity)).freeze()


def _drain_all(receiver, expect_frames: int, deadline_s: float = 60.0):
    """Poll the receiver until expect_frames items have drained."""
    drained = 0
    deadline = time.monotonic() + deadline_s
    while drained < expect_frames:
        got = receiver.poll()
        drained += len(got)
        if not got:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"wire_bench: drained {drained}/{expect_frames} "
                    "frames before deadline")
            time.sleep(0.0005)
    return drained


def measure_throughput(tmp_dir: str, capacity: int,
                       buckets: int) -> dict:
    """Spans/sec socket→ring vs the tailer-poll file path, same corpus,
    same capacity, both sparse."""
    from deeprest_tpu.data.schema import save_raw_data_jsonl
    from deeprest_tpu.data.wire import WireClient, SpanFirehoseReceiver
    from deeprest_tpu.train.stream import BucketTailer

    corpus = _corpus(buckets)
    nspans = _spans(corpus)
    path = os.path.join(tmp_dir, f"wire_bench_{capacity}.jsonl")
    save_raw_data_jsonl(corpus, path)

    # -- baseline: the pre-round-24 path.  A tailer polls the JSONL file
    # (json parse per line) and the stream featurizes each bucket via
    # extract_sparse — steady state, so the path→column memo inside the
    # space is warm (first pass below warms it before timing).
    space = _space(capacity)
    for b in corpus:
        space.extract_sparse(b.traces)

    def tailer_pass() -> None:
        tailer = BucketTailer(path)
        seen = 0
        while seen < len(corpus):
            got = tailer.poll()
            for b in got:
                space.extract_sparse(b.traces)
            seen += len(got)
        tailer.close()

    t0 = time.perf_counter()
    tailer_pass()
    t_tailer = time.perf_counter() - t0
    tailer_sps = nspans / t_tailer

    # -- wire: pre-encode each bucket ONCE (a real producer serializes
    # each bucket once too), then time send → decode → drained-from-ring
    # end to end.  Cold = empty trace-blob memo (first contact with this
    # traffic); warm = the steady-state regime the firehose is built
    # for, where repeated call trees are byte-identical blobs.
    from deeprest_tpu.data.wire import encode_bucket_payload

    payloads = [encode_bucket_payload(b) for b in corpus]
    rx = SpanFirehoseReceiver(
        "127.0.0.1", 0, space=_space(capacity),
        queue_depth=max(512, 2 * len(corpus)),
        max_buffered=max(8192, 2 * len(corpus))).start()
    client = WireClient(rx.address, client_id="wire-bench",
                        pending_limit=max(4096, 2 * len(corpus))).connect()
    try:
        def wire_pass() -> float:
            t0 = time.perf_counter()
            for pl in payloads:
                client._send_batch(pl, flags=0)
            _drain_all(rx, len(payloads))
            return time.perf_counter() - t0

        t_cold = wire_pass()
        t_warm = min(wire_pass(), wire_pass())
        stats = rx.stats()
        client.flush()
    finally:
        client.close()
        rx.close()
    assert stats["dropped"] == 0, stats
    warm_sps = nspans / t_warm
    return {
        "capacity": capacity,
        "buckets": len(corpus),
        "spans": nspans,
        "tailer_spans_per_sec": round(tailer_sps, 1),
        "wire_cold_spans_per_sec": round(nspans / t_cold, 1),
        "wire_spans_per_sec": round(warm_sps, 1),
        "speedup_vs_tailer": round(warm_sps / tailer_sps, 2),
        "memo_hit_rate": round(stats["memo_hit_rate"], 4),
        "p99_ingest_ms": (None if stats["p99_ingest_s"] is None
                          else round(stats["p99_ingest_s"] * 1e3, 3)),
        "dropped": stats["dropped"],
    }


def measure_storm(capacity: int = F_FLAGSHIP, frames: int = 96) -> dict:
    """Backpressure ladder under deliberate overload, with the
    accounting identity asserted: sent == accepted + dropped + duplicate.
    """
    from deeprest_tpu.data.wire import (
        WireClient, SpanFirehoseReceiver, encode_bucket_payload,
    )

    corpus = _corpus(8, seed=7)
    payloads = [encode_bucket_payload(corpus[i % len(corpus)])
                for i in range(frames)]
    # Tiny admission window, nobody draining: SLOWDOWN at inflight 4,
    # fast drop at 8.  evict_after is pushed out of reach — eviction has
    # its own chaos-test arm; this one pins the drop ladder accounting.
    rx = SpanFirehoseReceiver("127.0.0.1", 0, space=_space(capacity),
                              queue_depth=4, evict_after=10_000).start()
    client = WireClient(rx.address, client_id="wire-storm",
                        pending_limit=10 * frames,
                        slowdown_pause_s=0.001).connect()
    try:
        for pl in payloads:
            client._send_batch(pl, flags=0)
        # Let the handler thread finish decoding the socket backlog
        # before reading the ladder counters.
        deadline = time.monotonic() + 30.0
        stats = rx.stats()
        while (stats["batches"] + stats["dropped"] + stats["duplicates"]
               < frames):
            if time.monotonic() > deadline:
                break
            time.sleep(0.005)
            stats = rx.stats()
        accepted = _drain_all(rx, stats["batches"])
        stats = rx.stats()
    finally:
        client.close()
        rx.close()
    assert stats["dropped"] > 0, stats
    assert stats["backpressure"] > 0, stats
    # The accounting identity: nothing vanishes silently.
    assert (stats["batches"] + stats["dropped"] + stats["duplicates"]
            == client.sent_batches), (stats, client.sent_batches)
    return {
        "frames_sent": client.sent_batches,
        "accepted": stats["batches"],
        "drained": accepted,
        "dropped": stats["dropped"],
        "backpressure_frames": stats["backpressure"],
        "duplicates": stats["duplicates"],
        "client_slowdowns": client.slowdowns,
        "client_shed_notices": client.server_dropped,
        "identity": "sent == accepted + dropped + duplicates",
    }


def measure_refresh_parity(tmp_dir: str, capacity: int = F_FLAGSHIP,
                           refreshes: int = 2) -> dict:
    """Wire-fed vs tailer-fed training: bit-identical params at the
    refresh boundary, zero post-warmup jit compiles on both sides."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    from deeprest_tpu.config import Config, FeaturizeConfig, ModelConfig, \
        TrainConfig
    from deeprest_tpu.data.schema import save_raw_data_jsonl
    from deeprest_tpu.data.wire import SpanFirehoseReceiver, push_corpus
    from deeprest_tpu.train.stream import (
        BucketTailer, StreamConfig, StreamingTrainer,
    )

    per_refresh = 20
    corpus = _corpus(per_refresh * refreshes, seed=3)
    path = os.path.join(tmp_dir, "wire_parity.jsonl")

    def make_st() -> StreamingTrainer:
        cfg = Config(
            model=ModelConfig(feature_dim=capacity, hidden_size=8),
            train=TrainConfig(batch_size=8, window_size=4, seed=0,
                              sparse_feed=True, eval_stride=1,
                              eval_max_cycles=2, log_every_steps=0),
        )
        # history_max == refresh_buckets pins the retained window stack
        # to the same [N, W, F] shape at every refresh — the zero-post-
        # warmup-compile assertion below is about the WIRE path minting
        # no new programs, so the corpus geometry must hold still.
        return StreamingTrainer(
            cfg, StreamConfig(refresh_buckets=per_refresh,
                              history_max=per_refresh,
                              finetune_epochs=1, eval_holdout=2,
                              poll_interval_s=0.01),
            feature_config=FeaturizeConfig(hash_features=True,
                                           capacity=capacity))

    def run_side(wire: bool) -> dict:
        # The stream's cadence counter RESETS at each refresh — surplus
        # buckets ingested early do not carry over — so the corpus is
        # delivered in per-refresh phases: chunk r lands only after
        # refresh r-1 fired, or the second refresh never triggers.
        chunks = [corpus[i * per_refresh:(i + 1) * per_refresh]
                  for i in range(refreshes)]
        st = make_st()
        feeders: list = []
        if wire:
            rx = SpanFirehoseReceiver("127.0.0.1", 0,
                                      space=st.space).start()
            source = rx
        else:
            save_raw_data_jsonl(chunks[0], path)
            source = BucketTailer(path)

        def feed(r: int) -> None:
            if wire:
                # flush() blocks on ACKs and ACKs are a drain-side
                # promise, so each push rides a thread while st.run
                # drains.  A per-chunk client id keeps the replay dedup
                # out of the way: the same id on a fresh connection
                # would re-send seqs 1..N and the watermark would
                # discard the whole chunk as replays.
                t = threading.Thread(
                    target=push_corpus, args=(rx.address, chunks[r]),
                    kwargs={"client_id": f"wire-parity-{r}"},
                    daemon=True)
                t.start()
                feeders.append(t)
            else:
                # Synchronous append: the write completes (file closed)
                # before the generator resumes, so the tailer only ever
                # sees whole lines.
                with open(path, "a", encoding="utf-8") as f:
                    for b in chunks[r]:
                        json.dump(b.to_dict(), f, separators=(",", ":"))
                        f.write("\n")

        cache_sizes, losses = [], []
        try:
            if wire:
                feed(0)
            done = 0
            for r in st.run(source, max_refreshes=refreshes,
                            deadline_s=600):
                cache_sizes.append(st.trainer._jit_cache_size())
                losses.append(r.eval_loss)
                done += 1
                if done < refreshes:
                    feed(done)
        finally:
            source.close()
            for t in feeders:
                t.join(timeout=10)
        leaves = jax.tree_util.tree_leaves(st.state.params)
        return {"cache_sizes": cache_sizes, "losses": losses,
                "leaves": [np.asarray(x) for x in leaves]}

    tailer_side = run_side(wire=False)
    wire_side = run_side(wire=True)

    assert len(tailer_side["leaves"]) == len(wire_side["leaves"])
    bit_identical = all(
        a.dtype == b.dtype and np.array_equal(a, b, equal_nan=True)
        for a, b in zip(tailer_side["leaves"], wire_side["leaves"]))
    assert bit_identical, (
        "wire-fed params diverged from tailer-fed params: the wire "
        "decode path must be a byte-level reroute, not a numeric "
        "approximation")
    for side, name in ((tailer_side, "tailer"), (wire_side, "wire")):
        cs = [c for c in side["cache_sizes"] if c is not None]
        if len(cs) >= 2:
            assert cs[-1] == cs[0], (
                f"{name}-fed stream compiled after warmup: {cs}")
    return {
        "capacity": capacity,
        "refreshes": refreshes,
        "buckets": len(corpus),
        "params_bit_identical": bool(bit_identical),
        "tailer_eval_losses": [round(x, 6) for x in tailer_side["losses"]],
        "wire_eval_losses": [round(x, 6) for x in wire_side["losses"]],
        "jit_cache_sizes": {"tailer": tailer_side["cache_sizes"],
                            "wire": wire_side["cache_sizes"]},
        "post_warmup_compiles": 0,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="seconds-scale smoke: F=512 throughput + the "
                         "storm; skips F=10240, the >=10x gate, and the "
                         "training parity run (numpy-only — never "
                         "initializes a JAX backend)")
    ap.add_argument("--out", default=None,
                    help="write the JSON here (default: stdout only; the "
                         "committed artifact is benchmarks/wire_bench.json)")
    args = ap.parse_args()

    result: dict = {
        "schema_version": 1,
        "metric": "wire_ingest",
        "platform": "cpu",
        "quick": bool(args.quick),
        "recorded_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    with tempfile.TemporaryDirectory() as td:
        if args.quick:
            result["throughput"] = measure_throughput(
                td, F_FLAGSHIP, buckets=20)
            result["storm"] = measure_storm(frames=48)
        else:
            result["throughput"] = measure_throughput(
                td, F_10K, buckets=120)
            # The tentpole bar: warm wire ingest must beat the
            # tailer-poll path by >=10x at the 10k-endpoint width.
            sp = result["throughput"]["speedup_vs_tailer"]
            assert sp >= 10.0, (
                f"wire speedup {sp}x < 10x vs tailer-poll at F=10240")
            result["storm"] = measure_storm()
            result["refresh_parity"] = measure_refresh_parity(td)

    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
