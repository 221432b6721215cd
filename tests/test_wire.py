"""Span-firehose wire tier (round 24, data/wire.py): framing, the
decode→sparse parity pins, the tailer-protocol integration, the shared
watermark convention, and the healthz/metrics view consistency.

The load-bearing pin is bit-parity BY PATH, not by tolerance: the wire
receiver featurizes through ``trace_columns_from_dict`` +
``sparse_from_columns`` while the tailer path walks Span objects through
``extract_sparse`` — the two must produce identical arrays for identical
traffic, and a StreamingTrainer fed either way must land on
BIT-IDENTICAL params at the refresh boundary
(test_wire_vs_tailer_training_bit_parity)."""

import json
import socket
import threading
import time

import numpy as np
import pytest

from deeprest_tpu.config import Config, FeaturizeConfig, ModelConfig, \
    TrainConfig
from deeprest_tpu.data.featurize import CallPathSpace
from deeprest_tpu.data.schema import Bucket, Span
from deeprest_tpu.data.wire import (
    F_BATCH, F_DROPPED, F_HELLO, F_WELCOME, HEADER_SIZE, MAGIC,
    MAX_FRAME_BYTES, SpanFirehoseReceiver, WireClient,
    encode_bucket_payload, pack_frame, parse_hostport, push_corpus,
    _HEADER,
)
from deeprest_tpu.workload import normal_scenario, simulate_corpus


def _corpus(buckets: int, seed: int = 0):
    scn = normal_scenario(seed)
    scn.calls_per_user = 0.4
    return simulate_corpus(scn, buckets)


def _space(capacity: int = 512) -> CallPathSpace:
    return CallPathSpace(config=FeaturizeConfig(
        hash_features=True, capacity=capacity)).freeze()


def _drain(rx, n_frames: int, deadline_s: float = 30.0) -> list:
    out, frames = [], 0
    deadline = time.monotonic() + deadline_s
    while frames < n_frames:
        got = rx.poll()
        frames += len(got)
        out.extend(got)
        if not got:
            assert time.monotonic() < deadline, \
                f"drained {frames}/{n_frames} frames before deadline"
            time.sleep(0.002)
    return out


# ---------------------------------------------------------------------------
# framing


def test_parse_hostport():
    assert parse_hostport("0.0.0.0:7070") == ("0.0.0.0", 7070)
    assert parse_hostport(":7070") == ("127.0.0.1", 7070)
    for bad in ("7070", "host:", "host:abc", ""):
        with pytest.raises(ValueError):
            parse_hostport(bad)


def test_frame_roundtrip():
    frame = pack_frame(F_BATCH, b"payload", seq=42, flags=3)
    magic, ftype, flags, length, seq = _HEADER.unpack(frame[:HEADER_SIZE])
    assert (magic, ftype, flags, seq) == (MAGIC, F_BATCH, 3, 42)
    assert frame[HEADER_SIZE:] == b"payload" and length == 7


def test_frame_rejects_oversize_payload():
    class Huge(bytes):
        def __len__(self):
            return MAX_FRAME_BYTES + 1

    with pytest.raises(ValueError):
        pack_frame(F_BATCH, Huge())


def test_encode_bucket_payload_blob_determinism():
    """Identical call trees must serialize to identical blob bytes —
    the receiver's bytes→columns memo keys on exactly these bytes, so
    any nondeterminism here silently turns every frame into a miss."""
    (b,) = _corpus(1)
    assert encode_bucket_payload(b) == encode_bucket_payload(b)
    assert encode_bucket_payload(b) == encode_bucket_payload(b.to_dict())


# ---------------------------------------------------------------------------
# decode → sparse parity (the zero-dense bit-parity pins, by construction)


def test_trace_columns_from_dict_matches_span_path():
    space = _space()
    for b in _corpus(4):
        for t in b.traces:
            got = space.trace_columns_from_dict(t.to_dict())
            ref = space._trace_columns([Span.from_dict(t.to_dict())])
            np.testing.assert_array_equal(got, ref)


def test_sparse_from_columns_matches_extract_sparse():
    space = _space()
    for b in _corpus(4):
        parts = [space.trace_columns_from_dict(t.to_dict())
                 for t in b.traces]
        got_cols, got_vals = space.sparse_from_columns(parts)
        ref_cols, ref_vals = space.extract_sparse(b.traces)
        np.testing.assert_array_equal(got_cols, ref_cols)
        np.testing.assert_array_equal(got_vals, ref_vals)


# ---------------------------------------------------------------------------
# receiver end to end


def test_wire_featurized_parity_end_to_end():
    """Push a corpus through a real socket; every drained (row, metrics)
    item must be bit-identical to what the tailer path's featurizer
    produces for the same bucket, in order."""
    corpus = _corpus(6)
    space = _space()
    ref_space = _space()
    rx = SpanFirehoseReceiver("127.0.0.1", 0, space=space).start()
    try:
        t = threading.Thread(target=push_corpus,
                             args=(rx.address, corpus), daemon=True)
        t.start()
        items = _drain(rx, len(corpus))
        t.join(timeout=10)
    finally:
        rx.close()
    assert len(items) == len(corpus)
    for (row, metrics_row), b in zip(items, corpus):
        ref_cols, ref_vals = ref_space.extract_sparse(b.traces)
        np.testing.assert_array_equal(row[0], ref_cols)
        np.testing.assert_array_equal(row[1], ref_vals)
        assert metrics_row == {m.key: m.value for m in b.metrics}
    stats = rx.stats()
    assert stats["batches"] == len(corpus)
    assert stats["dropped"] == 0
    assert stats["spans"] == sum(1 for b in corpus
                                 for tr in b.traces for _ in tr.walk())


def test_byte_stable_blobs_hit_the_bytes_to_columns_memo():
    """``encode_bucket_payload`` is byte-stable, so a corpus sent again
    finds every trace blob in the receiver's bytes→columns memo: over the
    two passes more than half the lookups are hits, the second pass is all
    hits, and a memoized row is bit for bit the row that was parsed."""
    corpus = _corpus(10)
    payloads = [encode_bucket_payload(b) for b in corpus]
    traces = sum(len(b.traces) for b in corpus)
    rx = SpanFirehoseReceiver("127.0.0.1", 0, space=_space(),
                              queue_depth=64).start()
    client = WireClient(rx.address, client_id="memo",
                        pending_limit=64).connect()
    try:
        passes = []
        for _ in range(2):
            for payload in payloads:
                client._send_batch(payload, flags=0)
            passes.append((_drain(rx, len(payloads)),
                           rx.memo_hits, rx.memo_misses))
        client.flush()
        stats = rx.stats()
    finally:
        client.close()
        rx.close()
    (cold, hits0, misses0), (warm, hits1, misses1) = passes
    assert hits0 + misses0 == traces and misses0 > 0
    assert misses1 == misses0 and hits1 - hits0 == traces
    assert stats["memo_hit_rate"] == hits1 / (hits1 + misses1) > 0.5
    assert stats["dropped"] == 0 and stats["batches"] == 2 * len(corpus)
    for (row_c, metrics_c), (row_w, metrics_w) in zip(cold, warm):
        np.testing.assert_array_equal(row_c[0], row_w[0])
        np.testing.assert_array_equal(row_c[1], row_w[1])
        assert metrics_c == metrics_w


def test_wire_dense_mode_rejected():
    with pytest.raises(ValueError):
        SpanFirehoseReceiver(space=_space(), sparse=False)


def test_wire_bucket_mode_roundtrip():
    """space=None (the VerdictIngestor's mode): frames decode back to
    schema Buckets, value-equal with what was pushed."""
    corpus = _corpus(3)
    rx = SpanFirehoseReceiver("127.0.0.1", 0).start()
    try:
        t = threading.Thread(target=push_corpus,
                             args=(rx.address, corpus), daemon=True)
        t.start()
        items = _drain(rx, len(corpus))
        t.join(timeout=10)
    finally:
        rx.close()
    assert [b.to_dict() for b in items] == [b.to_dict() for b in corpus]
    assert all(isinstance(b, Bucket) for b in items)


def test_wire_jsonl_bulk_frame_is_one_atomic_item():
    """A FLAG_JSONL bulk frame (cold-start corpus replay) rides as ONE
    sequence number and drains atomically — and its featurized rows
    match the per-bucket path bit for bit."""
    corpus = _corpus(5)
    lines = [json.dumps(b.to_dict()).encode("utf-8") for b in corpus]
    space = _space()
    ref_space = _space()
    rx = SpanFirehoseReceiver("127.0.0.1", 0, space=space).start()
    client = WireClient(rx.address, client_id="bulk").connect()
    try:
        seq = client.send_jsonl(lines)
        assert seq == 1
        items = _drain(rx, len(corpus))   # one frame, five items
    finally:
        client.close()
        rx.close()
    assert len(items) == len(corpus)
    assert rx.stats()["batches"] == 1
    for (row, _), b in zip(items, corpus):
        ref_cols, ref_vals = ref_space.extract_sparse(b.traces)
        np.testing.assert_array_equal(row[0], ref_cols)
        np.testing.assert_array_equal(row[1], ref_vals)


# ---------------------------------------------------------------------------
# deferred commit (the overlapped-ETL contract) + shed accounting


def test_poll_deferred_commit_gates_watermark_and_acks():
    """poll_deferred() must hand out items WITHOUT advancing the
    watermark or releasing ACKs — only commit(token) does, once the
    caller has the rows in the ring.  This is what keeps the overlapped
    ETL loop's checkpoint cuts honest: a persisted watermark can never
    cover a frame still waiting in the featurize queue."""
    corpus = _corpus(3)
    rx = SpanFirehoseReceiver("127.0.0.1", 0, space=_space()).start()
    client = WireClient(rx.address, client_id="defer").connect()
    try:
        for b in corpus:
            client.send_bucket(b)
        deadline = time.monotonic() + 30
        while rx.stats()["batches"] < 3:
            assert time.monotonic() < deadline, rx.stats()
            time.sleep(0.002)
        items, token = rx.poll_deferred()
        assert len(items) == 3
        assert rx.ingest_watermark()["clients"].get("defer", 0) == 0
        # nothing is ACKed yet either: a flush cannot complete
        assert client.flush(timeout_s=0.3) is False
        rx.commit(token)
        assert rx.ingest_watermark()["clients"]["defer"] == 3
        assert client.flush(timeout_s=10)
        assert client.acked == 3
        assert rx.stats()["p99_ingest_s"] is not None
    finally:
        client.close()
        rx.close()


def test_dropped_notice_prunes_only_named_seqs():
    """A DROPPED notice names the exact shed seqs; the client must keep
    every other pending frame replayable — pruning a range would also
    discard accepted-but-uncommitted frames, unrecoverable if the
    receiver dies before committing them."""
    client = WireClient(("127.0.0.1", 1))
    client._pending = {1: (0, b"a"), 2: (0, b"b"), 3: (0, b"c")}
    client._handle(F_DROPPED, 0, json.dumps(
        {"seqs": [2], "count": 1}).encode("utf-8"))
    assert sorted(client._pending) == [1, 3]
    assert client.server_dropped == 1


def test_malformed_frame_counted_once_and_announced():
    """A frame that fails decode lands in the accounting exactly once
    (the dropped aggregate already includes malformed_total), and its
    seq is announced via DROPPED so the sender can prune it instead of
    retrying a frame that can never decode."""
    rx = SpanFirehoseReceiver("127.0.0.1", 0, space=_space()).start()
    try:
        s = socket.create_connection(rx.address, timeout=5)
        s.sendall(pack_frame(F_HELLO, b"{}"))
        hdr = s.recv(HEADER_SIZE, socket.MSG_WAITALL)
        magic, ftype, _, length, _ = _HEADER.unpack(hdr)
        assert (magic, ftype) == (MAGIC, F_WELCOME)
        if length:
            s.recv(length, socket.MSG_WAITALL)
        # valid framing, garbage sub-framing: decode raises, conn lives
        s.sendall(pack_frame(F_BATCH, b"\x00\x00\x00\x02{}", seq=1))
        deadline = time.monotonic() + 10
        while rx.stats()["dropped"] < 1:
            assert time.monotonic() < deadline, rx.stats()
            time.sleep(0.005)
        assert rx.stats()["dropped"] == 1       # once, not double
        hdr = s.recv(HEADER_SIZE, socket.MSG_WAITALL)
        magic, ftype, _, length, _ = _HEADER.unpack(hdr)
        assert (magic, ftype) == (MAGIC, F_DROPPED)
        meta = json.loads(s.recv(length, socket.MSG_WAITALL))
        assert meta["seqs"] == [1]
        s.close()
    finally:
        rx.close()


def test_stalled_receiver_bounds_client_pending_with_shed_accounting():
    """A receiver that accepts frames but never commits sends no ACKs;
    the client's pending window must stay bounded anyway — the ACK wait
    times out and sheds the oldest frames with accounting, instead of
    one full timeout per send on top of unbounded growth."""
    (bucket,) = _corpus(1)
    rx = SpanFirehoseReceiver("127.0.0.1", 0, space=_space()).start()
    client = WireClient(rx.address, client_id="stall",
                        pending_limit=4, timeout_s=0.1).connect()
    try:
        for _ in range(12):
            client.send_bucket(bucket)
        assert client.timeout_shed > 0
        assert len(client._pending) <= client.pending_limit + 1
    finally:
        client.close()
        rx.close()


def test_stats_concurrent_with_commit_never_raises():
    """stats() (the /healthz path) and the committing thread touch the
    same latency deque; iterating it off-lock raises RuntimeError
    ('deque mutated during iteration') under load.  Hammer stats()
    while draining a pushed corpus — no exception may escape."""
    corpus = _corpus(4) * 50
    rx = SpanFirehoseReceiver("127.0.0.1", 0, space=_space()).start()
    errs: list = []
    stop = threading.Event()

    def hammer():
        try:
            while not stop.is_set():
                rx.stats()
        except Exception as exc:               # pragma: no cover
            errs.append(exc)

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    try:
        pusher = threading.Thread(target=push_corpus,
                                  args=(rx.address, corpus),
                                  daemon=True)
        pusher.start()
        _drain(rx, len(corpus))
        pusher.join(timeout=30)
    finally:
        stop.set()
        t.join(timeout=10)
        rx.close()
    assert not errs, errs


# ---------------------------------------------------------------------------
# watermark convention (shared with LiveEndpointTailer — satellite 6)


def _raw_batch(sock, payload: bytes, seq: int) -> None:
    sock.sendall(pack_frame(F_BATCH, payload, seq=seq))


def test_watermark_resume_dedups_replayed_frames():
    """A restarted receiver handed the sidecar watermark must dedup a
    client's replay of already-committed frames instead of
    double-counting their spans."""
    corpus = _corpus(3)
    payloads = [encode_bucket_payload(b) for b in corpus]

    rx1 = SpanFirehoseReceiver("127.0.0.1", 0, space=_space()).start()
    try:
        c = WireClient(rx1.address, client_id="replayer").connect()
        for pl in payloads:
            c._send_batch(pl, flags=0)
        _drain(rx1, len(payloads))        # commits seqs 1..3
        wm = rx1.ingest_watermark()
        c.close()
    finally:
        rx1.close()
    assert wm["kind"] == "wire_seq"
    assert wm["clients"]["replayer"] == len(payloads)

    rx2 = SpanFirehoseReceiver("127.0.0.1", 0, space=_space()).start()
    rx2.resume_from(wm)
    try:
        # A well-behaved client learns the watermark from WELCOME, but a
        # crashed one may replay blind — speak the raw protocol and
        # resend the committed seqs, then one genuinely new frame.
        s = socket.create_connection(rx2.address, timeout=5)
        s.sendall(pack_frame(F_HELLO, json.dumps(
            {"client": "replayer"}).encode("utf-8")))
        hdr = s.recv(HEADER_SIZE, socket.MSG_WAITALL)
        magic, ftype, _, length, _ = _HEADER.unpack(hdr)
        assert (magic, ftype) == (MAGIC, F_WELCOME)
        welcome = json.loads(s.recv(length, socket.MSG_WAITALL))
        assert welcome["watermark"] == len(payloads)
        for seq, pl in enumerate(payloads, start=1):
            _raw_batch(s, pl, seq)                      # pure replay
        _raw_batch(s, payloads[0], len(payloads) + 1)   # genuinely new
        items = _drain(rx2, 1)
        deadline = time.monotonic() + 10
        while rx2.stats()["duplicates"] < len(payloads):
            assert time.monotonic() < deadline, rx2.stats()
            time.sleep(0.005)
        s.close()
        stats = rx2.stats()
    finally:
        rx2.close()
    assert len(items) == 1                # only the new frame drained
    assert stats["duplicates"] == len(payloads)
    assert stats["batches"] == 1
    assert rx2.ingest_watermark()["clients"]["replayer"] \
        == len(payloads) + 1


def test_watermark_resume_ignores_foreign_kinds():
    rx = SpanFirehoseReceiver("127.0.0.1", 0, space=_space())
    rx.resume_from({"kind": "time_cursor", "position": 123.0})
    rx.resume_from({"kind": "wire_seq", "clients": {"a": "junk"}})
    rx.resume_from("nonsense")
    assert rx.ingest_watermark() == {"kind": "wire_seq", "clients": {}}


def test_live_tailer_shares_the_watermark_convention():
    """LiveEndpointTailer speaks the same ingest_watermark/resume_from
    protocol with its own kind tag, so the stream sidecar can persist
    either source's cursor through one code path."""
    from deeprest_tpu.data.ingest import LiveEndpointTailer

    t = LiveEndpointTailer("http://127.0.0.1:1/api", bucket_s=5.0)
    wm = t.ingest_watermark()
    assert wm["kind"] == "time_cursor"
    t2 = LiveEndpointTailer("http://127.0.0.1:1/api", bucket_s=5.0)
    t2.resume_from(wm)
    assert t2.ingest_watermark() == wm
    # foreign kinds are ignored, never adopted as a cursor
    before = t2.ingest_watermark()
    t2.resume_from({"kind": "wire_seq", "clients": {"x": 9}})
    assert t2.ingest_watermark() == before


# ---------------------------------------------------------------------------
# training integration: wire-fed ≡ tailer-fed, bit for bit (tier-1 pin)


def _tiny_config(capacity: int = 64) -> Config:
    return Config(
        model=ModelConfig(feature_dim=capacity, hidden_size=4),
        train=TrainConfig(batch_size=4, window_size=4, seed=0,
                          sparse_feed=True, eval_stride=1,
                          eval_max_cycles=1, log_every_steps=0),
    )


def test_wire_vs_tailer_training_bit_parity(tmp_path):
    """The acceptance pin: one refresh trained from wire-pushed frames
    lands on params BIT-IDENTICAL to the same corpus through the file
    tailer — and the wire side's sidecar carries the wire_seq watermark
    so a restarted stream resumes without double-counting."""
    from deeprest_tpu.data.schema import save_raw_data_jsonl
    from deeprest_tpu.train.stream import (
        BucketTailer, StreamConfig, StreamingTrainer,
    )
    import jax

    corpus = _corpus(12, seed=3)
    path = tmp_path / "wire_parity.jsonl"
    save_raw_data_jsonl(corpus, str(path))

    def make_st(ckpt_dir=None):
        return StreamingTrainer(
            _tiny_config(), StreamConfig(refresh_buckets=12,
                                         finetune_epochs=1,
                                         eval_holdout=2,
                                         poll_interval_s=0.01),
            ckpt_dir=ckpt_dir,
            feature_config=FeaturizeConfig(hash_features=True,
                                           capacity=64))

    st_file = make_st()
    tailer = BucketTailer(str(path))
    results_file = list(st_file.run(tailer, max_refreshes=1,
                                    deadline_s=300))
    tailer.close()

    st_wire = make_st(ckpt_dir=str(tmp_path / "ckpt"))
    rx = SpanFirehoseReceiver("127.0.0.1", 0, space=st_wire.space).start()
    pusher = threading.Thread(
        target=push_corpus, args=(rx.address, corpus),
        kwargs={"client_id": "parity"}, daemon=True)
    pusher.start()
    try:
        results_wire = list(st_wire.run(rx, max_refreshes=1,
                                        deadline_s=300))
        pusher.join(timeout=10)
    finally:
        rx.close()

    assert len(results_file) == len(results_wire) == 1
    assert results_file[0].eval_loss == results_wire[0].eval_loss
    ref = jax.tree_util.tree_leaves(st_file.state.params)
    got = jax.tree_util.tree_leaves(st_wire.state.params)
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)

    # the sidecar persisted the wire source's committed-seq watermark
    from deeprest_tpu.train.checkpoint import load_sidecar

    assert results_wire[0].checkpoint_path is not None
    sidecar = load_sidecar(str(tmp_path / "ckpt"))
    src = sidecar["stream_ring_watermark"]["source"]
    assert src["kind"] == "wire_seq"
    assert src["clients"]["parity"] == len(corpus)


# ---------------------------------------------------------------------------
# observability: /healthz and /metrics see the same accounting


def test_healthz_and_metrics_views_are_consistent():
    from deeprest_tpu.obs import metrics as obs_metrics
    from deeprest_tpu.serve.server import PredictionService

    class _StubPredictor:
        metric_names = ["comp0_cpu"]
        window_size = 4

    corpus = _corpus(4)
    rx = SpanFirehoseReceiver("127.0.0.1", 0, space=_space()).start()
    svc = PredictionService(_StubPredictor(), None, backend="stub")
    svc.attach_wire(rx)
    try:
        t = threading.Thread(target=push_corpus,
                             args=(rx.address, corpus), daemon=True)
        t.start()
        _drain(rx, len(corpus))           # poll() delta-flushes the registry
        t.join(timeout=10)
        health = svc.healthz()
    finally:
        rx.close()

    wire = health["wire"]
    assert wire["batches"] == len(corpus)
    assert wire["spans"] == sum(1 for b in corpus
                                for tr in b.traces for _ in tr.walk())
    assert wire["dropped"] == 0
    # the registry's counters carry the same totals under the
    # deeprest_wire_* names the /metrics endpoint renders
    text = obs_metrics.REGISTRY.render()
    for key, name in (("spans", "deeprest_wire_spans_total"),
                      ("batches", "deeprest_wire_batches_total")):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(name + " ") or ln == name)
        assert float(line.split()[-1]) >= wire[key]
    assert "deeprest_wire_connections" in text
