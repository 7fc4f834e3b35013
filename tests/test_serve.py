"""Resident serving tier (ISSUE 11, drep_tpu/serve/): the acceptance
contract.

- concurrent classify against a running daemon returns verdicts
  IDENTICAL to one-shot `index classify` (LSH prune on and off),
  coalesced into fewer rect dispatches than clients, with zero writes
  under the index directory;
- a mid-flight generation publish is adopted without dropping or
  misclassifying any in-flight request, every verdict stamped with the
  generation that produced it;
- bounded admission: a full queue (or a draining daemon) refuses
  immediately with a retry_after hint;
- SIGTERM drains gracefully (exit 0); SIGKILL mid-batch gives clients a
  clean error, a restart serves the same generation, the index is
  untouched (the chaos_matrix --serve cells).
"""

import io
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _index_testlib as lib  # noqa: E402

from drep_tpu.index import (  # noqa: E402
    build_from_paths,
    classify_batch,
    index_classify,
    index_update,
    load_resident_index,
    sketch_queries,
)
from drep_tpu.serve import (  # noqa: E402
    AdmissionQueue,
    IndexServer,
    PendingRequest,
    ServeClient,
    ServeConfig,
    ServeError,
)
from drep_tpu.serve import protocol  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool(name: str):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"_serve_test_{name}", os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---- protocol + batcher units ---------------------------------------------


def test_protocol_roundtrip_and_errors():
    req = protocol.parse_request(b'{"op": "classify", "genome": "/x/a.fa", "id": 7}')
    assert req["genome"] == "/x/a.fa" and req["id"] == 7
    for bad in (b"not json", b'"str"', b'{"op": "nope"}', b'{"op": "classify"}'):
        with pytest.raises(protocol.ProtocolError):
            protocol.parse_request(bad)
    resp = protocol.error_response(
        "full", req_id=7, reason="backpressure", retry_after_s=0.05
    )
    assert resp == {"ok": False, "error": "full", "id": 7,
                    "reason": "backpressure", "retry_after_s": 0.05}
    # HTTP shim mapping
    assert protocol.http_to_request("GET", "/healthz", b"") == {"op": "status"}
    creq = protocol.http_to_request("POST", "/classify", b'{"genome": "/x.fa"}')
    assert creq["op"] == "classify" and creq["genome"] == "/x.fa"
    with pytest.raises(protocol.ProtocolError, match="no route"):
        protocol.http_to_request("GET", "/nope", b"")


def test_admission_queue_batches_backpressure_and_basename_deferral():
    q = AdmissionQueue(max_queue=3)
    got: list = []

    def mk(path):
        return PendingRequest(genome=path, reply=got.append)

    assert q.submit(mk("/a/x.fa")) is None
    assert q.submit(mk("/a/y.fa")) is None
    # same basename, DIFFERENT path: admitted, but never in one batch
    assert q.submit(mk("/b/x.fa")) is None
    assert q.submit(mk("/c/z.fa")) == "backpressure"
    batch = q.next_batch(max_batch=8, window_s=0.0)
    assert [r.genome for r in batch] == ["/a/x.fa", "/a/y.fa"]
    batch2 = q.next_batch(max_batch=8, window_s=0.0)
    assert [r.genome for r in batch2] == ["/b/x.fa"]
    # identical path twice shares one batch (the daemon fans out)
    assert q.submit(mk("/a/x.fa")) is None
    assert q.submit(mk("/a/x.fa")) is None
    assert len(q.next_batch(8, 0.0)) == 2
    # drain: refuse new, signal exhaustion with None
    q.drain()
    assert q.submit(mk("/d/w.fa")) == "draining"
    assert q.next_batch(8, 0.0) is None


def test_histogram_percentiles_report_and_prom():
    from drep_tpu.utils.profiling import Counters, Histogram, prom_text

    h = Histogram(size=100)
    for v in range(1, 1001):
        h.observe(float(v))
    assert h.count == 1000
    # window keeps the LAST 100 observations (901..1000)
    s = h.summary()
    assert 940 <= s["p50"] <= 960 and s["max"] == 1000.0 and s["count"] == 1000
    c = Counters()
    c.observe("serve_request_ms", 5.0)
    c.observe("serve_request_ms", 15.0)
    rep = c.report()
    assert rep["histograms"]["serve_request_ms"]["count"] == 2
    text = prom_text(c)
    assert 'drep_tpu_latency{name="serve_request_ms",stat="p99"}' in text
    c.reset()
    assert not c.hists


# ---- deadline budgets + wire CRC units (ISSUE 19) --------------------------


def test_wire_crc_seal_check_unseal(monkeypatch):
    """The per-line CRC frame contract: seal embeds CRC-32 of the bare
    payload as the last key, check_crc verifies+strips it, a flipped
    byte classifies as WireCorruption (detected, never merged), and
    frames WITHOUT a crc pass through untouched — mixed fleets and the
    DREP_TPU_WIRE_CRC=0 escape hatch interoperate."""
    obj = {"ok": True, "id": "ab12", "verdict": {"genome": "q.fa"}}
    line = protocol.seal(obj)
    assert line.endswith(b"}\n") and b',"crc":' in line
    assert protocol.unseal(line) == obj
    # round-trip through check_crc yields the bare (crc-stripped) frame
    assert json.loads(protocol.check_crc(line)) == obj
    # one flipped byte inside the body: detected, classified
    pos = line.index(b"ab12")
    garbled = line[:pos] + b"xb12" + line[pos + 4:]
    with pytest.raises(protocol.WireCorruption):
        protocol.check_crc(garbled)
    # crc-less frames pass through (the mixed-fleet contract)
    bare = protocol.encode(obj)
    assert protocol.unseal(bare) == obj
    # non-JSON / non-object frames classify as wire damage too
    for junk in (b"not json\n", b'"just a string"\n'):
        with pytest.raises(protocol.WireCorruption):
            protocol.unseal(junk)
    # the escape hatch: CRC off -> seal degenerates to plain encode
    monkeypatch.setenv("DREP_TPU_WIRE_CRC", "0")
    assert protocol.seal(obj) == bare


def test_deadline_and_cancel_wire_validation():
    """deadline_ms is a positive JSON number wherever it rides (the
    bool guard matters: True is an int to Python and a 1 ms budget
    would shed everything); cancel needs the id of a prior request."""
    req = protocol.parse_request(
        b'{"op": "classify", "genome": "/x.fa", "deadline_ms": 250.5}'
    )
    assert req["deadline_ms"] == 250.5
    assert protocol.parse_request(b'{"op": "cancel", "id": "ab12"}')["id"] == "ab12"
    for bad in (
        b'{"op": "classify", "genome": "/x.fa", "deadline_ms": true}',
        b'{"op": "classify", "genome": "/x.fa", "deadline_ms": 0}',
        b'{"op": "classify", "genome": "/x.fa", "deadline_ms": -5}',
        b'{"op": "classify", "genome": "/x.fa", "deadline_ms": "soon"}',
        b'{"op": "cancel"}',
        b'{"op": "cancel", "id": ""}',
        b'{"op": "cancel", "id": 7}',
    ):
        with pytest.raises(protocol.ProtocolError):
            protocol.parse_request(bad)


def test_queue_eta_histogram_rule():
    """The histogram-ETA shed rule, pinned: batches ahead (depth /
    capacity, plus the one you join) times window + recent MEDIAN batch
    wall; before any batch has run the window alone is the estimate."""
    from drep_tpu.serve.batcher import queue_eta_s
    from drep_tpu.utils.profiling import Histogram

    assert queue_eta_s(0, 8, 0.05) == pytest.approx(0.05)
    assert queue_eta_s(16, 8, 0.05) == pytest.approx(3 * 0.05)
    assert queue_eta_s(0, 1, 0.0) == 0.0
    h = Histogram(size=32)
    for ms in (100.0, 200.0, 300.0):
        h.observe(ms)
    assert queue_eta_s(0, 8, 0.05, h) == pytest.approx(0.05 + 0.2)
    assert queue_eta_s(16, 8, 0.05, h) == pytest.approx(3 * (0.05 + 0.2))


def test_batcher_sheds_expired_before_membership_and_cancels_queued():
    """An entry whose budget expired in queue is shed via on_shed
    strictly BEFORE batch membership (it can never reach the rect
    compare); cancel removes a still-queued entry by id."""
    shed: list = []
    q = AdmissionQueue(max_queue=8, on_shed=shed.append)
    now = time.monotonic()
    expired1 = PendingRequest(genome="/a/x.fa", reply=lambda r: None,
                              req_id="e1", deadline=now - 0.5)
    expired2 = PendingRequest(genome="/a/y.fa", reply=lambda r: None,
                              req_id="e2", deadline=now - 0.1)
    fresh = PendingRequest(genome="/a/z.fa", reply=lambda r: None,
                           req_id="f1", deadline=now + 60.0)
    for r in (expired1, expired2, fresh):
        assert q.submit(r) is None
    batch = q.next_batch(max_batch=8, window_s=0.0)
    assert [r.req_id for r in batch] == ["f1"]
    assert [r.req_id for r in shed] == ["e1", "e2"]
    # no deadline = unbounded (the daemon stamps the default knob)
    assert not PendingRequest(genome="/a", reply=lambda r: None).expired()
    # cancel: removes the queued entry once, unknown/None ids are no-ops
    victim = PendingRequest(genome="/a/w.fa", reply=lambda r: None, req_id="v")
    assert q.submit(victim) is None
    assert q.cancel("v") is victim
    assert q.cancel("v") is None
    assert q.cancel("ghost") is None
    assert q.cancel(None) is None
    assert q.depth() == 0


def test_serve_deadline_and_wire_knobs():
    """The ISSUE 19 serve knobs are declared (the drep-lint env-knob
    contract): the legacy-client default budget and the CRC gate."""
    from drep_tpu.utils import envknobs

    assert envknobs.knob("DREP_TPU_SERVE_DEADLINE_DEFAULT_MS").kind == "float"
    assert envknobs.env_float("DREP_TPU_SERVE_DEADLINE_DEFAULT_MS") == 30000.0
    assert envknobs.knob("DREP_TPU_WIRE_CRC").kind == "bool"
    assert envknobs.env_bool("DREP_TPU_WIRE_CRC") is True


# ---- the resident-core refactor -------------------------------------------


@pytest.fixture(scope="module")
def serve_index(tmp_path_factory):
    """One small structured index (3 groups so LSH pruning has tiles to
    skip) + disjoint query genomes, shared by the serving tests."""
    td = tmp_path_factory.mktemp("serve_idx")
    paths = lib.write_genome_set(str(td / "g"), [4, 4, 4], seed=5)
    loc = str(td / "idx")
    build_from_paths(loc, paths, length=0, streaming_block=4)
    queries = [paths[1], paths[5]] + lib.write_genome_set(
        str(td / "q"), [1], seed=77, prefix="novel"
    )
    return loc, queries


def test_classify_batch_independent_equals_oneshot(serve_index):
    """classify_batch(joint=False) — the daemon's assembly mode — must
    answer each query of a coalesced batch EXACTLY like a one-shot
    single-query classify, for one rect compare, without mutating the
    resident index, LSH prune on and off."""
    loc, queries = serve_index
    oneshot = {q: index_classify(loc, [q])[0] for q in queries}
    digest = lib.tree_digest(loc, exclude_dirs=())
    resident = load_resident_index(loc)
    gen0 = resident.generation
    for prune in ({"primary_prune": "off"}, {"primary_prune": "lsh"}):
        sq = sketch_queries(resident, queries)
        got = classify_batch(resident, sq, prune_cfg=prune, joint=False)
        assert [v["genome"] for v in got] == [os.path.basename(q) for q in queries]
        for q, v in zip(queries, got):
            assert v == oneshot[q], (prune, q)
        assert v["generation"] == gen0  # stamped with its generation
        # the resident index is untouched: same object answers again
        assert resident.n == 12 and resident.generation == gen0
    # joint mode (the CLI's multi-genome semantics) still matches the
    # one-shot multi-genome call byte-for-byte
    sq = sketch_queries(resident, queries)
    joint = classify_batch(resident, sq, joint=True)
    assert joint == index_classify(loc, queries)
    assert lib.tree_digest(loc, exclude_dirs=()) == digest  # zero writes


def test_device_resident_sketch_matrix_uploads_once(serve_index, monkeypatch):
    """The serve fast path keeps the resident sketch matrix
    device-resident ACROSS batches: exactly one upload per generation
    (counter-pinned — no per-batch re-upload), verdicts byte-identical
    to one-shot classify, a hot-swapped generation costs exactly one
    more upload, and pinning the knob off reproduces the same verdicts
    through the classic per-batch repack."""
    from drep_tpu.index import resident_device
    from drep_tpu.utils.profiling import counters

    loc, queries = serve_index
    resident_device.reset_for_tests()
    resident = load_resident_index(loc)
    oneshot = {q: index_classify(loc, [q])[0] for q in queries}
    for _ in range(3):
        sq = sketch_queries(resident, queries)
        got = classify_batch(resident, sq, joint=False)
        for q, v in zip(queries, got):
            assert v == oneshot[q]
    assert resident_device.upload_count() == 1, "re-uploaded per batch"
    assert resident_device.fallback_count() == 0
    assert counters.gauges.get("serve_resident_uploads") == 1.0
    # a generation hot-swap installs a FRESH resident object — the
    # daemon prewarms it: exactly one more upload, batches reuse it
    fresh = load_resident_index(loc)
    assert resident_device.prewarm_resident(fresh)
    assert resident_device.upload_count() == 2
    sq = sketch_queries(fresh, queries)
    got = classify_batch(fresh, sq, joint=False)
    for q, v in zip(queries, got):
        assert v == oneshot[q]
    assert resident_device.upload_count() == 2
    # knob off => classic union repack, byte-identical verdicts
    monkeypatch.setenv("DREP_TPU_SERVE_DEVICE_RESIDENT", "0")
    sq = sketch_queries(resident, queries)
    got = classify_batch(resident, sq, joint=False)
    for q, v in zip(queries, got):
        assert v == oneshot[q]
    assert resident_device.upload_count() == 2


# ---- the daemon -----------------------------------------------------------


def _start_server(loc, **over):
    classify_fn = over.pop("classify_fn", None)
    kw = {"batch_window_ms": 200.0, "max_batch": 16, "poll_generation_s": 0.1}
    kw.update(over)
    cfg = ServeConfig(index_loc=loc, **kw)
    srv = IndexServer(cfg, classify_fn=classify_fn)
    addr = srv.start()
    t = threading.Thread(target=srv.serve_batches, daemon=True)
    t.start()
    return srv, addr, t


def _stop_server(srv, t):
    srv.request_drain()
    t.join(timeout=30)
    srv.close()
    assert not t.is_alive()


@pytest.mark.parametrize("prune", ["off", "lsh"])
def test_concurrent_clients_match_oneshot_fewer_dispatches(serve_index, prune):
    """The acceptance cell: 3 concurrent clients against one daemon get
    verdicts identical to one-shot classify (prune on and off), the
    requests coalesce into FEWER rect dispatches than clients (counter-
    asserted), and the index directory is byte-for-byte unwritten."""
    from drep_tpu.utils.profiling import counters

    loc, queries = serve_index
    oneshot = {q: index_classify(loc, [q])[0] for q in queries}
    digest = lib.tree_digest(loc, exclude_dirs=())
    counters.reset()
    srv, addr, t = _start_server(
        loc, prune_cfg={"primary_prune": prune}
    )
    try:
        results: dict[str, dict] = {}
        errors: list = []
        barrier = threading.Barrier(len(queries))

        def one(q):
            try:
                with ServeClient(addr) as c:
                    barrier.wait()
                    results[q] = c.classify(q)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=one, args=(q,)) for q in queries]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not errors
        for q in queries:
            assert results[q]["verdict"] == oneshot[q], q
        # coalesced: fewer batches than clients, and the serve_batch
        # counter agrees with the server's own accounting
        assert srv.stats.batches_total < len(queries)
        st = counters.stages.get("serve_batch")
        assert st is not None and st.calls == srv.stats.batches_total
        assert max(r["batch_size"] for r in results.values()) >= 2
    finally:
        _stop_server(srv, t)
    assert lib.tree_digest(loc, exclude_dirs=()) == digest  # pure reader


def test_status_snapshot_and_http_shim(serve_index):
    import urllib.request

    loc, queries = serve_index
    srv, addr, t = _start_server(loc, batch_window_ms=1.0)
    try:
        with ServeClient(addr) as c:
            r = c.classify(queries[0])
            assert r["ok"] and r["verdict"]["genome"] == os.path.basename(queries[0])
            st = c.status()
        assert st["generation"] == 0 and st["n_genomes"] == 12
        assert st["requests_total"] == 1 and st["batches_total"] == 1
        assert st["latency_ms"]["serve_request_ms"]["count"] >= 1
        # the HTTP shim serves the SAME snapshot + classify
        with urllib.request.urlopen(f"http://{addr}/healthz", timeout=30) as resp:
            health = json.loads(resp.read())
        assert health["generation"] == 0 and health["n_genomes"] == 12
        body = json.dumps({"genome": queries[1]}).encode()
        req = urllib.request.Request(
            f"http://{addr}/classify", data=body, method="POST"
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            doc = json.loads(resp.read())
        assert doc["ok"] and doc["verdict"] == index_classify(loc, [queries[1]])[0]
    finally:
        _stop_server(srv, t)


def test_hot_swap_generation_mid_stream(tmp_path):
    """Build gen 0, serve, publish gen 1 mid-stream of queries: no
    request is dropped or misclassified — every verdict matches a
    one-shot classify against the generation it is STAMPED with, and
    the swap is adopted without a restart."""
    paths = lib.write_genome_set(str(tmp_path / "g"), [3, 2], seed=5)
    extra = lib.write_genome_set(str(tmp_path / "x"), [1], seed=31, prefix="x")
    queries = lib.write_genome_set(str(tmp_path / "q"), [2], seed=77, prefix="q")
    loc = str(tmp_path / "idx")
    build_from_paths(loc, paths[:4], length=0)
    frozen = str(tmp_path / "idx_gen0")
    shutil.copytree(loc, frozen)

    srv, addr, t = _start_server(loc, batch_window_ms=1.0)
    responses: list[dict] = []
    stop = threading.Event()
    errors: list = []

    def stream():
        try:
            with ServeClient(addr) as c:
                i = 0
                while not stop.is_set():
                    responses.append(c.classify(queries[i % len(queries)]))
                    i += 1
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    streamer = threading.Thread(target=stream, daemon=True)
    streamer.start()
    try:
        deadline = time.monotonic() + 60
        while not responses and time.monotonic() < deadline:
            time.sleep(0.01)
        # publish generation 1 mid-stream (paths[4] joins group 1)
        index_update(loc, [paths[4]])
        digest_after_update = lib.tree_digest(loc, exclude_dirs=())
        while time.monotonic() < deadline:
            if any(r["generation"] == 1 for r in responses):
                break
            time.sleep(0.05)
        stop.set()
        streamer.join(timeout=60)
        assert not errors
        gens = {r["generation"] for r in responses}
        assert gens == {0, 1}, gens  # served across the swap, stamped
        assert srv.stats.swaps_total == 1
        # in-flight requests all answered, none misclassified: each
        # verdict equals the one-shot answer AT ITS OWN GENERATION
        oracle = {
            (0, q): index_classify(frozen, [q])[0] for q in queries
        } | {
            (1, q): index_classify(loc, [q])[0] for q in queries
        }
        by_name = {os.path.basename(q): q for q in queries}
        for r in responses:
            q = by_name[r["verdict"]["genome"]]
            want = dict(oracle[(r["generation"], q)])
            # the frozen-dir oracle reports its own location-independent
            # verdict; generation stamps must still agree
            assert r["verdict"] == want, (r["generation"], q)
        # a query against the new genome resolves post-swap
        with ServeClient(addr) as c:
            r = c.classify(extra[0])
        assert r["generation"] == 1
        assert r["verdict"] == index_classify(loc, [extra[0]])[0]
    finally:
        stop.set()
        _stop_server(srv, t)
    # the SERVER wrote nothing: the index bytes are exactly what the
    # update published
    assert lib.tree_digest(loc, exclude_dirs=()) == digest_after_update


def test_backpressure_and_drain_refusals(serve_index):
    """A full admission queue refuses IMMEDIATELY with retry_after_s;
    a draining daemon refuses with reason=draining; admitted requests
    still answer."""
    loc, _queries = serve_index
    started = threading.Event()

    def slow_classify(resident, paths):
        started.set()
        time.sleep(0.4)
        return {
            os.path.basename(p): {"genome": os.path.basename(p),
                                  "generation": int(resident.generation)}
            for p in paths
        }

    cfg = ServeConfig(index_loc=loc, max_queue=2, max_batch=1,
                      batch_window_ms=0.0, poll_generation_s=60.0)
    srv = IndexServer(cfg, classify_fn=slow_classify)
    addr = srv.start()
    t = threading.Thread(target=srv.serve_batches, daemon=True)
    t.start()
    try:
        fake = [os.path.join(loc, "manifest.json")] * 5  # any readable file
        first_resp: list = []
        opener = threading.Thread(
            target=lambda: first_resp.extend(
                ServeClient(addr, timeout_s=60).classify_many(fake[:1])
            ),
            daemon=True,
        )
        # request 1 occupies the (slow) batch loop; with the loop
        # provably busy, 2 more fill the queue and 2 bounce immediately
        # with the backoff hint — fully deterministic
        opener.start()
        assert started.wait(timeout=30)
        with ServeClient(addr, timeout_s=60) as c:
            resps = c.classify_many(fake[1:])
        opener.join(timeout=30)
        ok = [r for r in first_resp + resps if r.get("ok")]
        refused = [r for r in first_resp + resps if not r.get("ok")]
        assert len(ok) == 3 and len(refused) == 2, (first_resp, resps)
        for r in refused:
            assert r["reason"] == "backpressure" and r["retry_after_s"] > 0
        assert srv.stats.rejected_total == 2
        # drain: new admissions refused with the drain reason
        srv.request_drain()
        with pytest.raises((ServeError, OSError)) as ei:
            with ServeClient(addr, timeout_s=10) as c2:
                c2.classify(fake[0])
        if isinstance(ei.value, ServeError):
            assert ei.value.reason in ("draining", "disconnected")
    finally:
        srv.queue.drain()
        t.join(timeout=30)
        srv.close()


def test_daemon_deadline_shed_cancel_and_eta_refusal(serve_index):
    """ISSUE 19 end-to-end: a request whose budget expires in queue is
    NEVER dispatched (shed strictly before batch membership, answered
    with an honest stamped refusal + the histogram-ETA retry hint); a
    cancel drops a queued entry without a dispatch and its connection
    gets the terminal ``cancelled`` refusal; and once the batch
    histogram knows the real batch wall, a budget below the queue ETA
    is refused AT ADMISSION — no queue time burned."""
    from drep_tpu.utils.profiling import counters

    loc, _queries = serve_index
    started = threading.Event()
    release = threading.Event()
    dispatched: list[str] = []

    def gated_classify(resident, paths):
        dispatched.extend(os.path.basename(p) for p in paths)
        started.set()
        release.wait(timeout=30)
        return {
            os.path.basename(p): {"genome": os.path.basename(p),
                                  "generation": int(resident.generation)}
            for p in paths
        }

    counters.reset()  # fresh serve_batch_ms histogram: ETA = window only
    cfg = ServeConfig(index_loc=loc, max_queue=8, max_batch=1,
                      batch_window_ms=0.0, poll_generation_s=60.0)
    srv = IndexServer(cfg, classify_fn=gated_classify)
    addr = srv.start()
    t = threading.Thread(target=srv.serve_batches, daemon=True)
    t.start()
    try:
        blocker = os.path.join(loc, "manifest.json")  # any readable file
        opener = threading.Thread(
            target=lambda: ServeClient(addr, timeout_s=60).classify(blocker),
            daemon=True,
        )
        opener.start()
        assert started.wait(timeout=30)  # the batch loop is provably held
        with ServeClient(addr, timeout_s=60) as c:
            c._send({"op": "classify", "genome": blocker, "id": "victim",
                     "deadline_ms": 100})
            c._send({"op": "classify", "genome": blocker, "id": "v2"})
            deadline = time.monotonic() + 30
            while srv.queue.depth() < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert srv.queue.depth() == 2
            with ServeClient(addr, timeout_s=30) as c2:
                assert c2.cancel("v2") is True  # dropped still-queued
                assert c2.cancel("ghost") is False  # in-flight flag path
            gone = c._recv_for("v2")
            assert not gone["ok"] and gone["reason"] == "cancelled"
            time.sleep(0.25)  # victim's 100 ms budget burns in queue
            release.set()  # loop frees, pops victim -> expired -> shed
            shed = c._recv_for("victim")
            assert not shed["ok"] and shed["reason"] == "deadline_exceeded"
            assert shed["retry_after_s"] > 0
        opener.join(timeout=60)
        # neither the shed nor the cancelled request ever reached the
        # classify_fn: only the blocker dispatched, exactly once
        assert dispatched == ["manifest.json"]
        assert srv.stats.deadline_shed == 1 and srv.stats.cancels == 1
        snap = srv.snapshot()
        assert snap["deadline_shed"] == 1 and snap["cancels"] == 1
        # the histogram now knows batches take ~250 ms+, so a 10 ms
        # budget is refused up front with the stamped reason (whether
        # the refusal lands before or after the client's own local
        # budget check, the error is the same honest classification)
        with pytest.raises(ServeError) as ei:
            with ServeClient(addr, timeout_s=30) as c3:
                c3.classify(blocker, deadline_ms=10)
        assert ei.value.reason == "deadline_exceeded"
        assert ei.value.retry_after_s and ei.value.retry_after_s > 0
        deadline = time.monotonic() + 10
        while srv.stats.deadline_shed < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.stats.deadline_shed == 2  # booked at admission
        assert dispatched == ["manifest.json"]  # still never dispatched
    finally:
        release.set()
        _stop_server(srv, t)


def test_poisoned_batch_isolates_the_bad_query(serve_index, tmp_path):
    """One malformed query coalesced with valid ones must not fail its
    neighbors: the daemon retries the batch per path, so only the bad
    file answers with classify_failed — the batching contract stays
    'identical to K separate one-shot classifies', errors included."""
    loc, queries = serve_index
    bad = str(tmp_path / "bad.fasta")
    with open(bad, "wb") as f:
        f.write(b"\x00\x01 definitely not fasta\n")
    srv, addr, t = _start_server(loc, batch_window_ms=300.0)
    try:
        with ServeClient(addr, timeout_s=120) as c:
            resps = c.classify_many([queries[0], bad, queries[1]])
        assert resps[0]["ok"] and resps[2]["ok"]
        assert resps[0]["verdict"] == index_classify(loc, [queries[0]])[0]
        assert not resps[1]["ok"] and resps[1]["reason"] == "classify_failed"
        assert "bad.fasta" in resps[1]["error"]
    finally:
        _stop_server(srv, t)


def test_serve_wrapper_refuses_log_dir_inside_index(tmp_path):
    from drep_tpu.errors import UserInputError
    from drep_tpu.workflows import index_serve_wrapper

    loc = str(tmp_path / "idx")
    os.makedirs(loc)
    with pytest.raises(UserInputError, match="read-only"):
        index_serve_wrapper(loc, log_dir=os.path.join(loc, "log"))


# ---- subprocess daemon: drain + chaos -------------------------------------


def _spawn_cli_daemon(loc, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.Popen(
        [sys.executable, "-m", "drep_tpu", "index", "serve", loc,
         "--batch_window_ms", "20", *extra],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=REPO, env=env,
    )
    line = proc.stdout.readline()
    assert line, "daemon died before its ready line"
    return proc, json.loads(line)


@pytest.mark.chaos
def test_daemon_sigterm_drains_cleanly(tmp_path):
    """The PR 9 drain idiom, serving-tier edition: SIGTERM -> queued work
    answered, new admissions refused, exit 0."""
    paths = lib.write_genome_set(str(tmp_path / "g"), [2, 1], seed=9)
    loc = str(tmp_path / "idx")
    build_from_paths(loc, paths, length=0)
    q = lib.write_genome_set(str(tmp_path / "q"), [1], seed=3, prefix="q")
    proc, ready = _spawn_cli_daemon(loc)
    try:
        with ServeClient(ready["serving"], timeout_s=300) as c:
            resps = c.classify_many(q * 1 + [paths[0]])
            assert all(r["ok"] for r in resps)
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0  # the drain contract
        # the listener is gone: a new client cannot connect
        with pytest.raises((ConnectionRefusedError, OSError, ServeError)):
            ServeClient(ready["serving"], timeout_s=5).ping()
    finally:
        if proc.poll() is None:
            proc.kill()


@pytest.mark.chaos
def test_sigkill_daemon_clean_error_restart_same_generation(tmp_path):
    """The chaos_matrix --serve cell: SIGKILL mid-batch -> every client
    sees a clean disconnection (not a hang, not a torn line), a restart
    serves the SAME generation, and the index is byte-for-byte
    untouched through kill and restart."""
    paths = lib.write_genome_set(str(tmp_path / "g"), [3, 2], seed=21)
    loc = str(tmp_path / "idx")
    build_from_paths(loc, paths, length=0)
    digest = lib.tree_digest(loc, exclude_dirs=())
    q = lib.write_genome_set(str(tmp_path / "q"), [3], seed=8, prefix="q")

    proc, ready = _spawn_cli_daemon(loc, "--batch_window_ms", "300")
    got_error = []

    def victim():
        try:
            with ServeClient(ready["serving"], timeout_s=60) as c:
                c.classify_many(q)  # lands inside the 300ms batch window
        except ServeError as e:
            got_error.append(e)

    t = threading.Thread(target=victim, daemon=True)
    try:
        t.start()
        time.sleep(0.15)  # requests admitted, batch window still open
        proc.kill()  # SIGKILL: no drain, no goodbye
        proc.wait(30)
        t.join(timeout=60)
        assert not t.is_alive(), "client hung on a SIGKILLed daemon"
        assert got_error and got_error[0].reason == "disconnected"
    finally:
        if proc.poll() is None:
            proc.kill()
    # restart: same generation, index untouched, still answers
    proc2, ready2 = _spawn_cli_daemon(loc)
    try:
        assert ready2["generation"] == ready["generation"] == 0
        with ServeClient(ready2["serving"], timeout_s=300) as c:
            r = c.classify(q[0])
        assert r["ok"] and r["generation"] == 0
        proc2.send_signal(signal.SIGTERM)
        assert proc2.wait(timeout=120) == 0
    finally:
        if proc2.poll() is None:
            proc2.kill()
    assert lib.tree_digest(loc, exclude_dirs=()) == digest


# ---- satellites ------------------------------------------------------------


def test_multi_replica_serve_federated_update_beside(tmp_path):
    """ISSUE 13 satellite — the multi-replica story the ROADMAP says was
    never demonstrated: TWO daemons resident on ONE federated index
    while an `index update` publishes the next federation generation
    beside them. Both replicas hot-swap without restart, every verdict
    is generation-stamped and equal to the one-shot answer at its own
    generation, and the store is byte-for-byte exactly what the update
    published (the daemons are pure readers)."""
    from drep_tpu.index import build_federated

    base = lib.write_genome_set(str(tmp_path / "g"), [2, 1], seed=72)
    batch = lib.write_genome_set(str(tmp_path / "n"), [1, 1], seed=73, prefix="n")
    loc = str(tmp_path / "fed")
    build_federated(loc, base, 2, length=0)

    def _strip(v: dict) -> dict:
        # a federated daemon's STREAMING verdicts carry partition
        # coverage stamps (ISSUE 14); the one-shot union oracle does not
        out = dict(v)
        for k in ("partitions_consulted", "partitions_unavailable", "partial"):
            out.pop(k, None)
        return out

    want_gen0 = index_classify(loc, [base[1]])[0]
    servers = [
        _start_server(loc, batch_window_ms=1.0, poll_generation_s=0.1)
        for _ in range(2)
    ]
    try:
        for _srv, addr, _t in servers:
            with ServeClient(addr) as c:
                r = c.classify(base[1])
            assert r["generation"] == 0 and _strip(r["verdict"]) == want_gen0
            assert r["verdict"]["partitions_unavailable"] == []  # full coverage
        # publish federation generation 1 beside the two live daemons
        # (the batch routes to BOTH partitions — a genuinely federated
        # update, not a single-store publish)
        summary = index_update(loc, batch)
        assert summary["generation"] == 1
        assert len(summary["partitions_updated"]) == 2
        digest_after = lib.tree_digest(loc, exclude_dirs=("log",))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not all(
            s.stats.swaps_total >= 1 for s, _a, _t in servers
        ):
            time.sleep(0.05)
        assert [s.stats.swaps_total for s, _a, _t in servers] == [1, 1]
        want_gen1 = index_classify(loc, [batch[0]])[0]
        for _srv, addr, _t in servers:
            with ServeClient(addr) as c:
                r = c.classify(batch[0])
            assert r["generation"] == 1
            assert _strip(r["verdict"]) == want_gen1
    finally:
        for srv, _addr, t in servers:
            _stop_server(srv, t)
    # the daemons wrote nothing: the tree is exactly the update's publish
    assert lib.tree_digest(loc, exclude_dirs=("log",)) == digest_after


def test_pod_status_follow_renders_in_place(tmp_path):
    """--follow: poll + re-render on an interval, read-only, bounded by
    --count for scripting; the snapshot function is the same collect()
    the serve daemon's health endpoint reuses."""
    ps = _tool("pod_status")
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    out = io.StringIO()
    rc = ps.follow(str(ckpt), interval_s=0.01, count=2, out=out)
    assert rc == 0
    text = out.getvalue()
    assert text.count("pod status @") == 2
    assert text.count("--- poll") == 2  # non-TTY: separators, not ANSI
    # --json follow is an NDJSON STREAM (ISSUE 15 satellite): one compact
    # JSON object per line, no banners — machine-consumable as-is
    out = io.StringIO()
    ps.follow(str(ckpt), interval_s=0.01, count=1, out=out, as_json=True)
    lines = out.getvalue().splitlines()
    assert len(lines) == 1 and "--- poll" not in out.getvalue()
    doc = json.loads(lines[0])
    assert doc["shards_published"] == 0


def test_stall_diagnosis_names_open_span(tmp_path):
    """trace_report.stall_diagnosis:
    an event log whose stream stops inside a span names that span as the
    stall site, with idle gaps and the last event."""
    tr = _tool("trace_report")
    log_dir = tmp_path / "log"
    log_dir.mkdir()
    lines = [
        {"run": "r", "pid": 0, "epoch": 0, "ev": "stage:cluster", "ph": "B",
         "mono": 1.0, "wall": 100.0},
        {"run": "r", "pid": 0, "epoch": 0, "ev": "stripe", "ph": "B",
         "mono": 2.0, "wall": 101.0, "args": {"bi": 0}},
        {"run": "r", "pid": 0, "epoch": 0, "ev": "stripe", "ph": "E",
         "mono": 3.0, "wall": 102.0, "args": {"bi": 0, "dur": 1.0}},
        {"run": "r", "pid": 0, "epoch": 0, "ev": "stripe", "ph": "B",
         "mono": 10.0, "wall": 109.0, "args": {"bi": 7}},
    ]
    with open(log_dir / "events.p0.jsonl", "w") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")
    diag = tr.stall_diagnosis(str(log_dir))
    assert diag is not None and diag["n_events"] == 4
    assert diag["stall_site"]["ev"] == "stripe"
    assert diag["stall_site"]["args"] == {"bi": 7}  # names the stripe
    assert {s["ev"] for s in diag["open_spans"]} == {"stage:cluster", "stripe"}
    assert diag["last_event"]["ev"] == "stripe"
    assert tr.stall_diagnosis(str(tmp_path / "empty")) is None
    # bench's hook finds the log dir through telemetry's configured sink
    from drep_tpu.utils import telemetry

    telemetry.configure(log_dir=str(log_dir), enabled=False)
    assert telemetry.configured_log_dir() == str(log_dir)
    telemetry.configure(log_dir=None)


@pytest.mark.slow
def test_serve_bench_loadgen_guard(tmp_path):
    """The perf guard (proxy metrics, never hardware claims): the
    loadgen pins batched >= unbatched throughput at concurrency and a
    startup-amortization ratio; the record is stamped proxy_metrics so
    nothing reads it as a hardware number."""
    out = str(tmp_path / "SERVE_BENCH.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_client.py"),
         "--bench", "--n_genomes", "10", "--clients", "16",
         "--requests_per_client", "4", "--speedup", "2.0",
         "--amortization", "2.0", "--out", out],
        capture_output=True, text=True, cwd=str(tmp_path), timeout=1200, env=env,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    with open(out) as f:
        rec = json.load(f)
    assert rec["proxy_metrics"] is True and rec["backend"] == "cpu"
    assert rec["configs"]["max_batch_16"]["mean_batch_size"] > 1.5
    assert rec["batched_speedup_x"] >= 2.0
    assert rec["guards"]["batched_speedup_ok"]
    assert rec["guards"]["startup_amortization_ok"]
