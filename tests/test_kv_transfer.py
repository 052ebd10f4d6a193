"""Cross-replica KV handoff (serving/transfer.py + the hardened wire
format in serving/kv_pool.py; docs/serving.md "Disaggregated serving").

Fast lane: the length-prefixed socket framing (declared-length bound,
truncation), envelope validation (``peek_chain_header`` — version byte,
size bound, trunk signature), the receive path's every-failure-is-a-
fallback contract against stub engines and real in-process HTTP export
servers, and ``deliver_chain_blob``'s pool-poisoning rejection.

Slow lane: the full cross-process round trip — a prefill-role replica
SUBPROCESS serializes its resident chain over ``POST /v1/kv/export``, a
decode-role replica subprocess receives and seats it, the stream is
bit-identical to the cold ``lm_generate`` recompute, and the
``kv_handoff_*`` counters on BOTH replicas' /metrics are exact.
"""

import http.client
import http.server
import io
import json
import re
import threading
import urllib.request

import numpy as np
import pytest
import jax

from paddle_tpu.models import transformer
from paddle_tpu.serving import ServingMetrics
from paddle_tpu.serving import transfer
from paddle_tpu.serving.decode_engine import DecodeEngine
from paddle_tpu.serving.kv_pool import (HostTier, MAX_CHAIN_BLOB_BYTES,
                                        WIRE_VERSION, WireFormatError,
                                        WireVersionError,
                                        peek_chain_header, restore_chain,
                                        serialize_chain)
from paddle_tpu.utils.error import ConfigError

VOCAB, D_MODEL, LAYERS, HEADS = 64, 32, 2, 2
MAX_LEN, BS = 48, 8
SIG = f"L{LAYERS}.d{D_MODEL}.dkv{D_MODEL // HEADS}.h{HEADS}.float32.b{BS}"


def _blob(rng, n_blocks=2, sig=SIG):
    tokens = [int(t) for t in rng.integers(1, VOCAB, n_blocks * BS)]
    arrays = [("k0", rng.standard_normal((n_blocks, BS, 16))
               .astype(np.float32)),
              ("v0", rng.standard_normal((n_blocks, BS, 16))
               .astype(np.float32))]
    return tokens, serialize_chain(tokens, n_blocks * BS, arrays, sig)


# ----------------------------------------------------- socket framing


def test_write_read_blob_round_trip():
    rng = np.random.default_rng(0)
    _, blob = _blob(rng)
    buf = io.BytesIO()
    transfer.write_blob(buf, blob)
    assert buf.getvalue()[:8] == len(blob).to_bytes(8, "little")
    buf.seek(0)
    assert transfer.read_blob(buf) == blob


def test_read_blob_bounds_declared_length_before_allocating():
    # a peer declaring a huge payload is rejected at the 8-byte prefix,
    # before the receive buffer grows toward it
    evil = (1 << 40).to_bytes(8, "little")
    with pytest.raises(transfer.HandoffError, match="receive bound"):
        transfer.read_blob(io.BytesIO(evil), max_bytes=1 << 20)
    # ... and the default bound is the wire format's own blob ceiling
    with pytest.raises(transfer.HandoffError, match="receive bound"):
        transfer.read_blob(io.BytesIO(
            (MAX_CHAIN_BLOB_BYTES + 1).to_bytes(8, "little")))


def test_read_blob_rejects_truncation():
    with pytest.raises(transfer.HandoffError, match="length prefix"):
        transfer.read_blob(io.BytesIO(b"\x05\x00\x00"))
    body = (100).to_bytes(8, "little") + b"x" * 40
    with pytest.raises(transfer.HandoffError, match="truncated at 40/100"):
        transfer.read_blob(io.BytesIO(body))


# ------------------------------------------------ envelope validation


def test_peek_chain_header_bounds_and_signature():
    rng = np.random.default_rng(1)
    tokens, blob = _blob(rng)
    header = peek_chain_header(blob, SIG)
    assert header["covered"] == len(tokens)
    assert [int(t) for t in header["tokens"]] == tokens
    # size bound checked FIRST, before any parsing
    with pytest.raises(WireFormatError, match="receive bound"):
        peek_chain_header(blob, SIG, max_bytes=16)
    with pytest.raises(WireFormatError, match="trunk signature"):
        peek_chain_header(blob, SIG.replace(f"L{LAYERS}",
                                            f"L{LAYERS + 1}"))
    with pytest.raises(WireVersionError):
        peek_chain_header(bytes([WIRE_VERSION + 1]) + blob[1:], SIG)
    with pytest.raises(WireFormatError, match="not valid JSON"):
        peek_chain_header(blob[:9] + b"\xff" * (len(blob) - 9), SIG)


def test_restore_chain_honors_max_bytes():
    rng = np.random.default_rng(2)
    _, blob = _blob(rng)
    with pytest.raises(WireFormatError, match="receive bound"):
        restore_chain(blob, SIG, max_bytes=32)
    # errors stay ValueError for every pre-hardening call site
    assert issubclass(WireVersionError, WireFormatError)
    assert issubclass(WireFormatError, ValueError)


# --------------------------------------------- in-process export peer


class _ExportPeer:
    """A minimal real-socket /v1/kv/export peer: serves one canned blob
    (optionally lying about its length or truncating mid-stream), so the
    fetch path is tested over genuine HTTP without an engine."""

    def __init__(self, blob, mode="ok"):
        peer = self

        class H(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length", 0)))
                if peer.mode == "http_error":
                    self.send_error(404, "no resident KV coverage")
                    return
                self.send_response(200)
                self.send_header("Content-Type",
                                 "application/octet-stream")
                self.end_headers()
                if peer.mode == "overdeclare":
                    self.wfile.write((1 << 40).to_bytes(8, "little"))
                elif peer.mode == "truncate":
                    self.wfile.write(len(peer.blob).to_bytes(8, "little"))
                    self.wfile.write(peer.blob[:len(peer.blob) // 2])
                else:
                    transfer.write_blob(self.wfile, peer.blob)

            def log_message(self, *a):
                pass

        self.blob, self.mode = blob, mode
        self.httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_fetch_chain_round_trip_over_real_socket():
    rng = np.random.default_rng(3)
    tokens, blob = _blob(rng)
    peer = _ExportPeer(blob)
    try:
        covered, got = transfer.fetch_chain(peer.url, tokens, SIG)
        assert covered == len(tokens) and got == blob
    finally:
        peer.close()


def test_fetch_chain_failure_modes_raise_handoff_error():
    rng = np.random.default_rng(4)
    tokens, blob = _blob(rng)
    # dead peer (the kill -9 case): connection refused, not a hang
    dead = _ExportPeer(blob)
    dead.close()
    with pytest.raises(transfer.HandoffError, match="failed"):
        transfer.fetch_chain(dead.url, tokens, SIG, timeout=2.0)
    for mode, pat in (("http_error", "HTTP 404"),
                      ("overdeclare", "receive bound"),
                      ("truncate", "truncated")):
        peer = _ExportPeer(blob, mode=mode)
        try:
            with pytest.raises(transfer.HandoffError, match=pat):
                transfer.fetch_chain(peer.url, tokens, SIG, timeout=5.0)
        finally:
            peer.close()
    # foreign blob: fetched fine, rejected at the envelope
    peer = _ExportPeer(blob)
    try:
        with pytest.raises(WireFormatError, match="trunk signature"):
            transfer.fetch_chain(
                peer.url, tokens,
                SIG.replace(f"d{D_MODEL}", f"d{D_MODEL * 2}"))
    finally:
        peer.close()


# ------------------------------------------- receive path (fallbacks)


class _StubEngine:
    """Duck-typed receiver: exactly the surface ``receive_chain`` uses."""

    def __init__(self, tier, faster=True, sig=SIG):
        self.host_tier = tier
        self.block_size = BS
        self._trunk_sig = sig
        self._faster = faster
        self.delivered = []

    def _handoff_predicted_faster(self, est):
        return self._faster, 0.1, 0.2

    def deliver_chain_blob(self, blob, max_bytes=None):
        header = peek_chain_header(blob, self._trunk_sig, max_bytes)
        self.host_tier.put(tuple(int(t) for t in header["tokens"]),
                           int(header["covered"]), blob)
        self.delivered.append(blob)
        return tuple(header["tokens"]), int(header["covered"])


def test_receive_chain_success_counts_and_parks():
    rng = np.random.default_rng(5)
    tokens, blob = _blob(rng)
    peer = _ExportPeer(blob)
    eng = _StubEngine(HostTier(64 << 20))
    m = ServingMetrics()
    try:
        out = transfer.receive_chain(eng, peer.url, tokens, metrics=m)
        assert out["outcome"] == "received" and out["reason"] is None
        assert out["bytes"] == len(blob)
        assert out["covered"] == len(tokens)
        assert eng.delivered == [blob]
        snap = m.snapshot()
        assert snap["kv_handoffs_total"] == {"sent": 0, "received": 1,
                                             "fallback": 0}
        assert snap["kv_handoff_bytes_total"] == len(blob)
        # an immediate retry finds the chain resident: no second fetch
        again = transfer.receive_chain(eng, peer.url, tokens, metrics=m)
        assert again["outcome"] == "received"
        assert again["reason"] == "resident" and again["bytes"] == 0
        assert m.snapshot()["kv_handoff_bytes_total"] == len(blob)
    finally:
        peer.close()


def test_receive_chain_every_failure_is_a_counted_fallback():
    rng = np.random.default_rng(6)
    tokens, blob = _blob(rng)
    m = ServingMetrics()

    def recv(eng, source, toks):
        return transfer.receive_chain(eng, source, toks, metrics=m)

    class _NoTier:
        host_tier = None

    cases = [
        (recv(_NoTier(), "http://127.0.0.1:9", tokens), "no_host_tier"),
        (recv(_StubEngine(HostTier(1 << 20)), "http://127.0.0.1:9",
              tokens[:BS - 1]), "below_block"),
        (recv(_StubEngine(HostTier(1 << 20), faster=False),
              "http://127.0.0.1:9", tokens), "analytic"),
        # dead peer: the socket error becomes a fallback, never a raise
        (recv(_StubEngine(HostTier(1 << 20)), "http://127.0.0.1:9",
              tokens), "HandoffError"),
    ]
    peer = _ExportPeer(blob)       # serves SIG blobs to a foreign engine
    try:
        cases.append((recv(_StubEngine(HostTier(1 << 20),
                                       sig=SIG + ".x"), peer.url,
                           tokens), "WireFormatError"))
    finally:
        peer.close()
    for out, reason in cases:
        assert out["outcome"] == "fallback", (reason, out)
        assert out["reason"] == reason, out
        assert out["bytes"] == 0 and out["covered"] == 0, out
    assert m.snapshot()["kv_handoffs_total"]["fallback"] == len(cases)


# ------------------------------------- delivery hardening (real engine)


@pytest.fixture(scope="module")
def cold_engine():
    """Uncompiled tiny-trunk engine (warm=False): delivery validation
    needs the trunk signature and tier, never a compiled step."""
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=VOCAB,
                              trg_vocab=1, d_model=D_MODEL,
                              num_heads=HEADS, dff=64, enc_layers=LAYERS,
                              dec_layers=0, max_len=MAX_LEN)
    return DecodeEngine(params, num_heads=HEADS, num_slots=2,
                        max_len=MAX_LEN, name="transfer_cold", warm=False,
                        kv_layout="paged", kv_block_size=BS,
                        kv_num_blocks=2 * (MAX_LEN // BS) + 1,
                        prefill_chunk=BS, kv_host_bytes=64 << 20)


def _poisoned(tokens, covered, arrays, sig):
    """serialize_chain with the coverage invariant bypassed — the blob a
    hostile peer would craft."""
    blob = serialize_chain(tokens, (len(tokens) // BS) * BS, arrays, sig)
    hlen = int.from_bytes(blob[1:9], "little")
    header = json.loads(blob[9:9 + hlen])
    header["covered"] = covered
    h = json.dumps(header).encode()
    return blob[:1] + len(h).to_bytes(8, "little") + h + blob[9 + hlen:]


def test_deliver_chain_blob_rejects_pool_poisoning(cold_engine):
    rng = np.random.default_rng(7)
    tokens = [int(t) for t in rng.integers(1, VOCAB, 2 * BS)]
    arrays = [("k0", rng.standard_normal((2, BS, 16)).astype(np.float32))]
    # coverage lying PAST the key would seat garbage beyond the tokens
    with pytest.raises(WireFormatError, match="refusing to pool"):
        cold_engine.deliver_chain_blob(
            _poisoned(tokens, 3 * BS, arrays, cold_engine._trunk_sig))
    # coverage over max_len would wedge receivers in eternal claim-defer
    long_toks = [int(t) for t in rng.integers(1, VOCAB, MAX_LEN + BS)]
    long_arr = [("k0", rng.standard_normal(
        ((MAX_LEN + BS) // BS, BS, 16)).astype(np.float32))]
    with pytest.raises(WireFormatError, match="refusing to pool"):
        cold_engine.deliver_chain_blob(
            serialize_chain(long_toks, MAX_LEN + BS, long_arr,
                            cold_engine._trunk_sig))
    # foreign trunk: rejected before it touches the tier
    with pytest.raises(WireFormatError, match="trunk signature"):
        cold_engine.deliver_chain_blob(
            serialize_chain(tokens, 2 * BS, arrays, SIG + ".other"))
    assert cold_engine.host_tier.bytes == 0
    # the honest blob pools fine
    key, covered = cold_engine.deliver_chain_blob(
        serialize_chain(tokens, 2 * BS, arrays, cold_engine._trunk_sig))
    assert key == tuple(tokens) and covered == 2 * BS
    assert cold_engine.host_tier.bytes > 0


def test_deliver_chain_blob_needs_host_tier():
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=VOCAB,
                              trg_vocab=1, d_model=D_MODEL,
                              num_heads=HEADS, dff=64, enc_layers=LAYERS,
                              dec_layers=0, max_len=MAX_LEN)
    eng = DecodeEngine(params, num_heads=HEADS, num_slots=2,
                       max_len=MAX_LEN, name="transfer_tierless", warm=False,
                       kv_layout="paged", kv_block_size=BS,
                       kv_num_blocks=13, prefill_chunk=BS)
    with pytest.raises(ConfigError, match="kv_host_bytes"):
        eng.deliver_chain_blob(b"\x01")


# ------------------------------------ cross-process round trip (slow)


def _outcome_counts(text):
    return {m.group(1): int(m.group(2)) for m in re.finditer(
        r'^\S*_kv_handoffs_total\{outcome="(\w+)"\} (\d+)\s*$',
        text, re.MULTILINE)}


@pytest.mark.slow
def test_cross_process_handoff_bit_identical_exact_counters():
    """One prefill-role replica subprocess serializes its chain over the
    socket; one decode-role subprocess receives and seats it.  The
    decode stream must be bit-identical to the cold in-process
    ``lm_generate`` recompute, and the ``kv_handoff_*`` counters on both
    /metrics must be EXACT: one sent, one received, zero fallbacks, the
    same blob bytes on both sides."""
    from paddle_tpu.serving.fleet import ReplicaSupervisor

    n_tokens, max_len, bs, plen = 12, 64, 8, 32
    extra = ["--gen-slots", "4", "--gen-max-len", str(max_len),
             "--gen-max-tokens", str(n_tokens),
             "--prefill-chunk", str(bs),
             "--kv-layout", "paged", "--kv-block-size", str(bs),
             "--kv-num-blocks", "49", "--kv-prefix-cache", "1",
             "--kv-host-bytes", str(64 << 20)]
    sup = ReplicaSupervisor(n_replicas=2, roles=("prefill", "decode"),
                            extra_args=extra, backoff_base_s=0.3, seed=0,
                            name="transfer_xproc")

    def post(url, body):
        req = urllib.request.Request(
            f"{url}/v1/generate", json.dumps(body).encode(),
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    sup.start()
    try:
        assert sup.wait_ready(timeout=240), "replicas never became ready"
        eps = dict(sup.endpoints())
        prefill_url, decode_url = eps["r0"], eps["r1"]
        prompt = [int(t)
                  for t in np.random.RandomState(11).randint(1, 256, plen)]

        # serialize side: prefill to the first token on r0
        lead = post(prefill_url, {"prompt": prompt, "max_tokens": 1})
        assert len(lead["tokens"]) == 1, lead

        # receive side: r1 pulls the chain over the socket, seats it,
        # and decodes the continuation
        out = post(decode_url, {
            "prompt": prompt, "replay": lead["tokens"],
            "max_tokens": n_tokens - 1,
            "kv_handoff": {"source": prefill_url,
                           "tokens": prompt + lead["tokens"]}})
        hand = out["kv_handoff"]
        assert hand["outcome"] == "received", hand
        assert hand["bytes"] > 0 and hand["covered"] >= plen, hand

        # bit-identity vs the cold recompute oracle
        params = transformer.init(
            jax.random.PRNGKey(0), src_vocab=256, trg_vocab=1,
            d_model=32, num_heads=2, dff=64, enc_layers=2, dec_layers=0,
            max_len=max_len)
        p = np.asarray(prompt, np.int32)
        ids = np.asarray(transformer.lm_generate(
            params, p[None], max_len=max_len, num_heads=2,
            prompt_lengths=np.asarray([p.size])))
        oracle = ids[0, p.size:p.size + n_tokens].tolist()
        assert lead["tokens"] + out["tokens"] == oracle

        # exact counters on both /metrics
        def metrics(url):
            with urllib.request.urlopen(f"{url}/metrics",
                                        timeout=30) as r:
                return r.read().decode()

        pre, dec = metrics(prefill_url), metrics(decode_url)
        assert _outcome_counts(pre) == {"sent": 1, "received": 0,
                                        "fallback": 0}, pre[-500:]
        assert _outcome_counts(dec) == {"sent": 0, "received": 1,
                                        "fallback": 0}, dec[-500:]
        sent_b = re.search(r"^\S*_kv_handoff_bytes_total (\d+)", pre,
                           re.MULTILINE)
        recv_b = re.search(r"^\S*_kv_handoff_bytes_total (\d+)", dec,
                           re.MULTILINE)
        assert sent_b and recv_b, (pre[-500:], dec[-500:])
        assert int(sent_b.group(1)) == int(recv_b.group(1)) \
            == hand["bytes"]
    finally:
        sup.stop()
