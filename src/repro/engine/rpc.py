"""Teacher transport: the ``stream.Teacher`` protocol over a real TCP socket.

``LatencyTeacher`` models the teacher round-trip in *ticks*; this module
replaces the model with an actual network hop so the streaming runtime and
the multiplexer can be exercised against a real transport: a label server
on the other end of a socket, wall-clock latency, and a timeout → loss
mapping (a reply that misses the deadline is treated exactly like a lost
ticket — the runtime's ring entry drains as ``queries_lost``, and a
straggler reply that limps in after its timeout is discarded, never
applied).

Two clients share the transport:

* ``RpcTeacher`` — one connection per tenant, one wire message per ask
  (the PR-3 shape).
* ``BatchedRpcClient`` / ``BatchedRpcTeacher`` — **one connection per
  teacher host shared by every tenant**: asks from all tenants that land
  within a flush window (``batch_window_s``, capped at ``batch_max`` asks)
  are coalesced into a single framed request, and the batched reply is
  demuxed back to per-tenant inboxes.  Each tenant handle still speaks the
  unchanged ``stream.Teacher`` protocol (ask/poll/in_flight, deadlines
  judged at reply *arrival*, timeout → loss), so a ``StreamSession`` can't
  tell the transports apart — only the wire can (see
  ``benchmarks/rpc_bench.py``).  The HMAC handshake runs once per
  connection, i.e. once per host instead of once per tenant.

Wire protocol — two framings, the server answers both, each request in
its own format:

* **v1 (legacy)**: newline-delimited JSON, one object per line, float
  lists for features::

    request:  {"ticket": int, "tick": int, "mask": [bool, ...],
               "feats": [[f, ...], ...]}
    reply:    {"ticket": int, "labels": [int, ...], "answered": [bool, ...]}

* **v2 (default)**: length-prefixed binary frames.  Every frame is::

    [1 byte version = 0x02] [4 bytes LE header length] [JSON header]
    [raw payload]

  The header carries ``{"kind": "ask"|"reply", "payload_len": int, ...}``
  plus per-message specs; the payload is the concatenation, in spec
  order, of raw little-endian numpy buffers — for an ask
  ``mask`` (S × uint8) then ``feats`` (S·n_in × float32), for a reply
  ``answered`` (S × uint8) then ``labels`` (S × int32).  One frame can
  carry many asks (the batched client) or exactly one (``RpcTeacher``
  with ``wire="v2"``); the reply frame mirrors the request frame.  The
  version byte 0x02 can never begin a JSON line, so a server (or reader)
  distinguishes the formats from the first byte of each message.

* **zlib envelope (optional, ``compress=True`` / ``--teacher-compress``)**:
  ``[0x03] [4 bytes LE compressed length] [zlib stream]`` whose
  decompressed bytes are one complete v2 frame.  The framing layer
  unwraps it transparently; the server answers a compressed request with
  a compressed reply (in kind) and meters the win
  (``frames_compressed``, ``compressed_bytes_in/out`` vs
  ``raw_bytes_in/out``).  With a secret, the grant is negotiated in the
  HMAC handshake (``"compress": "zlib"`` on the auth line, echoed in
  ``auth_ok``) so an older server is never sent a byte it can't parse.

Authentication (``secret=...`` / ``--secret``): a *mutual* shared-secret
HMAC challenge–response on connect, always in newline-JSON (it precedes
any framed traffic).  The server opens every connection with
``{"challenge": <hex nonce>}``; the client answers
``{"auth": HMAC_SHA256(secret, challenge), "nonce": <hex nonce>}``; the
server verifies the digest and answers the client's nonce with
``{"auth_ok": HMAC_SHA256(secret, nonce)}`` before any label traffic.  A
wrong or missing digest closes the socket (an unauthenticated client
never receives a label), and a server that cannot answer the client's
nonce — an imposter that merely emits a challenge — is rejected by the
client before any of its labels can train the fleet.  Without a secret
the handshake is skipped entirely (backwards compatible).

The bundled ``LabelServer`` answers deterministically —
``label[s] = (7 * tick + s) % n_out`` — so round-trip tests can assert
exact labels; ``loss_prob`` / ``jitter_s`` / ``delay_s`` fault-inject the
reply path (a lost ask is simply never answered — the client's deadline
maps it to loss).  A real deployment would put the pod-side backbone
ensemble behind the same message shapes.  Run it standalone::

    PYTHONPATH=src python -m repro.engine.rpc --port 0 --n-out 6

(``--port 0`` binds an ephemeral port and prints ``PORT <p>`` on stdout —
that is what ``loopback_server`` parses), or self-test the full
client/server round trip in one process pair::

    PYTHONPATH=src python -m repro.engine.rpc --selftest
"""

from __future__ import annotations

import argparse
import contextlib
import hmac
import json
import os
import pathlib
import secrets as secrets_mod
import socket
import subprocess
import sys
import threading
import time
import zlib
from typing import Optional

import numpy as np

from repro.engine.stream import TeacherReply
from repro.runtime import lockdebug
from repro.runtime import telemetry as _telemetry

# First byte of every v2 frame.  0x02 (STX) can never start a JSON line,
# so the two wire formats coexist on one connection.
WIRE_V2 = 0x02
_WIRE_V2_BYTE = bytes([WIRE_V2])

# Compressed envelope: [0x03][4 bytes LE compressed length][zlib stream]
# where the decompressed bytes are one complete v2 frame.  ``_iter_wire``
# unwraps it transparently, so everything downstream of the framing layer
# (codec, server, reader threads) sees plain v2 messages.  Negotiated in
# the HMAC handshake when a secret is set (``"compress": "zlib"`` in the
# client's auth line, echoed in ``auth_ok``); without a secret a client
# configured with ``compress=True`` just sends envelopes and the server
# answers each compressed request in kind.
WIRE_V3_ZLIB = 0x03
_WIRE_V3_BYTE = bytes([WIRE_V3_ZLIB])

# Speed over ratio: the payloads are float32 feature blocks produced at
# tick rate, so the codec sits on the hot path of every ask.
ZLIB_LEVEL = 1

WIRE_FORMATS = ("v1", "v2")

# Batched-client defaults: how long the first queued ask waits for
# company before the frame is flushed, and the per-frame ask cap.
DEFAULT_BATCH_WINDOW_S = 1e-3
DEFAULT_BATCH_MAX = 64


def expected_label(tick: int, s: int, n_out: int) -> int:
    """The deterministic rule ``LabelServer`` answers with."""
    return (7 * tick + s) % n_out


def _digest(secret: str, challenge: str) -> str:
    return hmac.new(
        secret.encode(), challenge.encode(), "sha256"
    ).hexdigest()


def _shutdown_socket(sock: socket.socket) -> None:
    """Tear a connection down for real: ``close()`` alone only drops one
    reference — ``makefile()`` readers keep the fd (and thus the peer's
    blocking ``recv``) alive, which is exactly how the label server used
    to accumulate one live thread per past connection.  ``shutdown`` sends
    the FIN regardless of refcounts, unblocking both ends' readers."""
    with contextlib.suppress(OSError):
        sock.shutdown(socket.SHUT_RDWR)
    with contextlib.suppress(OSError):
        sock.close()


# ---------------------------------------------------------------------------
# v2 framing codec
# ---------------------------------------------------------------------------


def _encode_frame(header: dict, payload: bytes = b"") -> bytes:
    hdr = json.dumps(header, separators=(",", ":")).encode()
    return _WIRE_V2_BYTE + len(hdr).to_bytes(4, "little") + hdr + payload


def _compress_frame(frame: bytes) -> bytes:
    """Wrap one complete v2 frame in a zlib envelope (wire byte 0x03)."""
    z = zlib.compress(frame, ZLIB_LEVEL)
    return _WIRE_V3_BYTE + len(z).to_bytes(4, "little") + z


def _read_exact(f, n: int) -> bytes:
    buf = f.read(n)
    if buf is None or len(buf) != n:
        raise EOFError(f"stream ended inside a frame (wanted {n} bytes, "
                       f"got {0 if buf is None else len(buf)})")
    return buf


def _iter_wire(f):
    """Yield every message on a buffered binary stream, either format.

    Yields ``("v2", header, payload)`` for binary frames and
    ``("v1", obj_or_None, raw_line)`` for JSON lines (``None`` when the
    line does not parse).  A zlib envelope (0x03) is unwrapped here and
    yielded as the v2 frame it contains, with ``header["_z"] =
    (wire_bytes, raw_bytes)`` so the server can meter compression and
    answer in kind.  Ends cleanly on EOF *between* messages; raises
    ``EOFError`` (or ``ValueError`` for a corrupt header / envelope) when
    the stream dies *inside* a frame — a torn frame desynchronizes
    everything after it, so the caller must drop the connection.
    """
    while True:
        b = f.read(1)
        if not b:
            return
        if b[0] == WIRE_V3_ZLIB:
            zlen = int.from_bytes(_read_exact(f, 4), "little")
            try:
                inner = zlib.decompress(_read_exact(f, zlen))
            except zlib.error as e:
                raise ValueError(f"corrupt zlib envelope: {e}") from e
            if not inner or inner[0] != WIRE_V2:
                raise ValueError("zlib envelope does not contain a v2 frame")
            hlen = int.from_bytes(inner[1:5], "little")
            header = json.loads(inner[5 : 5 + hlen].decode())
            if not isinstance(header, dict):
                raise ValueError(f"v2 frame header is not an object: {header!r}")
            payload = inner[5 + hlen :]
            if len(payload) != int(header.get("payload_len", 0)):
                raise ValueError("zlib envelope payload length mismatch")
            header["_z"] = (5 + zlen, len(inner))
            yield "v2", header, payload
        elif b[0] == WIRE_V2:
            hlen = int.from_bytes(_read_exact(f, 4), "little")
            header = json.loads(_read_exact(f, hlen).decode())
            if not isinstance(header, dict):
                # Valid JSON but not an object: without payload_len the
                # frame boundary is unknowable — corrupt, same as a torn
                # frame (ValueError routes it to the callers' drop paths).
                raise ValueError(f"v2 frame header is not an object: {header!r}")
            payload = _read_exact(f, int(header.get("payload_len", 0)))
            yield "v2", header, payload
        else:
            line = b + f.readline()
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                obj = None
            yield "v1", obj, line


def encode_asks(asks) -> bytes:
    """One v2 request frame from ``[(ticket, tick, mask, feats), ...]``.

    Header spec per ask: ``t`` ticket, ``k`` tick, ``s`` streams, ``d``
    n_in; payload per ask: mask (S × uint8) then feats (S·d × float32 LE).
    """
    specs, chunks = [], []
    for ticket, tick, mask, feats in asks:
        mask8 = np.ascontiguousarray(np.asarray(mask), dtype=np.uint8)
        f32 = np.ascontiguousarray(np.asarray(feats), dtype="<f4")
        s = int(mask8.shape[0])
        specs.append({"t": int(ticket), "k": int(tick), "s": s,
                      "d": int(f32.size // s) if s else 0})
        chunks += [mask8.tobytes(), f32.tobytes()]
    payload = b"".join(chunks)
    return _encode_frame(
        {"kind": "ask", "payload_len": len(payload), "asks": specs}, payload
    )


def decode_asks(header: dict, payload: bytes):
    """Inverse of ``encode_asks`` → ``[(ticket, tick, mask, feats), ...]``."""
    out, off = [], 0
    for spec in header["asks"]:
        s, d = int(spec["s"]), int(spec["d"])
        mask = np.frombuffer(payload, np.uint8, s, off).astype(bool)
        off += s
        feats = np.frombuffer(payload, "<f4", s * d, off).reshape(s, d)
        off += s * d * 4
        out.append((int(spec["t"]), int(spec["k"]), mask, feats))
    return out


def encode_replies(replies) -> bytes:
    """One v2 reply frame from ``[(ticket, answered, labels), ...]``."""
    specs, chunks = [], []
    for ticket, answered, labels in replies:
        a8 = np.ascontiguousarray(np.asarray(answered), dtype=np.uint8)
        l32 = np.ascontiguousarray(np.asarray(labels), dtype="<i4")
        specs.append({"t": int(ticket), "s": int(a8.shape[0])})
        chunks += [a8.tobytes(), l32.tobytes()]
    payload = b"".join(chunks)
    return _encode_frame(
        {"kind": "reply", "payload_len": len(payload), "replies": specs},
        payload,
    )


def decode_replies(header: dict, payload: bytes) -> list[TeacherReply]:
    """Inverse of ``encode_replies`` → ``[TeacherReply, ...]``."""
    out, off = [], 0
    for spec in header["replies"]:
        s = int(spec["s"])
        answered = np.frombuffer(payload, np.uint8, s, off).astype(bool)
        off += s
        labels = np.frombuffer(payload, "<i4", s, off).astype(np.int32)
        off += s * 4
        out.append(TeacherReply(ticket=int(spec["t"]), labels=labels,
                                answered=answered))
    return out


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------


class LabelServer:
    """Threaded loopback label server (one thread per client connection).

    Answers both wire formats, each request in its own format: a v1 JSON
    line gets a v1 JSON line back, a v2 frame (single or batched) gets one
    v2 reply frame covering every ask it carried.  ``loss_prob`` drops
    individual asks from the reply (the client's deadline maps them to
    loss), ``delay_s`` + uniform ``jitter_s`` sleep before each reply —
    the fault model the accounting identity is exercised against.
    """

    def __init__(self, port: int = 0, n_out: int = 6, delay_s: float = 0.0,
                 host: str = "127.0.0.1", secret: Optional[str] = None,
                 loss_prob: float = 0.0, jitter_s: float = 0.0,
                 seed: int = 0):
        self.n_out = n_out
        self.delay_s = delay_s
        self.jitter_s = jitter_s
        self.loss_prob = loss_prob
        self.seed = seed
        self.secret = secret
        self.auth_failures = 0  # connections rejected by the HMAC handshake
        self.requests_v1 = 0  # v1 JSON-line requests served
        self.frames_v2 = 0  # v2 request frames served (1 frame : N asks)
        self.asks_served = 0  # individual asks across both formats
        self.frame_errors = 0  # undecodable lines / torn v2 frames
        # Compression metering (zlib envelopes, both directions): wire
        # bytes actually moved vs the raw v2 bytes they stand for — the
        # transport-compression win is raw/compressed.
        self.frames_compressed = 0  # compressed request frames served
        self.compressed_bytes_in = 0  # wire bytes of compressed requests
        self.raw_bytes_in = 0  # their decompressed v2 sizes
        self.compressed_bytes_out = 0  # wire bytes of compressed replies
        self.raw_bytes_out = 0  # their raw v2 sizes
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(8)
        self.host = host
        self.port = self._sock.getsockname()[1]
        self._stop = threading.Event()
        # Guards the thread/conn bookkeeping AND the public counters —
        # concurrent per-connection threads must not lose increments
        # (tests assert exact counts).
        self._tlock = lockdebug.make_lock("rpc.LabelServer._tlock")
        self._threads: list[threading.Thread] = []
        self._conns: set[socket.socket] = set()
        self._accepted = 0

    def serve_forever(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                break
            if self._stop.is_set():  # close()'s wake-up dial, not a client
                with contextlib.suppress(OSError):
                    conn.close()
                break
            with self._tlock:
                # A long-running server accepts unboundedly many
                # connections; dead client threads must not accumulate.
                self._threads = [t for t in self._threads if t.is_alive()]
                self._accepted += 1
                self._conns.add(conn)
                t = threading.Thread(
                    target=self._client, args=(conn, self._accepted),
                    daemon=True,
                )
                t.start()
                self._threads.append(t)

    def start(self) -> "LabelServer":
        t = threading.Thread(target=self.serve_forever, daemon=True)
        t.start()
        with self._tlock:
            self._threads.append(t)
        return self

    def thread_count(self) -> int:
        """Live worker threads (accept loop + open connections)."""
        with self._tlock:
            return sum(t.is_alive() for t in self._threads)

    def stats(self) -> dict:
        """Every public counter as one JSON-able dict — the payload a wire
        ``stats`` request returns (see ``server_stats``).  The server
        usually runs as a separate process, so this wire scrape is the
        only way a client-side report can see these numbers."""
        with self._tlock:
            out = {
                "auth_failures": self.auth_failures,
                "requests_v1": self.requests_v1,
                "frames_v2": self.frames_v2,
                "asks_served": self.asks_served,
                "frame_errors": self.frame_errors,
                "frames_compressed": self.frames_compressed,
                "compressed_bytes_in": self.compressed_bytes_in,
                "raw_bytes_in": self.raw_bytes_in,
                "compressed_bytes_out": self.compressed_bytes_out,
                "raw_bytes_out": self.raw_bytes_out,
                "connections_accepted": self._accepted,
            }
        out["thread_count"] = self.thread_count()
        out["n_out"] = self.n_out
        out["delay_s"] = self.delay_s
        out["jitter_s"] = self.jitter_s
        out["loss_prob"] = self.loss_prob
        return out

    def close(self) -> None:
        """Stop accepting, unblock and join every client thread."""
        self._stop.set()
        # Closing a listening socket does not reliably interrupt a thread
        # blocked in accept(); dial it once so the accept loop wakes, sees
        # the stop flag, and exits.
        dial_host = "127.0.0.1" if self.host in ("0.0.0.0", "::") else self.host
        with contextlib.suppress(OSError):
            socket.create_connection((dial_host, self.port), timeout=0.5).close()
        with contextlib.suppress(OSError):
            self._sock.close()
        with self._tlock:
            conns = list(self._conns)
            threads = list(self._threads)
        for c in conns:
            _shutdown_socket(c)
        me = threading.current_thread()
        for t in threads:
            if t is not me:
                t.join(timeout=5.0)
        with self._tlock:
            self._threads = [t for t in self._threads if t.is_alive()]

    def _client(self, conn: socket.socket, conn_id: int) -> None:
        # Per-connection fault rng: deterministic given (seed, conn_id),
        # unshared so concurrent connections never race it.
        rng = np.random.default_rng((self.seed, conn_id))
        try:
            with conn, conn.makefile("rwb") as f:
                if self.secret is not None and not self._handshake(f):
                    self._count("auth_failures")
                    return  # close unauthenticated connections: no labels
                self._serve_connection(f, rng)
        finally:
            with self._tlock:
                self._conns.discard(conn)

    def _count(self, counter: str, by: int = 1) -> None:
        with self._tlock:
            setattr(self, counter, getattr(self, counter) + by)

    def _serve_connection(self, f, rng) -> None:
        try:
            for kind, obj, payload in _iter_wire(f):
                if kind == "v2":
                    if isinstance(obj, dict) and obj.get("kind") == "stats":
                        # Live counter scrape: answered immediately (no
                        # fault-model sleep — operators scrape a server
                        # that is deliberately simulating slow labels).
                        reply = _encode_frame(
                            {"kind": "stats", "payload_len": 0,
                             "stats": self.stats()}, b"")
                        try:
                            f.write(reply)
                            f.flush()
                        except OSError:
                            return
                        continue
                    if not isinstance(obj, dict) or obj.get("kind") != "ask":
                        continue
                    z = obj.pop("_z", None)
                    try:
                        asks = decode_asks(obj, payload)
                    except (KeyError, TypeError, ValueError):
                        self._count("frame_errors")
                        return  # desynchronized: drop the connection
                    self._count("frames_v2")
                    out = encode_replies(
                        (t, mask, labels)
                        for t, mask, labels in self._answer(asks, rng)
                    )
                    if z is not None:
                        # Answer a compressed request in kind and meter
                        # both directions of the compression win.
                        self._count("frames_compressed")
                        self._count("compressed_bytes_in", by=z[0])
                        self._count("raw_bytes_in", by=z[1])
                        raw_len = len(out)
                        out = _compress_frame(out)
                        self._count("compressed_bytes_out", by=len(out))
                        self._count("raw_bytes_out", by=raw_len)
                else:
                    if obj is None or not isinstance(obj, dict):
                        self._count("frame_errors")
                        continue
                    self._count("requests_v1")
                    ask = (
                        int(obj.get("ticket", 0)),
                        int(obj.get("tick", 0)),
                        np.asarray(obj.get("mask", []), bool),
                        None,
                    )
                    replies = self._answer([ask], rng)
                    if not replies:
                        continue  # lost: never answered
                    ticket, answered, labels = replies[0]
                    out = (json.dumps({
                        "ticket": ticket,
                        "labels": [int(v) for v in labels],
                        "answered": [bool(v) for v in answered],
                    }) + "\n").encode()
                self._sleep(rng)
                try:
                    f.write(out)
                    f.flush()
                except OSError:
                    return
        except (EOFError, ValueError):
            # Stream died (or header corrupted) inside a frame.
            self._count("frame_errors")

    def _answer(self, asks, rng):
        """Labels for each surviving ask: ``[(ticket, answered, labels)]``
        (a ``loss_prob`` casualty simply has no entry — never answered)."""
        out = []
        self._count("asks_served", by=len(asks))
        for ticket, tick, mask, _feats in asks:
            if self.loss_prob > 0.0 and rng.uniform() < self.loss_prob:
                continue
            labels = np.asarray(
                [expected_label(tick, s, self.n_out) for s in range(len(mask))],
                np.int32,
            )
            out.append((ticket, np.asarray(mask, bool), labels))
        return out

    def _sleep(self, rng) -> None:
        delay = self.delay_s
        if self.jitter_s > 0.0:
            delay += float(rng.uniform(0.0, self.jitter_s))
        if delay > 0.0:
            time.sleep(delay)

    def _handshake(self, f) -> bool:
        """Mutual challenge–response: send a nonce, require its keyed digest
        back (constant-time compare), then prove *our* knowledge of the
        secret by answering the client's nonce — all before serving a
        single label."""
        challenge = secrets_mod.token_hex(16)
        try:
            f.write((json.dumps({"challenge": challenge}) + "\n").encode())
            f.flush()
            line = f.readline()
        except OSError:
            return False
        try:
            reply = json.loads(line)
        except ValueError:
            # Not JSON — including a BINARY v2 frame from a no-secret
            # client that skipped straight to asking (UnicodeDecodeError
            # is a ValueError too): an unauthenticated connection.
            return False
        if not isinstance(reply, dict):
            return False
        if not hmac.compare_digest(
            str(reply.get("auth", "")), _digest(self.secret, challenge)
        ):
            return False
        ok = {"auth_ok": _digest(self.secret, str(reply.get("nonce", "")))}
        if reply.get("compress") == "zlib":
            # Compression negotiation rides the handshake: echo the
            # client's request so it knows zlib envelopes are understood.
            ok["compress"] = "zlib"
        try:
            f.write((json.dumps(ok) + "\n").encode())
            f.flush()
        except OSError:
            return False
        return True


# ---------------------------------------------------------------------------
# Client-side connection plumbing (shared by both clients)
# ---------------------------------------------------------------------------


def _authenticate(sock: socket.socket, wfile, secret: str,
                  compress: bool = False) -> bool:
    """Client half of the mutual HMAC handshake (see module docstring).
    Raises ``ConnectionError`` (after closing the socket) unless BOTH ends
    prove knowledge of the secret.  ``compress=True`` rides a
    ``"compress": "zlib"`` request on the auth line; the return value is
    whether the server echoed the grant (older servers simply don't)."""
    with sock.makefile("rb") as rf:
        try:
            hello = json.loads(rf.readline())
        except (OSError, ValueError):
            hello = None  # silent/closed/garbled server: not authenticated
        if not isinstance(hello, dict) or "challenge" not in hello:
            _shutdown_socket(sock)
            raise ConnectionError(
                "label server sent no auth challenge but a "
                "--teacher-secret is configured; refusing the "
                "unauthenticated connection"
            )
        nonce = secrets_mod.token_hex(16)
        auth_line = {
            "auth": _digest(secret, hello["challenge"]),
            "nonce": nonce,
        }
        if compress:
            auth_line["compress"] = "zlib"
        wfile.write((json.dumps(auth_line) + "\n").encode())
        wfile.flush()
        try:
            proof = json.loads(rf.readline())
        except (OSError, ValueError):
            proof = None
    ok = isinstance(proof, dict) and hmac.compare_digest(
        str(proof.get("auth_ok", "")), _digest(secret, nonce)
    )
    if not ok:
        _shutdown_socket(sock)
        raise ConnectionError(
            "label server failed to prove knowledge of the shared "
            "secret; refusing to train on its labels"
        )
    return bool(compress and proof.get("compress") == "zlib")


class _WireConnection:
    """The client-side connection plumbing both teachers share: dial +
    handshake, a buffered writer behind a write lock (two threads sharing
    a connection must never interleave partial frames), wire counters,
    and poison-on-failure — a write that raises ``OSError`` mid-frame
    leaves the stream desynchronized for the server, so the connection is
    marked ``broken`` and every later send skips the wire entirely
    (the callers map the silence to timeout → loss)."""

    def __init__(self, host: str, port: int, connect_timeout_s: float,
                 secret: Optional[str], compress: bool = False):
        self.sock = socket.create_connection((host, port),
                                             timeout=connect_timeout_s)
        self.wfile = self.sock.makefile("wb")
        # With a handshake, compression is negotiated (an older server
        # that doesn't echo the grant never sees a 0x03 byte); without
        # one there is no negotiation channel, so the caller's request is
        # taken at face value — the server answers envelopes in kind.
        if secret is not None:
            self.compress_granted = _authenticate(
                self.sock, self.wfile, secret, compress=compress)
        else:
            self.compress_granted = bool(compress)
        # connect_timeout_s governed the dial (and the auth readline);
        # steady-state reads must block indefinitely — reply deadlines are
        # enforced per ticket, not by a socket idle timeout.
        self.sock.settimeout(None)
        self.wlock = lockdebug.make_lock("rpc._WireConnection.wlock")
        self.broken = False
        self.messages = 0  # request messages actually written
        self.bytes = 0  # request bytes actually written

    def send(self, data: bytes) -> bool:
        """Write one whole frame/line; False when the connection is (or
        just became) dead — never writes after a half-frame poisoned it."""
        with self.wlock:
            if self.broken:
                return False
            try:
                self.wfile.write(data)
                self.wfile.flush()
            except OSError:
                self.broken = True
                _shutdown_socket(self.sock)
                return False
            self.messages += 1
            self.bytes += len(data)
            return True

    def close(self) -> None:
        # Shutdown BEFORE touching the write lock: a writer blocked in
        # flush() (peer stopped draining, send buffer full) holds the
        # lock, and only the shutdown can fail its write and free it —
        # lock-then-shutdown would deadlock close() against it.
        _shutdown_socket(self.sock)
        with self.wlock:
            with contextlib.suppress(OSError, ValueError):
                self.wfile.close()


def _reply_reader(sock: socket.socket, handler) -> None:
    """Reader-thread body both teachers share: decode every wire message
    (either format) and hand reply batches to ``handler(replies,
    arrived)``; exits when the socket dies (mid-frame included)."""
    try:
        with sock.makefile("rb") as f:
            for kind, obj, payload in _iter_wire(f):
                replies = _parse_wire_replies(kind, obj, payload)
                if replies:
                    handler(replies, time.monotonic())
    except (OSError, ValueError, EOFError):
        pass  # socket closed (or stream died mid-frame)


def _parse_wire_replies(kind, obj, payload) -> list[TeacherReply]:
    """Replies carried by one wire message, either format (empty when the
    message is not a reply — e.g. an unexpected auth challenge)."""
    if kind == "v2":
        if isinstance(obj, dict) and obj.get("kind") == "reply":
            return decode_replies(obj, payload)
        return []
    if not isinstance(obj, dict) or "ticket" not in obj:
        return []
    return [TeacherReply(
        ticket=int(obj["ticket"]),
        labels=np.asarray(obj["labels"], np.int32),
        answered=np.asarray(obj["answered"], bool),
    )]


# ---------------------------------------------------------------------------
# Per-tenant client (one connection per tenant)
# ---------------------------------------------------------------------------


class RpcTeacher:
    """``stream.Teacher`` over its own TCP socket, with timeout → loss.

    ``ask`` serializes the tick's features + mask and sends them (one wire
    message per ask — ``wire="v2"`` binary frames by default, ``"v1"``
    newline-JSON for back-compat); a reader thread validates each reply
    against its ticket's deadline *at arrival time* and queues the
    survivors in an inbox that ``poll`` drains — so a reply that made the
    deadline is never lost to a late poll (e.g. a tick stalled behind an
    XLA compile).  A ticket unanswered for ``timeout_s`` wall seconds
    leaves ``in_flight()`` and is mapped to loss: the runtime's pending
    ring entry is never claimed (it drains as ``queries_lost``), and a
    reply that misses its deadline is dropped at arrival (counted in
    ``timed_out``) — never delivered, so a stale straggler cannot train
    the fleet.

    Socket writes are serialized by a write lock (two threads sharing a
    connection must never interleave partial frames), and a write that
    raises ``OSError`` mid-frame marks the connection **dead**: the stream
    past a half-written frame is garbage to the server, so every later ask
    skips the wire entirely and maps straight to timeout → loss instead of
    desynchronizing the framing further.
    """

    def __init__(self, host: str, port: int, timeout_s: float = 5.0,
                 connect_timeout_s: float = 5.0, secret: Optional[str] = None,
                 wire: str = "v2", compress: bool = False):
        if wire not in WIRE_FORMATS:
            raise ValueError(f"unknown wire format {wire!r}; choose {WIRE_FORMATS}")
        if compress and wire != "v2":
            raise ValueError(
                "compress=True requires the v2 wire format (zlib envelopes "
                "carry v2 frames; v1 newline-JSON has no framing to wrap)")
        self.timeout_s = timeout_s
        self.wire = wire
        self._endpoint = f"{host}:{int(port)}"  # telemetry label only
        # Authentication (when configured) happens inside the connection
        # constructor, synchronously, before the reader thread owns the
        # socket.
        self._conn = _WireConnection(host, port, connect_timeout_s, secret,
                                     compress=compress)
        self._lock = lockdebug.make_lock("rpc.RpcTeacher._lock")  # pending map + inbox
        self._next_ticket = 0
        # ticket -> wall deadline; present == still in flight.
        self._pending: dict[int, float] = {}
        self._inbox: list[TeacherReply] = []
        self.timed_out = 0  # tickets whose reply missed (or never made) the deadline
        self._reader = threading.Thread(
            target=_reply_reader, args=(self._conn.sock, self._on_replies),
            daemon=True,
        )
        self._reader.start()

    @property
    def broken(self) -> bool:
        """True once a write failure poisoned the connection (every ask
        since maps to timeout → loss without touching the wire)."""
        return self._conn.broken

    @property
    def wire_messages(self) -> int:
        return self._conn.messages

    @property
    def wire_bytes(self) -> int:
        return self._conn.bytes

    def sync_telemetry(self, **labels) -> None:
        """Mirror wire meters into the enabled telemetry registry (see
        ``BatchedRpcClient.sync_telemetry``); no-op when telemetry is off."""
        tel = _telemetry.TELEMETRY
        if tel is None:
            return
        labels.setdefault("endpoint", self._endpoint)
        reg = tel.registry
        reg.set_counter("odl_rpc_wire_messages", self.wire_messages, **labels)
        reg.set_counter("odl_rpc_wire_bytes", self.wire_bytes, **labels)
        with self._lock:
            reg.set_counter("odl_rpc_timed_out", self.timed_out, **labels)

    def _on_replies(self, replies: list[TeacherReply], arrived: float) -> None:
        with self._lock:
            for reply in replies:
                deadline = self._pending.pop(reply.ticket, None)
                if deadline is None:
                    # Unknown ticket, or already expired (and counted) by
                    # _expire.
                    continue
                if arrived > deadline:
                    self.timed_out += 1  # straggler: timeout -> loss
                    continue
                self._inbox.append(reply)

    def ask(self, feats, mask, tick: int) -> int:
        with self._lock:
            ticket = self._next_ticket
            self._next_ticket += 1
            self._pending[ticket] = time.monotonic() + self.timeout_s
        mask_np = np.asarray(mask, bool)
        if self.wire == "v2":
            data = encode_asks([(ticket, int(tick), mask_np,
                                 np.asarray(feats, np.float32))])
            if self._conn.compress_granted:
                data = _compress_frame(data)
        else:
            data = (json.dumps({
                "ticket": ticket,
                "tick": int(tick),
                "mask": mask_np.tolist(),
                "feats": np.asarray(feats, np.float32).tolist(),
            }) + "\n").encode()
        # A dead connection leaves the ticket pending until its deadline,
        # then maps it to loss like any other timeout.
        self._conn.send(data)
        return ticket

    def _expire(self) -> None:
        now = time.monotonic()
        with self._lock:
            dead = [t for t, dl in self._pending.items() if dl < now]
            for t in dead:
                del self._pending[t]
                self.timed_out += 1

    def poll(self, tick: int) -> list[TeacherReply]:
        self._expire()  # never-arrived tickets past their deadline -> loss
        with self._lock:
            out, self._inbox = self._inbox, []
        return out

    def in_flight(self) -> int:
        self._expire()
        with self._lock:
            return len(self._pending)

    def close(self) -> None:
        self._conn.close()

    def __enter__(self) -> "RpcTeacher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# Batched shared-connection client (one connection per teacher host)
# ---------------------------------------------------------------------------


class BatchedRpcTeacher:
    """One tenant's ``stream.Teacher`` handle over a shared
    ``BatchedRpcClient`` — the unchanged protocol (ask/poll/in_flight,
    deadlines judged at arrival, timeout → loss), multiplexed with every
    other tenant's traffic onto one connection.  Create via
    ``BatchedRpcClient.tenant()``."""

    def __init__(self, client: "BatchedRpcClient", name: Optional[str] = None):
        self._client = client
        self.name = name
        self._inbox: list[TeacherReply] = []
        self.timed_out = 0  # this tenant's deadline casualties

    def ask(self, feats, mask, tick: int) -> int:
        return self._client._ask(self, feats, mask, tick)

    def poll(self, tick: int) -> list[TeacherReply]:
        return self._client._poll(self)

    def in_flight(self) -> int:
        return self._client._in_flight(self)

    def close(self) -> None:
        """No-op: the shared connection outlives any one tenant — close
        the ``BatchedRpcClient`` itself when every tenant is done."""

    def __enter__(self) -> "BatchedRpcTeacher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class BatchedRpcClient:
    """One shared connection to one teacher host, multiplexing every
    tenant's asks into batched v2 frames.

    ``tenant()`` mints a per-tenant ``BatchedRpcTeacher`` handle.  An ask
    from any handle is assigned a connection-global ticket, registered
    with its wall deadline, and queued; the queue is flushed as **one**
    framed request when either ``batch_max`` asks have accumulated or
    ``batch_window_s`` has elapsed since the first queued ask (a
    background flusher owns the window; ``batch_window_s=0`` flushes
    inline, degenerating to one frame per ask).  The reader thread demuxes
    each reply to the handle that asked, judging deadlines at arrival —
    semantics are bit-for-bit those of a per-tenant ``RpcTeacher``
    connection (locked by ``tests/test_rpc.py``); only the number of wire
    messages changes (measured by ``benchmarks/rpc_bench.py``).

    The HMAC handshake (``secret=``) runs once, here, per connection —
    not once per tenant.  Writes hold the write lock for the whole frame,
    and a mid-frame ``OSError`` marks the connection dead.  A dead
    connection gets **one** metered lazy reconnect-and-reask attempt at
    the next flush (``reconnects`` / ``asks_reasked``): a fresh dial +
    handshake + reader thread, with every still-pending unexpired ticket
    re-asked through it — original deadlines kept, so a reply that would
    have timed out anyway still maps to loss.  If the dial fails (or the
    fresh connection poisons again before the flush), the old behavior
    applies: queued and later asks map straight to timeout → loss until
    the *next* poisoning earns its own single attempt.
    """

    def __init__(self, host: str, port: int, timeout_s: float = 5.0,
                 connect_timeout_s: float = 5.0, secret: Optional[str] = None,
                 batch_window_s: float = DEFAULT_BATCH_WINDOW_S,
                 batch_max: int = DEFAULT_BATCH_MAX, compress: bool = False):
        if batch_max < 1:
            raise ValueError(f"batch_max must be >= 1, got {batch_max}")
        self.timeout_s = timeout_s
        self.batch_window_s = batch_window_s
        self.batch_max = int(batch_max)
        # Kept for the lazy reconnect path.
        self._host, self._port = host, int(port)
        self._connect_timeout_s = connect_timeout_s
        self._secret = secret
        self._compress = bool(compress)
        # The write lock + HMAC handshake live in the connection — once
        # per connection, i.e. once per teacher host, not once per tenant.
        self._conn = _WireConnection(host, port, connect_timeout_s, secret,
                                     compress=compress)
        self._cond = lockdebug.make_condition("rpc.BatchedRpcClient._cond")  # queue + pending + inboxes
        self._closed = False
        self._next_ticket = 0
        # ticket -> (owning handle, wall deadline, wire payload); present
        # == in flight.  The payload (tick, mask, feats) rides along so a
        # reconnect can re-ask in-flight tickets — bounded by the tenants'
        # ring capacities, same rationale as ``stream.PendingTicket.x``.
        self._pending: dict[
            int, tuple[BatchedRpcTeacher, float, tuple]
        ] = {}
        # Unflushed asks: (ticket, tick, mask, feats).
        self._queue: list[tuple[int, int, np.ndarray, np.ndarray]] = []
        self._flush_deadline: Optional[float] = None
        self._tenants: list[BatchedRpcTeacher] = []
        self._reconnect_lock = lockdebug.make_lock("rpc.BatchedRpcClient._reconnect_lock")
        self._reconnect_spent = False  # current broken conn's attempt used
        self.timed_out = 0  # deadline casualties across all tenants
        self.asks_sent = 0  # individual asks across all frames
        self.reconnects = 0  # successful lazy reconnects
        self.asks_reasked = 0  # in-flight asks re-sent after a reconnect
        self._reader = threading.Thread(
            target=_reply_reader, args=(self._conn.sock, self._on_replies),
            daemon=True,
        )
        self._reader.start()
        self._flusher: Optional[threading.Thread] = None
        if self.batch_window_s > 0:
            self._flusher = threading.Thread(target=self._flush_loop, daemon=True)
            self._flusher.start()

    @property
    def broken(self) -> bool:
        return self._conn.broken

    @property
    def wire_messages(self) -> int:
        return self._conn.messages

    @property
    def wire_bytes(self) -> int:
        return self._conn.bytes

    def sync_telemetry(self, **labels) -> None:
        """Mirror this connection's wire meters into the enabled telemetry
        registry (absolute writes, same pull-based discipline as
        ``StreamSession.sync_telemetry``); no-op when telemetry is off."""
        tel = _telemetry.TELEMETRY
        if tel is None:
            return
        labels.setdefault("endpoint", f"{self._host}:{self._port}")
        reg = tel.registry
        reg.set_counter("odl_rpc_wire_messages", self.wire_messages, **labels)
        reg.set_counter("odl_rpc_wire_bytes", self.wire_bytes, **labels)
        with self._cond:
            reg.set_counter("odl_rpc_asks_sent", self.asks_sent, **labels)
            reg.set_counter("odl_rpc_timed_out", self.timed_out, **labels)
            reg.set_counter("odl_rpc_reconnects", self.reconnects, **labels)
            reg.set_counter("odl_rpc_asks_reasked", self.asks_reasked,
                            **labels)

    def tenant(self, name: Optional[str] = None) -> BatchedRpcTeacher:
        """A new per-tenant ``stream.Teacher`` handle on this connection."""
        handle = BatchedRpcTeacher(self, name=name)
        with self._cond:
            self._tenants.append(handle)
        return handle

    # -- Teacher-protocol backend (called through the handles) -------------

    def _ask(self, handle: BatchedRpcTeacher, feats, mask, tick: int) -> int:
        mask_np = np.asarray(mask, bool)
        feats_np = np.asarray(feats, np.float32)
        batch = None
        with self._cond:
            ticket = self._next_ticket
            self._next_ticket += 1
            self._pending[ticket] = (
                handle, time.monotonic() + self.timeout_s,
                (int(tick), mask_np, feats_np),
            )
            self._queue.append((ticket, int(tick), mask_np, feats_np))
            if (len(self._queue) >= self.batch_max
                    or self.batch_window_s <= 0 or self._conn.broken):
                batch = self._take_locked()
            else:
                if self._flush_deadline is None:
                    self._flush_deadline = time.monotonic() + self.batch_window_s
                self._cond.notify_all()
        if batch:
            self._send(batch)
        return ticket

    def _poll(self, handle: BatchedRpcTeacher) -> list[TeacherReply]:
        self._expire()
        with self._cond:
            out, handle._inbox = handle._inbox, []
        return out

    def _in_flight(self, handle: BatchedRpcTeacher) -> int:
        self._expire()
        with self._cond:
            return sum(1 for ent in self._pending.values() if ent[0] is handle)

    # -- internals ---------------------------------------------------------

    def _take_locked(self):  # odlint: holds-lock(_cond)
        batch = self._queue[: self.batch_max]
        self._queue = self._queue[self.batch_max:]
        self._flush_deadline = (
            time.monotonic() + self.batch_window_s if self._queue else None
        )
        return batch

    def _flush_loop(self) -> None:
        while True:
            with self._cond:
                while not self._closed and self._flush_deadline is None:
                    self._cond.wait()
                if self._closed:
                    return
                now = time.monotonic()
                if self._flush_deadline > now:
                    # Wait out the window (new asks may refill batch_max
                    # and flush inline first — re-check on wake).
                    self._cond.wait(timeout=self._flush_deadline - now)
                    continue
                batch = self._take_locked()
            if batch:
                self._send(batch)

    def _send(self, batch) -> None:
        if self._conn.broken:
            # One lazy reconnect attempt per poisoned connection.  On
            # success every still-pending unexpired ticket — including
            # this batch's, registered in ``_ask`` — is re-asked through
            # the fresh connection, so the batch must not be sent again
            # here.  On failure the old behavior applies: the tickets
            # stay pending until their deadlines, then map to loss.
            self._reconnect_and_reask()
            return
        tel = _telemetry.TELEMETRY
        tok = tel.tracer.begin("rpc.flush") if tel is not None else None
        sent = self._conn.send(self._frame(batch))
        if sent:
            with self._cond:
                self.asks_sent += len(batch)
        if tok is not None:
            tel.tracer.end(tok, asks=len(batch), sent=sent)
            tel.registry.observe("odl_rpc_batch_occupancy", len(batch))

    def _frame(self, batch) -> bytes:
        data = encode_asks(batch)
        # Read the grant off the *current* connection: a reconnect
        # renegotiates, and an older server may refuse what the original
        # connection had granted.
        if self._conn.compress_granted:
            data = _compress_frame(data)
        return data

    def _reconnect_and_reask(self) -> None:
        with self._reconnect_lock:
            if self._closed:
                return  # nobody is left to consume the replies
            if not self._conn.broken:
                return  # another thread already swapped in a live conn
            if self._reconnect_spent:
                return  # this poisoning's single attempt is used up
            self._reconnect_spent = True
            try:
                conn = _WireConnection(self._host, self._port,
                                       self._connect_timeout_s, self._secret,
                                       compress=self._compress)
            except OSError:
                return
            old, self._conn = self._conn, conn
            threading.Thread(
                target=_reply_reader, args=(conn.sock, self._on_replies),
                daemon=True,
            ).start()
            old.close()
            tel = _telemetry.TELEMETRY
            if tel is not None:
                tel.tracer.event("rpc.reconnect",
                                 endpoint=f"{self._host}:{self._port}")
            with self._cond:
                self.reconnects += 1
                # A later poisoning earns its own single attempt.
                self._reconnect_spent = False
                # Every pending ticket's frame either died with the old
                # socket or was answered on it after it went half-dead —
                # either way the reply can now only arrive via a re-ask.
                # Original deadlines are kept: a reply that would have
                # timed out anyway still maps to loss.
                now = time.monotonic()
                resend = [
                    (t, *payload)
                    for t, (_, dl, payload) in sorted(self._pending.items())
                    if dl >= now
                ]
            for i in range(0, len(resend), self.batch_max):
                chunk = resend[i:i + self.batch_max]
                if self._conn.send(self._frame(chunk)):
                    with self._cond:
                        self.asks_sent += len(chunk)
                        self.asks_reasked += len(chunk)

    def _on_replies(self, replies: list[TeacherReply], arrived: float) -> None:
        with self._cond:
            for reply in replies:
                ent = self._pending.pop(reply.ticket, None)
                if ent is None:
                    continue  # unknown or already expired
                handle, deadline = ent[0], ent[1]
                if arrived > deadline:
                    handle.timed_out += 1
                    self.timed_out += 1
                    continue
                handle._inbox.append(reply)

    def _expire(self) -> None:
        now = time.monotonic()
        with self._cond:
            dead = [t for t, ent in self._pending.items() if ent[1] < now]
            for t in dead:
                handle = self._pending.pop(t)[0]
                handle.timed_out += 1
                self.timed_out += 1

    def close(self) -> None:
        with self._cond:
            self._closed = True
            batch = self._take_locked() if self._queue else None
            self._cond.notify_all()
        if batch:
            self._send(batch)  # best effort: don't strand queued asks
        if self._flusher is not None:
            self._flusher.join(timeout=2.0)
        self._conn.close()

    def __enter__(self) -> "BatchedRpcClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def server_stats(host: str, port: int, secret: Optional[str] = None,
                 timeout_s: float = 5.0) -> dict:
    """Scrape a running ``LabelServer``'s counters over the wire.

    Dials a fresh connection, performs the HMAC handshake when a secret is
    configured, sends one v2 ``{"kind": "stats"}`` frame, and returns the
    server's counter dict (see ``LabelServer.stats``).  The label server
    usually lives in another process, so this is the only way a client-side
    report can include its numbers.
    """
    sock = socket.create_connection((host, port), timeout=timeout_s)
    try:
        wfile = sock.makefile("wb")
        if secret is not None:
            _authenticate(sock, wfile, secret)
        wfile.write(_encode_frame({"kind": "stats", "payload_len": 0}, b""))
        wfile.flush()
        with sock.makefile("rb") as rf:
            for kind, obj, _payload in _iter_wire(rf):
                if (kind == "v2" and isinstance(obj, dict)
                        and obj.get("kind") == "stats"):
                    return dict(obj.get("stats") or {})
    finally:
        _shutdown_socket(sock)
    raise ConnectionError(
        "label server closed the connection without answering the stats "
        "request (pre-stats server version?)")


# ---------------------------------------------------------------------------
# Loopback subprocess helper
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def loopback_server(n_out: int = 6, delay_s: float = 0.0,
                    secret: Optional[str] = None, loss_prob: float = 0.0,
                    jitter_s: float = 0.0):
    """Spawn ``python -m repro.engine.rpc`` as a subprocess label server on
    an ephemeral loopback port, on the CPU backend; yields ``(host, port)``
    and tears the process down on exit."""
    src_root = str(pathlib.Path(__file__).resolve().parents[2])
    env = dict(os.environ)
    env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
    # The label server computes on the host only: it must never claim the
    # chip the parent serves on (nor silently fall back to another device).
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "repro.engine.rpc", "--port", "0",
           "--n-out", str(n_out), "--delay-ms", str(int(delay_s * 1000)),
           "--loss-prob", str(loss_prob),
           "--jitter-ms", str(int(jitter_s * 1000))]
    if secret is not None:
        cmd += ["--secret", secret]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, env=env, text=True)
    try:
        line = proc.stdout.readline()
        if not line.startswith("PORT "):
            raise RuntimeError(f"label server failed to start: {line!r}")
        yield "127.0.0.1", int(line.split()[1])
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def _selftest() -> int:
    """Round trips over a subprocess loopback server (CI smoke): v2 and v1
    per-tenant clients, the batched shared-connection client with two
    tenants, then HMAC auth and an unauthenticated client against a
    secured server (must get nothing)."""
    s, n_out = 4, 6
    feats = np.zeros((s, 3), np.float32)
    mask = np.ones((s,), bool)

    def drain(teacher, timeout=10.0):
        deadline = time.monotonic() + timeout
        replies = []
        while not replies and time.monotonic() < deadline:
            replies = teacher.poll(0)
            if not replies and teacher.in_flight() == 0:
                replies = teacher.poll(0)
                break
            time.sleep(0.01)
        return replies

    def roundtrip(host, port, secret=None, timeout_s=10.0, wire="v2"):
        with RpcTeacher(host, port, timeout_s=timeout_s, secret=secret,
                        wire=wire) as teacher:
            ticket = teacher.ask(feats, mask, tick=3)
            replies = drain(teacher, timeout=min(timeout_s, 10.0))
            return ticket, replies

    want = [expected_label(3, i, n_out) for i in range(s)]
    with loopback_server(n_out=n_out) as (host, port):
        for wire in WIRE_FORMATS:
            ticket, replies = roundtrip(host, port, wire=wire)
            assert replies and replies[0].ticket == ticket, f"no {wire} reply"
            assert replies[0].labels.tolist() == want, (wire, replies[0].labels)
        # Batched shared connection: two tenants, one socket, one frame
        # carrying both asks (window generous enough to coalesce them).
        with BatchedRpcClient(host, port, timeout_s=10.0,
                              batch_window_s=0.2) as client:
            a, b = client.tenant("a"), client.tenant("b")
            a.ask(feats, mask, tick=3)
            b.ask(feats, mask, tick=3)
            ra, rb = drain(a), drain(b)
            assert ra and ra[0].labels.tolist() == want, "batched tenant a"
            assert rb and rb[0].labels.tolist() == want, "batched tenant b"
            assert client.wire_messages == 1 and client.asks_sent == 2, (
                client.wire_messages, client.asks_sent)
    # Compressed envelopes against an in-process server (for counter
    # access): answered in kind, metered, and byte-identical labels.
    # A wide tick so the win is unambiguous (real feature payloads
    # dominate the frame, exactly the bytes zlib earns its keep on).
    s_z = 64
    feats_z = np.zeros((s_z, 8), np.float32)
    want_z = [expected_label(3, i, n_out) for i in range(s_z)]
    server = LabelServer(port=0, n_out=n_out).start()
    try:
        with RpcTeacher("127.0.0.1", server.port, timeout_s=10.0,
                        compress=True) as teacher:
            ticket = teacher.ask(feats_z, np.ones((s_z,), bool), tick=3)
            replies = drain(teacher)
            assert replies and replies[0].ticket == ticket, "no zlib reply"
            assert replies[0].labels.tolist() == want_z, replies[0].labels
        assert server.frames_compressed == 1, server.frames_compressed
        assert server.raw_bytes_in > server.compressed_bytes_in > 0, (
            server.raw_bytes_in, server.compressed_bytes_in)
        assert server.raw_bytes_out >= server.compressed_bytes_out > 0, (
            server.raw_bytes_out, server.compressed_bytes_out)
    finally:
        server.close()
    with loopback_server(n_out=n_out, secret="s3cr3t") as (host, port):
        ticket, replies = roundtrip(host, port, secret="s3cr3t")
        assert replies and replies[0].labels.tolist() == want, "auth roundtrip"
        # Unauthenticated client: the server closes the connection; the ask
        # times out into loss and no label ever arrives.
        _, replies = roundtrip(host, port, secret=None, timeout_s=0.5)
        assert not replies, "unauthenticated client must receive nothing"
    # odlint: disable=ODL005 -- CLI selftest result line, not library code
    print("rpc selftest OK (v1 + v2 + zlib + batched + hmac + reject):", want)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--n-out", type=int, default=6)
    ap.add_argument("--delay-ms", type=int, default=0,
                    help="server-side per-request delay (timeout testing)")
    ap.add_argument("--jitter-ms", type=int, default=0,
                    help="extra uniform per-reply delay in [0, J] ms")
    ap.add_argument("--loss-prob", type=float, default=0.0,
                    help="fraction of asks never answered (client deadline "
                    "maps them to loss)")
    ap.add_argument("--secret", default=None,
                    help="shared secret: require the HMAC challenge-response "
                    "handshake on every connection")
    ap.add_argument("--selftest", action="store_true",
                    help="spawn a loopback server and round-trip one ask")
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()
    server = LabelServer(port=args.port, n_out=args.n_out,
                         delay_s=args.delay_ms / 1000.0, secret=args.secret,
                         loss_prob=args.loss_prob,
                         jitter_s=args.jitter_ms / 1000.0)
    # odlint: disable=ODL005 -- CLI contract: launchers parse this PORT line
    print(f"PORT {server.port}", flush=True)
    server.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
