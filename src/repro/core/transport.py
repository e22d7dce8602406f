"""Worker transports — the parent's view of a shard server, anywhere.

The multi-server federation tier (``repro.core.store.
ProcessShardedModelStore``) talks to its shard workers exclusively through
the small interface defined here: ``put`` (fire-and-forget submit), ``rpc``
/ ``rpc_recv`` (one replying command, bounded), ``restart`` (crash
recovery: reset the worker from a fresh seed blob so the parent can replay
its journal), ``alive``/``kill``/``discard``/``stop``.  Three flavors
implement it:

  * ``InprocessWorkerHandle`` (``repro.core.server_proc``) — deterministic
    in-process emulation; what ``runtime_sim`` and the fast tests use.
  * ``ProcessWorkerHandle`` (``repro.core.server_proc``) — spawned worker
    processes on mp.Queues; single-host multi-core.
  * ``TcpWorkerHandle`` (here) — a worker on **another host**, reached over
    a TCP socket speaking length-prefixed msgpack frames.  The standalone
    server side is ``repro.launch.shard_server``.

Every payload crossing any of the three uses the identical codec
(``repro.checkpoint.msgpack_ckpt.packb`` / ``unpackb_np``), and every TCP
frame follows the normative spec in ``docs/WIRE_PROTOCOL.md`` byte for
byte — ``tests/test_wire_protocol.py`` holds the golden-bytes tests.

Frame layout (all integers big-endian):

    offset  size  field
    0       2     magic      b"FC"
    2       1     version    0x04 (see the versioning rules in the spec)
    3       1     kind       0x00 command (parent->worker),
                             0x01 reply   (worker->parent)
    4       4     length     payload byte length (u32)
    8       8     trace_ctx  telemetry trace context (u64; 0 = untraced) —
                             propagates one submit's span chain across the
                             TCP boundary (``repro.obs.record``)
    16      len   payload    msgpack message (checkpoint array ext codec)

The connection handshake doubles as crash recovery: every (re)connect
sends a ``["seed", shard_idx, seed_blob]`` command and waits for the
``["seeded", shard_idx]`` reply — the worker rebuilds its state from the
blob (the parent's authoritative mirrors), after which the parent replays
its journal of unacked updates.  Replayed submits are deduplicated
worker-side by their monotone update ``seq`` (see
``ShardWorker.held``), so a reconnect mid-flight neither loses nor
double-counts updates.
"""

from __future__ import annotations

import os
import pathlib
import select
import socket
import struct
import subprocess
import sys
import threading
import time

from repro.checkpoint.msgpack_ckpt import packb
from repro.checkpoint.msgpack_ckpt import unpackb_np as unpackb
from repro.launch.device import host_only_env
from repro.obs import clock
from repro.obs.record import current_trace

FRAME_MAGIC = b"FC"
WIRE_VERSION = 4
KIND_COMMAND = 0x00
KIND_REPLY = 0x01
_HEADER = struct.Struct(">2sBBIQ")      # magic, version, kind, length,
HEADER_SIZE = _HEADER.size              # trace_ctx — 16 bytes
MAX_FRAME_BYTES = 1 << 31               # sanity bound on declared lengths


class WorkerUnavailable(RuntimeError):
    """The shard worker died (or was never reachable) mid-command."""


class WorkerTimeout(WorkerUnavailable):
    """The shard worker is alive but missed the bounded reply deadline."""


class FrameProtocolError(RuntimeError):
    """The peer sent bytes that are not a FedCCL wire frame."""


class FrameVersionError(FrameProtocolError):
    """The peer speaks a different wire version — refuse loudly instead of
    unpacking garbage params (see the versioning rules in
    ``docs/WIRE_PROTOCOL.md``)."""


# -------------------------------------------------------------------- frames

def pack_frame(payload: bytes, kind: int = KIND_COMMAND,
               trace_ctx: int = 0) -> bytes:
    """One wire frame, exactly as specified in ``docs/WIRE_PROTOCOL.md``."""
    return _HEADER.pack(FRAME_MAGIC, WIRE_VERSION, kind, len(payload),
                        trace_ctx) + payload


def parse_header(header: bytes) -> tuple[int, int, int]:
    """Validate a 16-byte frame header; returns (kind, payload_length,
    trace_ctx).  Raises ``FrameProtocolError`` / ``FrameVersionError`` with
    actionable messages instead of ever yielding garbage params
    downstream."""
    magic, version, kind, length, trace_ctx = _HEADER.unpack(header)
    if magic != FRAME_MAGIC:
        raise FrameProtocolError(
            f"not a FedCCL frame (magic {magic!r}, expected {FRAME_MAGIC!r})")
    if version != WIRE_VERSION:
        raise FrameVersionError(
            f"peer speaks wire version {version}, this build speaks "
            f"{WIRE_VERSION} — upgrade the older side (frames are not "
            f"cross-version compatible; see docs/WIRE_PROTOCOL.md)")
    if kind not in (KIND_COMMAND, KIND_REPLY):
        raise FrameProtocolError(f"unknown frame kind 0x{kind:02x}")
    if length > MAX_FRAME_BYTES:
        raise FrameProtocolError(f"frame length {length} exceeds sanity "
                                 f"bound {MAX_FRAME_BYTES}")
    return kind, length, trace_ctx


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks = []
    while n:
        chunk = sock.recv(min(n, 1 << 20))
        if not chunk:
            raise ConnectionError("peer closed the connection mid-frame")
        chunks.append(chunk)
        n -= len(chunk)
    return b"".join(chunks)


def send_frame(sock: socket.socket, payload: bytes,
               kind: int = KIND_COMMAND, trace_ctx: int = 0) -> int:
    """Write one frame; returns bytes put on the wire."""
    frame = pack_frame(payload, kind, trace_ctx)
    sock.sendall(frame)
    return len(frame)


def recv_frame(sock: socket.socket) -> tuple[int, bytes, int]:
    """Read one frame; returns (kind, payload, trace_ctx).  Raises
    ``ConnectionError`` on EOF, ``TimeoutError`` on the socket's own
    deadline, and the frame errors above on malformed bytes."""
    kind, length, trace_ctx = parse_header(_recv_exact(sock, HEADER_SIZE))
    return kind, (_recv_exact(sock, length) if length else b""), trace_ctx


def parse_host(spec: str) -> tuple[str, int]:
    """``"host:port"`` -> ``(host, port)`` (IPv6 literals in brackets)."""
    s = str(spec).strip()
    if s.startswith("["):                         # [::1]:9000
        host, _, rest = s[1:].partition("]")
        port = rest.lstrip(":")
    else:
        host, _, port = s.rpartition(":")
    if not host or not port:
        raise ValueError(f"server host {spec!r} is not 'host:port'")
    return host, int(port)


# ------------------------------------------------------------ loopback spawn

class LoopbackShardServers:
    """Spawn N standalone shard servers (``repro.launch.shard_server``) on
    loopback ephemeral ports — the zero-config way to run the TCP topology
    on one machine (quickstart ``--topology tcp``, the loopback equivalence
    tests, and the bench's TCP column).

    In production the servers are long-lived peers under their own
    supervisor; this helper IS that supervisor for local runs: ``hosts``
    feeds ``FedCCLConfig.server_hosts``, ``kill``/``respawn`` inject and
    recover crashes (same address, so the parent's reconnect picks the
    fresh server up), and the context manager tears everything down.
    """

    def __init__(self, n: int, *, startup_timeout: float = 60.0):
        self.startup_timeout = float(startup_timeout)
        self._src = str(pathlib.Path(__file__).resolve().parents[2])
        self.procs: list = [None] * n
        self.ports: list[int] = [0] * n
        for i in range(n):
            self._spawn(i, port=0)

    def _spawn(self, i: int, port: int):
        env = host_only_env()      # the parent owns the chip
        env["PYTHONPATH"] = self._src + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.shard_server",
             "--host", "127.0.0.1", "--port", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env)
        deadline = clock.monotonic() + self.startup_timeout
        line = ""
        while True:
            if clock.monotonic() >= deadline:
                proc.kill()
                raise RuntimeError(
                    f"shard server {i} did not announce within "
                    f"{self.startup_timeout:.0f}s")
            # select-gate the pipe: a bare readline() would block past the
            # deadline on a server that hangs before announcing
            ready, _, _ = select.select([proc.stdout], [], [], 0.25)
            if not ready:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"shard server {i} exited with {proc.returncode} "
                        f"before listening")
                continue
            line = proc.stdout.readline()
            if "SHARD_SERVER_LISTENING" in line:
                break
            if not line and proc.poll() is not None:
                raise RuntimeError(
                    f"shard server {i} exited with {proc.returncode} "
                    f"before listening")
        self.procs[i] = proc
        self.ports[i] = int(line.rsplit("port=", 1)[1])

    @property
    def hosts(self) -> list[str]:
        """``FedCCLConfig.server_hosts``-shaped addresses."""
        return [f"127.0.0.1:{p}" for p in self.ports]

    def kill(self, i: int):
        """SIGKILL one server — the crash-injection hook."""
        self.procs[i].kill()
        self.procs[i].wait(10.0)

    def respawn(self, i: int):
        """Supervisor restart on the SAME port, so the parent's journaled
        reconnect finds the fresh server at the old address."""
        if self.procs[i].poll() is None:
            self.kill(i)
        self._spawn(i, port=self.ports[i])

    def close(self):
        for proc in self.procs:
            if proc is not None and proc.poll() is None:
                proc.terminate()
        for proc in self.procs:
            if proc is not None:
                try:
                    proc.wait(10.0)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait(10.0)
                if proc.stdout is not None:
                    proc.stdout.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# ----------------------------------------------------------------- interface

class Transport:
    """One shard server, as the parent store sees it.

    Contract shared by the in-process emulation, the spawned-process
    handle, and the TCP handle:

      * ``put(raw)`` — fire-and-forget command; must never raise on a dead
        worker (the journal keeps the update; the next replying command
        surfaces the failure and triggers recovery).
      * ``rpc(raw, timeout)`` / ``rpc_recv(timeout)`` — one replying
        command (callers serialize per shard via the store's rpc lock);
        raises ``WorkerUnavailable`` if the worker is gone and
        ``WorkerTimeout`` if it misses the deadline.
      * ``restart(seed_blob)`` — replace/reset the worker from the
        parent's mirrors; the caller replays its journal right after.
      * ``spawns`` — cumulative (re)starts, for respawn observability.
      * ``tx_bytes`` / ``rx_bytes`` — wire-payload byte counters (the
        bytes-on-wire metric in ``benchmarks/multiproc_store.py``).
    """

    idx: int
    spawns: int = 0
    tx_bytes: int = 0
    rx_bytes: int = 0

    def put(self, raw: bytes):
        raise NotImplementedError

    def rpc(self, raw: bytes, timeout: float) -> bytes:
        raise NotImplementedError

    def rpc_recv(self, timeout: float) -> bytes:
        raise NotImplementedError

    def restart(self, seed_blob: bytes):
        raise NotImplementedError

    def alive(self) -> bool:
        raise NotImplementedError

    def kill(self):
        raise NotImplementedError

    def discard(self):
        raise NotImplementedError

    def stop(self, timeout: float):
        raise NotImplementedError


# ---------------------------------------------------------------- tcp flavor

class TcpWorkerHandle(Transport):
    """Parent-side endpoint of a shard server on another host
    (``repro.launch.shard_server``).

    The socket carries the identical messages the mp.Queue transport
    carries, wrapped in the frames above.  Sends are guarded by a lock
    (many submit threads share one socket); receives only happen from the
    replying-command paths, which the store already serializes per shard.

    Failure model: any socket error marks the connection broken.  ``put``
    never raises (the journal is the source of truth — parity with
    mp.Queue's buffering semantics); the next ``rpc``/``rpc_recv`` raises
    ``WorkerUnavailable``, upon which the store calls ``restart`` —
    reconnect (with bounded retry, so a supervisor-restarted server on the
    same address is picked up), re-seed, then journal replay.  The worker's
    held-seq dedup makes the replay idempotent.
    """

    def __init__(self, shard_idx: int, seed_blob: bytes, address,
                 connect_timeout: float = 30.0):
        self.idx = shard_idx
        self.address = (address if isinstance(address, tuple)
                        else parse_host(address))
        self.connect_timeout = float(connect_timeout)
        self.spawns = 0
        self.tx_bytes = 0
        self.rx_bytes = 0
        self._send_lock = threading.Lock()
        self._sock: socket.socket | None = None
        self._broken = True
        self._start(seed_blob)

    # ------------------------------------------------------------- lifecycle
    def _start(self, seed_blob: bytes):
        deadline = clock.monotonic() + self.connect_timeout
        last_err: Exception | None = None
        while True:
            try:
                sock = socket.create_connection(self.address, timeout=5.0)
                break
            except OSError as e:
                last_err = e
                if clock.monotonic() >= deadline:
                    raise WorkerUnavailable(
                        f"shard server {self.address[0]}:{self.address[1]} "
                        f"unreachable within {self.connect_timeout:.0f}s: "
                        f"{e}") from e
                time.sleep(0.2)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sock = sock
        self._broken = False
        # handshake: seed the worker from the parent mirrors and wait for
        # the ack — connect failures surface here, not on the first drain
        try:
            self._send(packb(["seed", self.idx, seed_blob]))
            reply = unpackb(self._recv(self.connect_timeout))
        except WorkerUnavailable:
            raise
        except Exception as e:
            self._mark_broken()
            raise WorkerUnavailable(
                f"shard server {self.address[0]}:{self.address[1]} failed "
                f"the seed handshake: {type(e).__name__}: {e}") from e
        if reply[0] == "error":
            self._mark_broken()
            raise WorkerUnavailable(
                f"shard server {self.address[0]}:{self.address[1]} rejected "
                f"the seed: {reply[2]}")
        assert reply[0] == "seeded" and int(reply[1]) == self.idx, reply
        self.spawns += 1

    def _mark_broken(self):
        self._broken = True
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    # ----------------------------------------------------------------- wire
    def _send(self, raw: bytes):
        with self._send_lock:
            # local capture: a concurrent _mark_broken (the recv side holds
            # no send lock) may null self._sock between check and use
            sock = self._sock
            if self._broken or sock is None:
                raise WorkerUnavailable(
                    f"shard server {self.address[0]}:{self.address[1]} "
                    f"connection is down")
            try:
                # the thread-local trace context (set by the store's submit
                # path for sampled submits, and by drain RPCs) rides the
                # frame header across the TCP boundary
                self.tx_bytes += send_frame(sock, raw, KIND_COMMAND,
                                            current_trace())
            except OSError as e:
                self._mark_broken()
                raise WorkerUnavailable(
                    f"send to shard server {self.address[0]}:"
                    f"{self.address[1]} failed: {e}") from e

    def _recv(self, timeout: float) -> bytes:
        # local capture — see _send: a concurrent send-side _mark_broken
        # must surface as WorkerUnavailable (the recovery path), never as
        # an AttributeError on a nulled socket
        sock = self._sock
        if self._broken or sock is None:
            raise WorkerUnavailable(
                f"shard server {self.address[0]}:{self.address[1]} "
                f"connection is down")
        try:
            sock.settimeout(max(timeout, 1e-3))
            kind, payload, _ = recv_frame(sock)
        except TimeoutError:
            raise WorkerTimeout(
                f"shard server {self.address[0]}:{self.address[1]} missed "
                f"the {timeout:.1f}s reply deadline") from None
        except (ConnectionError, OSError, FrameProtocolError) as e:
            self._mark_broken()
            raise WorkerUnavailable(
                f"recv from shard server {self.address[0]}:"
                f"{self.address[1]} failed: {type(e).__name__}: {e}") from e
        if kind != KIND_REPLY:
            self._mark_broken()
            raise WorkerUnavailable(
                f"shard server {self.address[0]}:{self.address[1]} sent a "
                f"command frame where a reply was expected")
        self.rx_bytes += HEADER_SIZE + len(payload)
        return payload

    # ------------------------------------------------------------- interface
    def put(self, raw: bytes):
        try:
            self._send(raw)
        except WorkerUnavailable:
            pass        # journaled; the next replying command recovers

    def rpc(self, raw: bytes, timeout: float) -> bytes:
        self._send(raw)
        return self._recv(timeout)

    def rpc_recv(self, timeout: float) -> bytes:
        return self._recv(timeout)

    def restart(self, seed_blob: bytes):
        """Reconnect + re-seed (the server process is managed externally —
        a supervisor restart on the same address is transparently picked
        up).  The caller replays the journal right after, and the fresh
        worker's held-seq dedup drops any duplicate."""
        self._mark_broken()
        self._start(seed_blob)

    def alive(self) -> bool:
        return not self._broken

    def kill(self):
        """Drop the connection (crash injection for reconnect tests).  The
        remote server survives; only this session dies."""
        self._mark_broken()

    def discard(self):
        self._mark_broken()

    def stop(self, timeout: float):
        """End the session gracefully: the server replies and goes back to
        accepting the next parent; it is NOT shut down (its lifecycle
        belongs to its own supervisor — see docs/OPERATIONS.md)."""
        try:
            reply = unpackb(self.rpc(packb(["stop"]), timeout))
            assert reply[0] == "stopped"
        except WorkerUnavailable:
            pass
        finally:
            self._mark_broken()
