"""MessageTransport — async TCP message substrate (ref: ``NIOTransport``).

Re-creation of the reference's from-scratch NIO layer
(``nio/NIOTransport.java:115``: single selector thread, non-blocking
connect/accept/read/write, per-destination pending-write queues with
congestion back-pressure, auto-reconnect; wire format = 4-byte magic
preamble + 4-byte length + payload, ``NIOTransport.java:483-524``) on top
of one asyncio event loop running in a dedicated thread, so synchronous
callers (the manager tick loop) can ``send_to_id`` without owning a loop.

Differences by design, not omission: SSL is delegated to asyncio's native
TLS support (``ssl_context`` arg vs the reference's hand-rolled SSLEngine
wrapper, ``SSLDataProcessingWorker.java:59``); byte-order and magic match
no one — this framework's peers only speak to each other.
"""

from __future__ import annotations

import asyncio
import struct
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from ..obs import gplog
from ..obs.metrics import ROW_BOUNDS
from ..obs.spans import span

_LOG = gplog.get_logger("transport")
MAGIC = 0x47503270  # "GP2p"
_HDR = struct.Struct(">II")  # magic, payload length
MAX_PAYLOAD = 256 * 1024 * 1024
CONGESTION_LIMIT = 4096  # per-peer queued messages before drops (isCongested)



class _Latest:
    """Queue marker for :meth:`MessageTransport.send_latest_to_id`: the
    sender encodes whatever item the slot holds when its turn comes."""

    __slots__ = ("slot",)

    def __init__(self, slot: str):
        self.slot = slot


# latest_encoder(item, base) -> (frame, rows, next base): called in
# ``_sender`` when a latest-wins slot's turn comes.  ``item`` is whatever
# ``send_latest_to_id`` was last given for that peer (for the blob a
# marker: the frame is cut from the sender's mirror as it stands then);
# ``base`` is what the encoder named as the next base for the frame last
# written AND drained on the connection that is open now (for the blob
# its tick), None on a new connection or after ``forget_latest_base``.
# ``rows`` is None for a frame that stands alone, else the rows of a
# delta against ``base``.
LatestEncoder = Callable[[Any, Any], Tuple[bytes, Optional[int], Any]]

# handler(payload: bytes, sender: (host, port), reply) -> None
# ``reply(bytes)`` queues a frame back on the SAME connection (needed for
# client request/response: clients don't listen on a port).
Handler = Callable[[bytes, Tuple[str, int], Callable[[bytes], None]], None]


class MessageTransport:
    def __init__(
        self,
        my_id: int,
        node_config,
        handler: Handler,
        listen_host: Optional[str] = None,
        listen_port: Optional[int] = None,
        ssl_context=None,
        ssl_server_context=None,
        ssl_client_context=None,
        metrics=None,
        latest_encoder: Optional[LatestEncoder] = None,
    ):
        self.my_id = int(my_id)
        # the owning node's MetricsRegistry (None: a transport outside
        # any node counts nothing): the latest-wins frames' accounting
        # and the blob.encode / blob.send spans
        self.metrics = metrics
        if metrics is not None:
            # registered at 0: a snapshot shows a counter that never
            # fired apart from a program that has no such counter
            for key in ("blob_frames_superseded", "blob_frames_written",
                        "blob_bytes_written", "blob_bytes_sent",
                        "blob_frames_delta", "blob_frames_full"):
                metrics.count(key, 0)
            # what a frame waits for this loop's turn, out and in: a
            # reply from reply() to its write on the loop thread, and the
            # loop's own lateness (_probe_lag)
            metrics.register_hist("transport_reply_lag_s")
            metrics.register_hist("transport_loop_lag_s")
        # frames of a latest-wins slot are encoded at the sender's turn,
        # against the base THIS connection last carried (see LatestEncoder)
        self._latest_encoder = latest_encoder
        self.node_config = node_config
        self.handler = handler
        if listen_host is None or listen_port is None:
            listen_host, listen_port = node_config.get_node_address(my_id)
        self.listen_host, self.listen_port = listen_host, int(listen_port)
        # TLS: a mesh peer both LISTENS and DIALS, and asyncio requires a
        # TLS_SERVER context on the listener and a TLS_CLIENT context on
        # outbound connects — one context cannot serve both directions.
        # `ssl_context` remains as a single-role convenience.
        self._ssl_server = ssl_server_context or ssl_context
        self._ssl_client = ssl_client_context or ssl_context
        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self._loop.run_forever, name=f"transport-{my_id}", daemon=True
        )
        self._writers: Dict[Tuple[str, int], asyncio.StreamWriter] = {}
        # accepted connections' writers, while their read loops run
        self._inbound: set = set()
        self._queues: Dict[Tuple[str, int], asyncio.Queue] = {}
        self._senders: Dict[Tuple[str, int], asyncio.Task] = {}
        # (addr, slot) -> newest unsent item of a latest-wins slot
        self._latest: Dict[Tuple[Tuple[str, int], str], Any] = {}
        self._latest_lock = threading.Lock()
        # peers that said they do not hold the base of what they were
        # sent: their next latest-wins frame stands alone
        self._base_forgotten: set = set()
        self._server: Optional[asyncio.AbstractServer] = None
        self._lag_probe: Optional[asyncio.TimerHandle] = None
        self._reply_lags: list = []  # this turn's, loop thread only
        self._started = threading.Event()
        self._stopped = False
        self.n_sent = 0
        self.n_rcvd = 0
        self.n_dropped = 0  # congestion drops (NIOInstrumenter analog)
        # WAN emulation hook (JSONDelayEmulator analog, nio/
        # JSONDelayEmulator.java:36-56): delay_fn(addr) -> seconds of
        # artificial link delay before a frame is queued for delivery
        self.delay_fn: Optional[Callable[[Tuple[str, int]], float]] = None

    # ---- lifecycle -----------------------------------------------------
    def start(self) -> None:
        self._thread.start()
        fut = asyncio.run_coroutine_threadsafe(self._start_server(), self._loop)
        fut.result(timeout=10)
        self._started.set()

    async def _start_server(self) -> None:
        self._server = await asyncio.start_server(
            self._on_connection, self.listen_host, self.listen_port,
            ssl=self._ssl_server,
        )
        if self.listen_port == 0 and self._server.sockets:
            # ephemeral bind: report the kernel-chosen port (race-free
            # alternative to probe-and-rebind in tests/tools)
            self.listen_port = self._server.sockets[0].getsockname()[1]
        if self.metrics is not None:
            self._probe_lag(None)

    LAG_PROBE_S = 0.1

    def _probe_lag(self, due: Optional[float]) -> None:
        """The event loop's health probe: a timer every LAG_PROBE_S that
        observes how late the loop ran it (``transport_loop_lag_s``) —
        what a frame that arrives, or a marker that is queued, waits for
        this loop's turn.  Runs on the loop; ``stop`` cancels it."""
        now = self._loop.time()
        if due is not None:
            self.metrics.observe("transport_loop_lag_s", max(0.0, now - due))
        due = now + self.LAG_PROBE_S
        self._lag_probe = self._loop.call_at(due, self._probe_lag, due)

    def stop(self) -> None:
        if self._stopped:
            return
        self._stopped = True

        async def _shutdown():
            if self._lag_probe is not None:
                self._lag_probe.cancel()
            if self._server is not None:
                self._server.close()
            # cancel every task on this loop (senders AND the per-connection
            # read handlers — leaving them pending spews "Task was
            # destroyed" / "Event loop is closed" at interpreter exit)
            me = asyncio.current_task()
            for task in asyncio.all_tasks():
                if task is not me:
                    task.cancel()
            for w in self._writers.values():
                try:
                    w.close()
                except Exception:
                    pass

        try:
            asyncio.run_coroutine_threadsafe(_shutdown(), self._loop).result(5)
        except Exception:
            pass
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=5)

    # ---- receive path --------------------------------------------------
    # reply-path write-buffer cap: a slow client must not buffer replies
    # unboundedly in its connection's writer (congestion -> drop, like the
    # forward path; clients retransmit)
    REPLY_BUFFER_LIMIT = 8 * 1024 * 1024

    def _note_reply_lag(self, lag_s: float) -> None:
        """On the loop: a reply's wait for this loop's turn.  The lags of
        one turn of the loop go to the registry together, once the
        turn's writes are done — one lock a turn, not one a frame."""
        if self.metrics is None:
            return
        if not self._reply_lags:
            self._loop.call_soon(self._flush_reply_lags)
        self._reply_lags.append(lag_s)

    def _flush_reply_lags(self) -> None:
        lags, self._reply_lags = self._reply_lags, []
        self.metrics.observe_bulk("transport_reply_lag_s", lags)

    async def _on_connection(self, reader: asyncio.StreamReader, writer):
        peer = writer.get_extra_info("peername") or ("?", 0)
        self._inbound.add(writer)

        def reply(payload: bytes) -> None:
            t_called = time.perf_counter()

            def _w():
                self._note_reply_lag(time.perf_counter() - t_called)
                try:
                    if writer.transport.get_write_buffer_size() \
                            > self.REPLY_BUFFER_LIMIT:
                        self.n_dropped += 1
                        return
                    writer.write(_HDR.pack(MAGIC, len(payload)) + payload)
                except Exception:
                    self.n_dropped += 1
            self._loop.call_soon_threadsafe(_w)

        try:
            while True:
                hdr = await reader.readexactly(_HDR.size)
                magic, length = _HDR.unpack(hdr)
                if magic != MAGIC or length > MAX_PAYLOAD:
                    break  # protocol violation: drop the connection
                payload = await reader.readexactly(length)
                self.n_rcvd += 1
                try:
                    self.handler(payload, peer, reply)
                except Exception as exc:
                    # handler errors must not kill the read loop — but the
                    # frame is gone, and with it requests nobody will
                    # answer: say so, once a kind of error
                    gplog.warn_once(_LOG, type(exc).__name__,
                                    "frame handler raised, frame dropped: "
                                    "%r", exc)
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        except asyncio.CancelledError:
            raise
        finally:
            self._inbound.discard(writer)
            try:
                writer.close()
            except Exception:
                pass  # loop may already be closing (shutdown teardown)

    def reset_connections(self) -> None:
        """Close every established connection, accepted and dialled, and
        forget what was queued for them — what the peers of a process
        that died see.  The listener stays: whoever connects anew is
        served by the handler as before.  A sender whose writer was
        closed under it finds out at its next write and dials again, and
        its latest-wins bases go with the connection."""
        def _reset():
            for w in list(self._inbound) + list(self._writers.values()):
                try:
                    w.close()
                except Exception:
                    pass  # already gone
            for q in self._queues.values():
                while not q.empty():
                    q.get_nowait()
            with self._latest_lock:
                self._latest.clear()

        self._loop.call_soon_threadsafe(_reset)

    # ---- send path -----------------------------------------------------
    def send_to_id(self, node_id: int, payload: bytes) -> bool:
        """Queue for delivery to a node id; False when congested/unknown."""
        if node_id not in self.node_config:
            return False
        return self.send_to_address(
            self.node_config.get_node_address(node_id), payload
        )

    def send_latest_to_id(self, node_id: int, slot: str, item) -> bool:
        """Queue an item that SUPERSEDES a still-unsent item of the same
        ``slot`` to that node — for frames that carry a whole state, where
        only the newest matters (the consensus blob: the engine is built
        for dropped and stale deliveries).  ``latest_encoder`` makes the
        frame when its turn to be written comes, against the base that
        connection last carried, so a peer that keeps up gets what
        changed, one that fell behind the union of what it missed, and a
        new connection the whole; a superseded item costs nothing.
        CONGESTION_LIMIT counts frames, and a whole blob is 17.8 MB at
        the deployed 65,536 rows: at most one item per (peer, slot)
        waits, whatever the peer's pace."""
        if self._latest_encoder is None:
            raise ValueError("this transport was given no latest_encoder")
        if node_id not in self.node_config:
            return False
        addr = self.node_config.get_node_address(node_id)
        addr = (addr[0], int(addr[1]))
        with self._latest_lock:
            waiting = (addr, slot) in self._latest
            self._latest[(addr, slot)] = item
        if waiting:
            # its marker is already queued; the frame it replaced never
            # leaves (the blob is the one latest-wins slot in use)
            if self.metrics is not None:
                self.metrics.count("blob_frames_superseded")
            return True
        return self.send_to_address(addr, _Latest(slot))

    def forget_latest_base(self, node_id: int) -> None:
        """That peer does not hold the base its deltas name (it said so):
        its next latest-wins frame is encoded against nothing."""
        if node_id in self.node_config:
            addr = self.node_config.get_node_address(node_id)
            self._base_forgotten.add((addr[0], int(addr[1])))

    def send_to_address(self, addr: Tuple[str, int], payload: bytes,
                        delay: float = 0.0) -> bool:
        """Queue a frame; `delay` postpones the enqueue (chunk pacing /
        emulation) on top of any configured delay_fn link delay."""
        if self._stopped:
            return False
        addr = (addr[0], int(addr[1]))
        if self.delay_fn is not None:
            delay += self.delay_fn(addr)
        if delay > 0:
            self._loop.call_soon_threadsafe(
                self._loop.call_later, delay, self._enqueue, addr, payload
            )
        else:
            self._loop.call_soon_threadsafe(self._enqueue, addr, payload)
        return True

    def _enqueue(self, addr: Tuple[str, int], payload: bytes) -> None:
        q = self._queues.get(addr)
        if q is None:
            q = asyncio.Queue()
            self._queues[addr] = q
            self._senders[addr] = self._loop.create_task(self._sender(addr, q))
        if q.qsize() >= CONGESTION_LIMIT:
            self.n_dropped += 1  # congestion: drop, like the reference
            if isinstance(payload, _Latest):
                with self._latest_lock:  # the next frame queues anew
                    self._latest.pop((addr, payload.slot), None)
            return
        q.put_nowait(payload)

    def is_congested(self, node_id: int) -> bool:
        try:
            addr = self.node_config.get_node_address(node_id)
        except KeyError:
            return True
        q = self._queues.get((addr[0], int(addr[1])))
        return q is not None and q.qsize() >= CONGESTION_LIMIT

    async def _sender(self, addr: Tuple[str, int], q: asyncio.Queue) -> None:
        """Per-peer writer with auto-reconnect (pending-writes analog)."""
        writer: Optional[asyncio.StreamWriter] = None
        # slot -> the base of the frame last written and drained on
        # `writer`: what the peer's reader holds once it has read this
        # connection that far
        bases: Dict[str, Any] = {}
        while not self._stopped:
            payload = await q.get()
            item = None
            if isinstance(payload, _Latest):
                slot = payload.slot
                with self._latest_lock:
                    item = self._latest.pop((addr, slot), None)
                if item is None:
                    continue  # forgotten meanwhile (reset_connections)
            for _attempt in (0, 1):
                if writer is None:
                    bases.clear()
                    try:
                        _r, writer = await asyncio.open_connection(
                            addr[0], addr[1], ssl=self._ssl_client
                        )
                        self._writers[addr] = writer
                    except OSError:
                        writer = None
                        await asyncio.sleep(0.05)
                        continue
                try:
                    if item is not None:
                        await self._write_latest(writer, addr, slot, item,
                                                 bases)
                    else:
                        await self._write(writer, payload)
                    self.n_sent += 1
                    break
                except (ConnectionError, OSError):
                    try:
                        writer.close()
                    except Exception:
                        pass
                    writer = None  # retry once with a fresh connection

    async def _write_latest(self, writer, addr, slot: str, item,
                            bases: Dict[str, Any]) -> None:
        """Encode a latest-wins item against this connection's base, write
        and drain it; only then is it the base of the next."""
        # until the drain returns the peer may hold either: no base
        base = bases.pop(slot, None)
        if addr in self._base_forgotten:
            self._base_forgotten.discard(addr)
            base = None
        with span(self.metrics, "blob.encode", node=self.my_id):
            frame, rows, next_base = self._latest_encoder(item, base)
        # the span crosses awaits, so it carries no CPU time: other tasks
        # of this loop run meanwhile
        with span(self.metrics, "blob.send", node=self.my_id):
            await self._write(writer, frame)
        bases[slot] = next_base
        mx = self.metrics
        if mx is None:
            return
        mx.count("blob_frames_written")  # it has left
        mx.count("blob_bytes_written", len(frame))
        mx.count("blob_bytes_sent", len(frame))
        mx.gauge("blob_frame_bytes", len(frame))
        if rows is None:
            mx.count("blob_frames_full")
        else:
            mx.count("blob_frames_delta")
            mx.observe("blob_delta_rows", rows, bounds=ROW_BOUNDS)

    @staticmethod
    async def _write(writer, payload: bytes) -> None:
        writer.write(_HDR.pack(MAGIC, len(payload)) + payload)
        await writer.drain()
