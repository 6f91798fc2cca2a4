"""Load clients: an open loop of SSE streams through the HTTP proxy and a
closed loop of unary calls through a deployment handle. Both run on ONE
thread of the driver process, and every time is ``time.monotonic()`` of
this host.
"""

from __future__ import annotations

import dataclasses
import json
import selectors
import socket
import time
from typing import List, Optional


@dataclasses.dataclass
class Stream:
    i: int
    due: float                      # when the schedule wanted it sent
    max_tokens: int
    sent: Optional[float] = None    # when it was sent
    status: Optional[int] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None
    buf: bytes = b""

    @property
    def ok(self) -> bool:
        return (self.done and self.error is None and self.status == 200
                and len(self.tokens) == self.max_tokens)


def _open_stream(host, port, path, st: Stream, body: dict):
    payload = json.dumps(body).encode()
    head = (f"POST {path} HTTP/1.1\r\nhost: {host}\r\n"
            f"accept: text/event-stream\r\n"
            f"content-type: application/json\r\n"
            f"content-length: {len(payload)}\r\n\r\n").encode()
    sock = socket.create_connection((host, port), timeout=30)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.sendall(head + payload)
    sock.setblocking(False)
    return sock


def _feed(st: Stream, chunk: bytes, now: float) -> None:
    """Parse what arrived. Every ``data:`` line of the event stream is
    one token (the chunked framing's size lines are skipped); its time is
    when its bytes reached this client."""
    st.buf += chunk
    while b"\n" in st.buf:
        line, _, st.buf = st.buf.partition(b"\n")
        line = line.strip()
        if st.status is None:
            if line.startswith(b"HTTP/1."):
                st.status = int(line.split()[1])
                if st.status != 200:
                    st.error = f"status {st.status}"
            continue
        if line == b"data: [DONE]":
            st.done = True
        elif line.startswith(b"event: error"):
            st.error = "error event"
        elif line.startswith(b"data: "):
            if st.error is not None:
                st.error += ": " + line[6:80].decode(errors="replace")
                continue
            try:
                st.tokens.append(int(json.loads(line[6:])))
            except (ValueError, TypeError):
                st.error = f"not a token: {line[:60]!r}"
            st.token_times.append(now)


def open_loop_sse(requests, *, host, port, path, temperature, start_at,
                  stop_sending_at, drain_s, on_tick=None) -> List[Stream]:
    """Send each request when it falls due (an open loop: whether or not
    earlier ones have finished), from ``start_at`` until
    ``stop_sending_at``; then wait up to ``drain_s`` for the streams that
    are open. ``on_tick(now)`` is called about every 50 ms."""
    sel = selectors.DefaultSelector()
    streams: List[Stream] = []
    open_n = 0
    req = next(requests)
    due = start_at + req["gap_s"]
    deadline = stop_sending_at + drain_s
    while True:
        now = time.monotonic()
        while req is not None and due < stop_sending_at and due <= now:
            st = Stream(i=req["i"], due=due, max_tokens=req["max_tokens"])
            streams.append(st)
            try:
                st.sent = time.monotonic()
                sock = _open_stream(host, port, path, st, {
                    "prompt": req["prompt"], "max_tokens": req["max_tokens"],
                    "temperature": temperature, "bench_sent": st.sent})
                sel.register(sock, selectors.EVENT_READ, st)
                open_n += 1
            except OSError as e:
                st.error, st.done = f"connect: {e!r}", True
            req = next(requests)
            due += req["gap_s"]
            now = time.monotonic()
        if req is not None and due >= stop_sending_at:
            req = None
        if req is None and now >= stop_sending_at and (
                open_n == 0 or now > deadline):
            break
        wait = 0.05 if req is None else min(0.05, max(0.0, due - now))
        for key, _ in sel.select(wait):
            st, sock = key.data, key.fileobj
            now = time.monotonic()
            try:
                chunk = sock.recv(65536)
            except BlockingIOError:
                continue
            except OSError as e:
                chunk, st.error = b"", f"recv: {e!r}"
            if chunk:
                _feed(st, chunk, now)
            if not chunk or st.done or st.error:
                if not st.done and st.error is None:
                    st.error = "closed before [DONE]"
                st.done = True
                sel.unregister(sock)
                sock.close()
                open_n -= 1
        if on_tick is not None:
            on_tick(time.monotonic())
    for key in list(sel.get_map().values()):     # never answered in time
        key.data.error = key.data.error or "unanswered at the drain limit"
        sel.unregister(key.fileobj)
        key.fileobj.close()
    sel.close()
    return streams


@dataclasses.dataclass
class Call:
    i: int
    max_tokens: int
    sent: float
    finished: Optional[float] = None
    tokens: Optional[list] = None
    error: Optional[str] = None


def closed_loop_handle(requests, handle, *, clients, temperature,
                       stop_sending_at, drain_s, on_tick=None) -> List[Call]:
    """``clients`` callers, each sending its next request when the last
    was answered, until ``stop_sending_at``; answers then in flight are
    awaited for up to ``drain_s``."""
    import ray_tpu

    inflight = {}
    calls: List[Call] = []

    def send():
        req = next(requests)
        call = Call(i=req["i"], max_tokens=req["max_tokens"],
                    sent=time.monotonic())
        ref = handle.remote(req["prompt"], max_tokens=req["max_tokens"],
                            temperature=temperature)
        inflight[ref] = call
        calls.append(call)

    for _ in range(clients):
        send()
    deadline = stop_sending_at + drain_s
    while inflight and time.monotonic() < deadline:
        ready, _ = ray_tpu.wait(list(inflight), num_returns=1, timeout=0.05)
        for ref in ready:
            call = inflight.pop(ref)
            try:
                call.tokens = list(ray_tpu.get(ref, timeout=10.0))
            except Exception as e:  # noqa: BLE001 — counted as failed
                call.error = repr(e)[:200]
            call.finished = time.monotonic()
            if call.finished < stop_sending_at:
                send()
        if on_tick is not None:
            on_tick(time.monotonic())
    for call in inflight.values():
        call.error = "unanswered at the drain limit"
    return calls


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list (q in 0..100)."""
    vals = sorted(values)
    k = max(0, min(len(vals) - 1, int(-(-q * len(vals) // 100)) - 1))
    return vals[k]
