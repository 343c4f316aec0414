"""The serve traffic's edge clients, in a process of their own.

    python3 chipbench/loadgen.py

``kinds/serve.py`` starts it in set-up and drives it by pickled messages
on its standard input and output.  It holds no chip and imports neither
JAX nor the program, so that the clients neither share the server's
interpreter lock nor wait for it: one thread and one socket to the
worker, as the fleet's front door connects, speaking the fleet's framing
(``repro.serving.realfleet``): a frame is a ``!I`` length (of the type
byte and the body), a ``!B`` message type and the body; a request's body
is a ``!I`` request id and the packed payload, an answer's a ``!I``
request id, a ``!H`` served batch size and the packed action, an error's
a ``!I`` request id and the text.

Messages in: the set-up (``addr``, the payload ``bodies``, the message
types ``msg`` and ``warm``, the number of requests that warm the
connection), answered ``"ready"``; one per window (``seconds``,
``rate_hz``, ``offsets``, ``payloads``), answered by its first slot
``t0`` and its ``records``; ``None`` to close.

Each client sends its next decision at its slot of the grid, or as soon
as the answer to its previous one arrives where that comes later; a
record is ``(client, payload, due, lag, latency, answer)``, times on the
host's monotonic clock, which every process shares.  ``answer`` is the
answer frame's body after the request id (``bytes``), the error text
(``str``), or ``None`` where no answer came within ``GRACE_S`` of the
last send.
"""
from __future__ import annotations

import collections
import heapq
import itertools
import pickle
import select
import socket
import struct
import sys
import time

GRACE_S = 60.0
START_S = 0.25          # from the window's message to its first slot


class Clients:
    def __init__(self, setup: dict):
        self.sock = socket.create_connection(tuple(setup["addr"]))
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.bodies = setup["bodies"]
        self.msg = setup["msg"]
        self.ids = itertools.count()
        self.buf = bytearray()
        for i in range(setup["warm"]):
            self._send(next(self.ids), self.bodies[i % len(self.bodies)])
            while not self._frames():
                self._read(None)

    def _send(self, req_id: int, body: bytes) -> None:
        frame = struct.pack("!I", req_id) + body
        self.sock.sendall(struct.pack("!IB", len(frame) + 1,
                                      self.msg["req"]) + frame)

    def _read(self, timeout) -> bool:
        """Wait up to ``timeout`` for bytes from the worker; whether any
        came."""
        if not select.select([self.sock], [], [], timeout)[0]:
            return False
        data = self.sock.recv(1 << 20)
        if not data:
            raise ConnectionError("the worker closed the connection")
        self.buf += data
        return True

    def _frames(self) -> list:
        """``(request id, answer)`` of every whole frame received."""
        out = []
        while len(self.buf) >= 4:
            (n,) = struct.unpack_from("!I", self.buf)
            if len(self.buf) < 4 + n:
                break
            mtype, body = self.buf[4], bytes(self.buf[5:4 + n])
            del self.buf[:4 + n]
            (req_id,) = struct.unpack_from("!I", body)
            if mtype == self.msg["resp"]:
                out.append((req_id, body[4:]))
            elif mtype == self.msg["err"]:
                out.append((req_id, body[4:].decode(errors="replace")))
            else:
                raise ConnectionError(f"unexpected message type {mtype}")
        return out

    def window(self, job: dict) -> dict:
        period = 1.0 / job["rate_hz"]
        seconds, payloads = job["seconds"], job["payloads"]
        t0 = time.monotonic() + START_S
        slots = []
        for c, off in enumerate(job["offsets"]):
            k = 0
            while off + k * period < seconds:
                slots.append((t0 + off + k * period, c, k))
                k += 1
        heapq.heapify(slots)
        free = [t0] * len(payloads)          # when each client may send
        waiting = collections.defaultdict(collections.deque)
        busy: set = set()
        flight: dict = {}
        records: list = []
        last_send = t0

        def send(due: float, c: int, k: int) -> None:
            nonlocal last_send
            j = payloads[c][k % len(payloads[c])]
            req_id = next(self.ids)
            last_send = time.monotonic()
            self._send(req_id, self.bodies[j])
            flight[req_id] = (c, j, due, last_send - max(due, free[c]))
            busy.add(c)

        while slots or flight:
            now = time.monotonic()
            while slots and slots[0][0] <= now:
                due, c, k = heapq.heappop(slots)
                if c in busy:
                    waiting[c].append((due, c, k))
                else:
                    send(due, c, k)
            if slots:
                timeout = max(0.0, slots[0][0] - time.monotonic())
            else:
                timeout = last_send + GRACE_S - time.monotonic()
                if timeout <= 0:
                    break
            if not self._read(timeout):
                continue
            done = time.monotonic()
            for req_id, answer in self._frames():
                if req_id not in flight:      # of a window given up on
                    continue
                c, j, due, lag = flight.pop(req_id)
                records.append((c, j, due, lag, done - due, answer))
                busy.discard(c)
                free[c] = done
                if waiting[c]:
                    send(*waiting[c].popleft())
        never = list(flight.values()) + [
            (c, payloads[c][k % len(payloads[c])], due, 0.0)
            for queued in waiting.values() for due, c, k in queued]
        for c, j, due, lag in never:
            records.append((c, j, due, lag, time.monotonic() - due, None))
        return {"t0": t0, "records": records}

    def close(self) -> None:
        self.sock.close()


def main() -> None:
    inp, out = sys.stdin.buffer, sys.stdout.buffer
    clients = Clients(pickle.load(inp))
    pickle.dump("ready", out)
    out.flush()
    try:
        # ends on None, or on the end of the input where the harness
        # stopped before it could say so
        while (job := pickle.load(inp)) is not None:
            pickle.dump(clients.window(job), out)
            out.flush()
    except EOFError:
        pass
    finally:
        clients.close()


if __name__ == "__main__":
    main()
