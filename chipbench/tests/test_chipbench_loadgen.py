"""The serve traffic's clients (``chipbench/loadgen.py``) against a real
``WorkerServer`` whose server half takes a fixed time: every slot of the
grid is sent once, a client whose answer comes after its next slot sends
at once when it comes, the answers are the server's, and a worker that
answers with an error is recorded as such."""
import pickle
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import loadgen
from repro.serving import realfleet

MSG = {"req": realfleet.MSG_REQ, "resp": realfleet.MSG_RESP,
       "err": realfleet.MSG_ERR}


def _worker(service_s, fail=False):
    def serve(stacked):
        time.sleep(service_s)
        if fail:
            raise ValueError("planted")
        return stacked["x"].reshape(len(stacked["x"]), -1) * 2.0

    ws = realfleet.WorkerServer(serve, max_batch=8)
    return ws, ws.start()


def _clients(addr, n_bodies=4):
    bodies = [realfleet.pack_payload({"x": np.full((1, 3), i, np.float32)})
              for i in range(n_bodies)]
    return loadgen.Clients({"addr": addr, "bodies": bodies, "msg": MSG,
                            "warm": 2})


@pytest.mark.parametrize("service_s", [0.005, 0.15])
def test_every_slot_sent_once_and_answered(service_s):
    ws, addr = _worker(service_s)
    clients = _clients(addr)
    try:
        job = {"seconds": 0.5, "rate_hz": 10.0, "offsets": [0.0, 0.05],
               "payloads": [[0, 1], [2, 3]]}
        got = clients.window(job)
    finally:
        clients.close()
        ws.stop()
    t0, records = got["t0"], got["records"]
    dues = sorted((c, round(due - t0, 6)) for c, _, due, *_ in records)
    assert dues == [(c, round(off + 0.1 * k, 6)) for c, off in
                    enumerate(job["offsets"]) for k in range(5)
                    if off + 0.1 * k < 0.5]
    by_client = {}
    for c, j, due, lag, latency, answer in sorted(records,
                                                  key=lambda r: r[2]):
        assert isinstance(answer, bytes) and latency >= service_s
        action = realfleet.unpack_payload(answer[2:])["action"]
        np.testing.assert_array_equal(action, np.full(3, 2.0 * j))
        assert j == job["payloads"][c][len(by_client.get(c, [])) % 2]
        by_client.setdefault(c, []).append((due, latency))
        assert lag >= 0
    # a deferred decision's lag runs from its client's answer, not its slot
    assert np.median([r[3] for r in records]) < 0.04
    if service_s > 0.1:
        # the answer comes after the next slot: the next decision waits
        # for it, and its latency counts from its own slot
        for seq in by_client.values():
            for (d0, l0), (d1, l1) in zip(seq, seq[1:]):
                assert d0 + l0 <= d1 + l1 - service_s + 1e-3


def test_error_answers_are_recorded():
    ws, addr = _worker(0.0, fail=True)
    clients = _clients(addr)
    try:
        got = clients.window({"seconds": 0.2, "rate_hz": 10.0,
                              "offsets": [0.0], "payloads": [[0]]})
    finally:
        clients.close()
        ws.stop()
    answers = [r[5] for r in got["records"]]
    assert len(answers) == 2
    assert all(isinstance(a, str) and "planted" in a for a in answers)


def test_process_messages_and_exit():
    ws, addr = _worker(0.0)
    proc = subprocess.Popen([sys.executable, loadgen.__file__],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE)

    def tell(message):
        pickle.dump(message, proc.stdin)
        proc.stdin.flush()

    try:
        body = realfleet.pack_payload({"x": np.zeros((1, 3), np.float32)})
        tell({"addr": addr, "bodies": [body], "msg": MSG, "warm": 1})
        assert pickle.load(proc.stdout) == "ready"
        tell({"seconds": 0.1, "rate_hz": 10.0, "offsets": [0.0],
              "payloads": [[0]]})
        assert len(pickle.load(proc.stdout)["records"]) == 1
        tell(None)
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        ws.stop()
