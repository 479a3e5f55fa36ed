"""The open-loop generator counts a server stall in every request that fell
due behind it, and follows the volume server's 307."""

import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

import openloop


class _Server:
    """One-at-a-time server; the `stall_at`-th request sleeps `stall` s.
    Paths under /r/ are redirected with a 307 to /o/."""

    def __init__(self, stall_at: int, stall: float):
        lock = threading.Lock()
        seen = [0]

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            disable_nagle_algorithm = True  # headers and body, no 40 ms

            def log_message(self, *a):
                pass

            def do_GET(self):
                if self.path.startswith("/r/"):
                    self.send_response(307)
                    self.send_header("Location", "http://%s:%d/o/%s" % (
                        self.server.server_address[0],
                        self.server.server_address[1], self.path[3:]))
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return
                with lock:
                    seen[0] += 1
                    if seen[0] == stall_at:
                        threading.Event().wait(stall)
                body = self.path.encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.address = "127.0.0.1:%d" % self.httpd.server_address[1]
        self.t = threading.Thread(target=self.httpd.serve_forever,
                                  daemon=True)
        self.t.start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_schedule_fixes_count_and_varies_order():
    a_due, a_keys = openloop.schedule(2**31 + 5, 200.0, 3.0, 1000)
    b_due, b_keys = openloop.schedule(2**31 + 6, 200.0, 3.0, 1000)
    assert len(a_due) == len(b_due) == 600
    assert np.all(np.diff(a_due) >= 0) and a_due[-1] < 3.0
    assert not np.array_equal(a_keys, b_keys)
    again = openloop.schedule(2**31 + 5, 200.0, 3.0, 1000)
    assert np.array_equal(again[0], a_due)


def test_a_stall_inflates_the_requests_due_behind_it():
    srv = _Server(stall_at=10, stall=0.4)
    try:
        due = np.arange(40) * 0.02            # one every 20 ms
        keys = np.arange(40)
        fids = ["r/%d" % i for i in range(40)]
        lat, late, status, bodies = openloop.run_schedule(
            [openloop._Client(srv.address)], fids, due, keys,
            keep_bodies=True)
    finally:
        srv.close()
    assert (status == 200).all()
    assert bodies[3] == b"/o/3"
    # the stalled request and those due during its 0.4 s all wait for it:
    # timed from their due time, not from when the one client got to them
    assert lat[9] >= 0.35
    assert lat[10] >= 0.3 and lat[15] >= 0.2
    assert late[15] >= 0.2
    # once the backlog drains, requests are on time again
    assert lat[35:].max() < 0.1
