"""Closed loop: each client sends its next request only once the last
one has its terminal record in hand, so a slow server is offered less.

`run` blocks until every client has stopped: at the window's end no new
request is sent, and requests in flight are polled until `drain_until`.
"""

from __future__ import annotations

import threading
import time

TERMINAL = ("done", "failed", "cancelled")


class Control:
    """What the parent tells the loop while it runs."""

    def __init__(self):
        self.t_end = None          # monotonic; None while warming up
        self.drain_until = None
        self.abort = threading.Event()


def run(n_clients, make_client, send, take_request, control, on_record,
        poll_s=5.0):
    def client_loop(k):
        cl = make_client()
        try:
            while not control.abort.is_set():
                if control.t_end is not None \
                        and time.monotonic() >= control.t_end:
                    return
                nxt = take_request()
                if nxt is None:
                    on_record({"i": -1, "status": "pool_dry", "client": k,
                               "t_submit": time.monotonic()})
                    return
                i, histories = nxt
                rec = {"i": i, "client": k, "n": len(histories),
                       "status": "error", "t_submit": time.monotonic()}
                try:
                    ans = send(cl, histories)
                    rec["t_ack"] = time.monotonic()
                    while ans.get("status") not in TERMINAL:
                        left = poll_s
                        if control.drain_until is not None:
                            left = min(left, control.drain_until
                                       - time.monotonic())
                        if left <= 0 or control.abort.is_set():
                            break
                        ans = cl.result(ans["id"], wait_s=round(left, 3))
                    rec["t_done"] = time.monotonic()
                    if ans.get("status") == "done" and "results" not in ans:
                        # decided before `submit` returned: the
                        # acknowledgement carries no results
                        ans = cl.result(ans["id"])
                        rec["t_done"] = time.monotonic()
                    rec["status"] = (ans["status"]
                                     if ans.get("status") in TERMINAL
                                     else "not_terminal")
                    rec["answer"] = ans
                except Exception as e:  # refused, or the connection died
                    rec["t_done"] = time.monotonic()
                    rec["error"] = f"{type(e).__name__}: {e}"[:200]
                    status = getattr(e, "status", None)
                    rec["status"] = "refused" if status in (429, 503) \
                        else "error"
                on_record(rec)
        finally:
            cl.close()

    threads = [threading.Thread(target=client_loop, args=(k,), daemon=True,
                                name=f"client-{k}")
               for k in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
