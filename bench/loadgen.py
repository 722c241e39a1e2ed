"""Open-loop HTTP load generator, run as a child process of the benchmark.

It imports neither JAX nor the program, so it holds no chip and does not
share the server's interpreter lock.  It reads one JSON plan from its
standard input::

    {"port": 8099, "t0": <time.monotonic() of the window start>,
     "timeout": 120, "requests": [[due_offset_s, path, keep_body], ...]}

and sends each request from its own thread at ``t0 + due_offset_s``
whether or not earlier ones have answered.  ``time.monotonic`` is the
system-wide monotonic clock, the same in both processes.  When every
request has answered or timed out it writes one JSON list to standard
output: per request its due, sent and delivery times, status, the
snapshot it claims, its scan report's cached and folded group counts, and
the body where ``keep_body`` asks for it.
"""
import http.client
import json
import sys
import threading
import time


def one(port, path, due, timeout, keep, out, i):
    rec = {"path": path, "due": due, "keep": keep, "sent": None,
           "done": None, "status": None, "error": None}
    now = time.monotonic()
    if due > now:
        time.sleep(due - now)
    rec["sent"] = time.monotonic()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            raw = resp.read()
            rec["status"] = resp.status
        finally:
            conn.close()
        rec["done"] = time.monotonic()
        body = json.loads(raw)
        snap = body.get("snapshot") or {}
        rec["rows"] = snap.get("rows")
        rec["groups"] = sum(f.get("groups", 0) for f in snap.get("files", ()))
        report = body.get("report") or {}
        rec["groups_cached"] = report.get("groups_cached", 0)
        rec["groups_folded"] = report.get("groups_folded", 0)
        rec["nbytes"] = len(raw)
        if keep:
            rec["body"] = body
    except Exception as e:          # a request that never answers
        rec["error"] = f"{type(e).__name__}: {e}"
    out[i] = rec


def main():
    plan = json.loads(sys.stdin.readline())
    t0, timeout = float(plan["t0"]), float(plan.get("timeout", 120))
    out = [None] * len(plan["requests"])
    threads = [threading.Thread(target=one, daemon=True, args=(
        plan["port"], path, t0 + float(due), timeout, bool(keep), out, i))
        for i, (due, path, keep) in enumerate(plan["requests"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout + max(0.0, t0 - time.monotonic()) + 600)
    json.dump(out, sys.stdout)
    sys.stdout.write("\n")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
