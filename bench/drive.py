"""The two loops that drive the system under test through a measured window.

Both take what the window drives from the program (``AsyncAidwServer`` and
``InterpolationSession``) and time it with the benchmark's own clock
(``time.monotonic``): a read is timed from the moment it was due, not from
when the program stamped it, and a call from before it is made until its
values are on the host.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from bench import generate

WAIT_PAST_CLOSE_S = 60.0   # an answer later than this never came


@dataclass
class Window:
    """What one measured window did, on the benchmark's clock."""

    t_open: float = 0.0
    t_close: float = 0.0              # last answer in (or given up)
    reads: list = field(default_factory=list)     # dicts, in due order
    updates: list = field(default_factory=list)   # dicts, in due order
    calls: list = field(default_factory=list)     # closed loop: dicts
    lateness_s: list = field(default_factory=list)


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def served(srv, ops: list[generate.Op], *, first_epoch: int) -> Window:
    """Open loop: submit each op when it is due, whatever the server does.

    A collector thread waits for the answers in submission order (the
    server's FIFO completes them in that order) and stamps each on arrival.
    ``first_epoch`` is the server's dataset epoch when the window opens; a
    read's expected epoch counts the updates submitted before it."""
    inbox: queue.Queue = queue.Queue()
    win = Window()
    give_up = [float("inf")]

    def arrived(rec, handle) -> bool:
        """Wait in short slices until the answer is in or the give-up time
        (set when the window closes) has passed."""
        while time.monotonic() < give_up[0]:
            if rec["kind"] == "read":
                try:
                    srv.result(handle, timeout=0.5)
                    return handle.status == "done"
                except TimeoutError:
                    continue
            if handle.applied.wait(timeout=0.5):
                return handle.error is None and not handle.skipped
        return False

    def collect():
        while (item := inbox.get()) is not None:
            rec, handle = item
            try:
                rec["ok"] = arrived(rec, handle)
            except Exception as e:      # the server failed it
                rec["ok"], rec["error"] = False, repr(e)
            rec["t_done"] = time.monotonic() if rec["ok"] else give_up[0]

    collector = threading.Thread(target=collect, name="bench-collector",
                                 daemon=True)
    collector.start()
    epoch = first_epoch
    try:
        with _annotate("bench.window"):
            win.t_open = time.monotonic()
            for op in ops:
                due = win.t_open + op.t
                delay = due - time.monotonic()
                if delay > 0:
                    with _annotate("bench.sleep"):
                        time.sleep(delay)
                rec = {"kind": op.kind, "due": due, "t_submit": time.monotonic()}
                win.lateness_s.append(rec["t_submit"] - due)
                if op.kind == "read":
                    with _annotate("bench.submit"):
                        handle = srv.submit(op.queries)
                    rec.update(handle=handle, queries=op.queries, epoch=epoch)
                    win.reads.append(rec)
                else:
                    with _annotate("bench.update"):
                        handle = srv.submit_update(inserts=op.inserts,
                                                   deletes=op.deletes)
                    epoch += 1
                    rec.update(inserts=op.inserts, deletes=op.deletes)
                    win.updates.append(rec)
                inbox.put((rec, handle))
            give_up[0] = time.monotonic() + WAIT_PAST_CLOSE_S
            inbox.put(None)
            with _annotate("bench.drain"):
                collector.join(WAIT_PAST_CLOSE_S + 10.0)
            win.t_close = time.monotonic()
    finally:
        inbox.put(None)
        collector.join(WAIT_PAST_CLOSE_S + 10.0)
    return win


def closed(sess, call_queries: int, seconds: float, queries) -> Window:
    """Closed loop: one client sends the next call of ``call_queries``
    queries, ``queries(call_queries)`` (the mix's pattern), when the last
    one's values are on the host; calls start until ``seconds`` have
    passed, and each runs to its end."""
    win = Window()
    with _annotate("bench.window"):
        win.t_open = time.monotonic()
        while time.monotonic() - win.t_open < seconds:
            q = queries(call_queries)
            with _annotate("bench.call"):
                t1 = time.monotonic()
                res = sess.query(q)
                values = np.asarray(res.values)
                t2 = time.monotonic()
            win.calls.append({"queries": q, "values": values, "t1": t1,
                              "t2": t2, "alpha": res.alpha,
                              "r_obs": res.r_obs})
        win.t_close = time.monotonic()
    for c in win.calls:           # read back after the window: not timed
        c["alpha"], c["r_obs"] = np.asarray(c["alpha"]), np.asarray(c["r_obs"])
    return win


@contextlib.contextmanager
def profiled(trace_dir):
    """The JAX profiler around a block, or nothing when ``trace_dir`` is
    None.  Python-function tracing is off: the host side records the
    runtime's own events and the benchmark's annotations only."""
    if trace_dir is None:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
