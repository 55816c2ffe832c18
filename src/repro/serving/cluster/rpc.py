"""Process-to-process control plane for the serving fleet (stdlib only).

The data plane is per-host (each process serves queries on its own devices
against its own replica), so the only cross-process traffic is control:
query routing, epoch-tagged update broadcast, health probes, and telemetry
pulls.  That traffic is small and latency-tolerant, so the transport is
deliberately simple — one TCP connection per (coordinator, host) pair,
newline-delimited JSON messages with base64-encoded ndarrays, correlation
ids for request/response matching, and a reader thread per side:

* coordinator side — :class:`RemoteHost`, a proxy implementing the
  :class:`repro.serving.cluster.host.HostServer` surface, so the router
  and :class:`~repro.serving.cluster.fleet.AidwCluster` cannot tell a
  remote host from a local one.  Blocking calls (``wait``/``flush``/
  ``wait_update``) multiplex over the one connection via correlation ids.
* host side — :func:`serve_host`, a dispatch loop around one local
  :class:`HostServer`.  Blocking ops run on their own threads so a slow
  ``await`` never stalls heartbeat probes; socket writes are serialized
  by a lock.

Epoch ordering over this transport is free: a TCP connection is FIFO and
each host has exactly one update source (the coordinator), so updates
arrive in broadcast epoch order; the host-side
:class:`~repro.serving.cluster.epochs.EpochApplier` still verifies it.

Array payloads round-trip bit-exactly (raw little-endian bytes, base64),
which the cluster's bit-identity guarantee depends on.

``main()`` is the worker-process entry point::

    python -m repro.serving.cluster.rpc --host-id 1 --n-hosts 2 \
        --points 16384 --seed 0 [--jax-coordinator 127.0.0.1:29801]

:func:`spawn_worker` launches exactly that as a subprocess (the load
generator's ``--cluster-procs`` mode and the CI cluster-suite tests).
"""

from __future__ import annotations

import base64
import itertools
import json
import socket
import subprocess
import sys
import threading
import time

import numpy as np

from ..queue import AdmissionQueueFull
from .bootstrap import ClusterConfig, bootstrap
from .epochs import EpochUpdate, UpdateHandle
from .host import HostServer

__all__ = ["RemoteHost", "RemoteRequest", "serve_host", "spawn_worker",
           "connect_with_retry", "free_port_base"]


# -- wire format -------------------------------------------------------------


def enc_array(a) -> dict | None:
    if a is None:
        return None
    a = np.ascontiguousarray(a)
    return {"dtype": str(a.dtype), "shape": list(a.shape),
            "b64": base64.b64encode(a.tobytes()).decode("ascii")}


def dec_array(d) -> np.ndarray | None:
    if d is None:
        return None
    # copy: frombuffer views are read-only, and decoded arrays flow into
    # code (delta rebinning) that expects ordinary writable ndarrays
    return np.frombuffer(base64.b64decode(d["b64"]),
                         dtype=np.dtype(d["dtype"])).reshape(d["shape"]).copy()


def _send(wfile, wlock, obj: dict) -> None:
    data = (json.dumps(obj) + "\n").encode()
    with wlock:
        wfile.write(data)
        wfile.flush()


# -- coordinator side --------------------------------------------------------


class RemoteRequest:
    """Coordinator-side stand-in for a request living on a remote host."""

    def __init__(self, uid: int, queries_xy):
        self.uid = uid
        self.queries_xy = queries_xy
        self.status = "queued"
        self.done = False
        self.values = None
        self.overflow = 0
        self.epoch: int | None = None


class RemoteHost:
    """Proxy for a :class:`HostServer` in another process.

    Implements the same surface (submit/wait/submit_update/wait_update/
    queue_depth/flush/report/reset_telemetry/close); any transport failure
    raises RuntimeError, which the router treats as host death (drain).
    """

    def __init__(self, host_id, address: tuple[str, int], *,
                 connect_timeout_s: float = 60.0):
        self.host_id = host_id
        self._sock = connect_with_retry(address, connect_timeout_s)
        self._rfile = self._sock.makefile("rb")
        self._wfile = self._sock.makefile("wb")
        self._wlock = threading.Lock()
        self._mid = itertools.count()
        self._pending: dict[int, list] = {}    # mid -> [event, reply|None]
        self._plock = threading.Lock()
        self._dead: BaseException | None = None
        self._reader = threading.Thread(target=self._read_loop,
                                        name=f"rpc-reader-{host_id}",
                                        daemon=True)
        self._reader.start()

    # transport --------------------------------------------------------------

    def _read_loop(self) -> None:
        try:
            for line in self._rfile:
                msg = json.loads(line)
                with self._plock:
                    slot = self._pending.pop(msg.get("id"), None)
                if slot is not None:
                    slot[1] = msg
                    slot[0].set()
        except Exception as e:
            self._dead = e
        finally:
            self._dead = self._dead or ConnectionError("rpc stream closed")
            with self._plock:
                for ev, _ in self._pending.values():
                    ev.set()
                self._pending.clear()

    def _call(self, op: str, timeout: float | None = None, **fields) -> dict:
        if self._dead is not None:
            raise RuntimeError(
                f"remote host {self.host_id} unreachable") from self._dead
        mid = next(self._mid)
        slot = [threading.Event(), None]
        with self._plock:
            self._pending[mid] = slot
        try:
            _send(self._wfile, self._wlock, {"op": op, "id": mid, **fields})
        except Exception as e:
            with self._plock:
                self._pending.pop(mid, None)
            raise RuntimeError(
                f"remote host {self.host_id} unreachable") from e
        if not slot[0].wait(timeout):
            with self._plock:
                self._pending.pop(mid, None)
            # TRANSPORT timeout, not a remote "not done yet" (those come
            # back as {"timeout": true} replies well inside the padded
            # bound): the host is frozen or the link is gone — raise the
            # error class the router treats as host death, so a hung host
            # gets drained instead of heartbeat-fed forever
            raise RuntimeError(f"rpc {op} to host {self.host_id} got no "
                               f"response in {timeout}s (host hung?)")
        reply = slot[1]
        if reply is None:
            raise RuntimeError(
                f"remote host {self.host_id} unreachable") from self._dead
        if reply.get("error"):
            raise _remote_error(reply)
        return reply

    # HostServer surface -----------------------------------------------------

    def submit(self, queries_xy, *, deadline_s: float | None = None,
               uid: int | None = None, timeout: float | None = None,
               trace_id: str | None = None,
               parent_span: str | None = None) -> RemoteRequest:
        """``timeout`` bounds remote admission (a full queue raises
        :class:`~repro.serving.queue.AdmissionQueueFull` from the host,
        re-raised here by type) — without it a backpressured host would
        blow the transport bound and read as dead.  ``trace_id``/
        ``parent_span`` ride the wire so the remote host's serving spans
        join the router's trace."""
        q = np.asarray(queries_xy)
        reply = self._call("submit",
                           timeout=30.0 if timeout is None else timeout + 30.0,
                           q=enc_array(q), deadline_s=deadline_s, uid=uid,
                           wait_s=timeout, trace_id=trace_id,
                           parent_span=parent_span)
        req = RemoteRequest(reply["uid"], q)
        if reply.get("status") == "shed":      # shed on arrival remotely
            req.status, req.done = "shed", True
        return req

    def wait(self, req: RemoteRequest,
             timeout: float | None = None) -> RemoteRequest:
        if req.done:
            return req
        # the remote side bounds its own wait; pad the transport timeout so
        # a response that IS coming isn't cut off mid-flight
        reply = self._call("await", timeout=None if timeout is None
                           else timeout + 30.0, uid=req.uid, wait_s=timeout)
        if reply.get("timeout"):
            raise TimeoutError(f"request {req.uid} not done on host "
                               f"{self.host_id} after {timeout}s")
        req.status = reply["status"]
        req.done = True
        req.values = dec_array(reply.get("values"))
        req.overflow = int(reply.get("overflow", 0))
        req.epoch = reply.get("epoch")
        return req

    def submit_update(self, upd: EpochUpdate) -> UpdateHandle:
        handle = UpdateHandle(upd.epoch)
        try:
            reply = self._call(
                "update", timeout=60.0, epoch=upd.epoch,
                points=enc_array(upd.points_xyz),
                inserts=enc_array(upd.inserts),
                deletes=enc_array(None if upd.deletes is None
                                  else np.asarray(upd.deletes)),
                compact=int(upd.compact), trace_id=upd.trace_id,
                parent_span=upd.parent_span)
            handle.duplicate = bool(reply.get("duplicate"))
            handle._bound.set()
        except BaseException as e:
            handle._fail(e)
        return handle

    def wait_update(self, handle: UpdateHandle,
                    timeout: float | None = None) -> None:
        if handle.error is not None:
            raise handle.error
        if handle.duplicate:
            return
        reply = self._call("update_wait", timeout=None if timeout is None
                           else timeout + 30.0, epoch=handle.epoch,
                           wait_s=timeout)
        if reply.get("timeout"):
            raise TimeoutError(f"epoch {handle.epoch} not applied on host "
                               f"{self.host_id} after {timeout}s")

    def shard_knn(self, queries_xy, *, timeout: float | None = None):
        # like wait()/wait_update(): an unbounded caller wait must not be
        # cut off by a transport cap (a cold shard's first-bucket compile
        # can far outlast any fixed bound on the CPU CI mesh)
        reply = self._call(
            "shard_knn", timeout=None if timeout is None else timeout + 30.0,
            q=enc_array(np.asarray(queries_xy)), wait_s=timeout)
        return (dec_array(reply["d2"]), dec_array(reply["z"]),
                dec_array(reply["overflow"]), reply.get("epoch"))

    def shard_partial(self, queries_xy, alpha, *,
                      timeout: float | None = None):
        reply = self._call(
            "shard_partial",
            timeout=None if timeout is None else timeout + 30.0,
            q=enc_array(np.asarray(queries_xy)),
            alpha=enc_array(np.asarray(alpha)), wait_s=timeout)
        return (dec_array(reply["swz"]), dec_array(reply["sw"]),
                reply.get("epoch"))

    def prewarm(self, wait: bool = True,
                timeout: float | None = None) -> dict:
        """Fleet control-plane prewarm: AOT-compile + warm the remote
        host's whole bucket ladder before it enters rotation.  Like
        wait()/flush(), the caller's bound rides as ``wait_s`` and the
        transport timeout gets slack on top — an unbounded prewarm (cold
        CPU CI ladder) must not be cut off by a transport cap."""
        reply = self._call(
            "prewarm", timeout=None if timeout is None else timeout + 30.0,
            wait=int(bool(wait)), wait_s=timeout)
        return reply["status"]

    @property
    def epoch(self) -> int:
        return int(self._call("epoch", timeout=30.0)["epoch"])

    def queue_depth(self) -> int:
        return int(self._call("depth", timeout=30.0)["depth"])

    def probe(self) -> int:
        """Active liveness probe (router ``check()``): raises when the host
        process is gone, hung, or its worker died; else the queue depth."""
        return int(self._call("probe", timeout=30.0)["depth"])

    def flush(self, timeout: float | None = None) -> None:
        self._call("flush", timeout=None if timeout is None
                   else timeout + 30.0, wait_s=timeout)

    def report(self) -> dict:
        return self._call("report", timeout=60.0)["report"]

    def metrics_text(self, prefix: str = "aidw") -> str:
        """Prometheus text exposition pulled from the remote host."""
        return self._call("metrics", timeout=60.0, prefix=prefix)["text"]

    def metrics_snapshot(self) -> dict:
        """Remote host's registry snapshot (JSON)."""
        return self._call("metrics", timeout=60.0)["snapshot"]

    def spans(self, drain: bool = True) -> list[dict]:
        """Pull the remote host's finished span dicts (the cross-process
        trace collection hook; ``drain=True`` empties the remote buffer)."""
        return self._call("spans", timeout=60.0, drain=int(drain))["spans"]

    def debugz(self) -> dict:
        """Pull the remote host's diagnostics bundle (queue/epoch position,
        registry state, SLO evaluation, flight-recorder traces).  The
        bundle is JSON by construction, so it rides the control plane
        as-is."""
        return self._call("debugz", timeout=60.0)["bundle"]

    def reset_telemetry(self) -> None:
        self._call("reset", timeout=30.0)

    def close(self, timeout: float | None = 30.0) -> None:
        try:
            self._call("close", timeout=timeout, wait_s=timeout)
        except (RuntimeError, TimeoutError):
            pass                               # already gone is fine
        try:
            self._sock.close()
        except OSError:
            pass


class _RemoteCallError(RuntimeError):
    """An exception raised ON the remote host, re-raised here by type name."""


def _remote_error(reply: dict):
    kind = reply.get("error_type", "")
    msg = f"[host] {reply['error']}"
    # AdmissionQueueFull must survive the wire: the router treats it as
    # backpressure (try another host), anything unrecognized as host death
    for cls in (TimeoutError, ValueError, KeyError, IndexError,
                AdmissionQueueFull):
        if kind == cls.__name__:
            return cls(msg)
    return _RemoteCallError(f"{kind}: {msg}")


def free_port_base(n_hosts: int = 1) -> int:
    """A base control port whose worker slots ``base+1 .. base+n_hosts-1``
    are all bindable RIGHT NOW (best effort: another process can still
    grab one before the worker does, but an already-taken port is caught
    here instead of as a connect timeout minutes later)."""
    for _ in range(64):
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        base = probe.getsockname()[1]
        probe.close()
        try:
            for i in range(1, n_hosts):
                s = socket.create_server(("127.0.0.1", base + i))
                s.close()
            return base
        except OSError:
            continue
    raise OSError(f"no block of {n_hosts} consecutive free ports found")


def connect_with_retry(address: tuple[str, int],
                       timeout_s: float = 60.0) -> socket.socket:
    """Dial until the host process is listening (it may still be compiling
    its session when the coordinator comes up)."""
    deadline = time.monotonic() + timeout_s
    last: Exception | None = None
    while time.monotonic() < deadline:
        try:
            return socket.create_connection(address, timeout=10.0)
        except OSError as e:
            last = e
            time.sleep(0.2)
    raise ConnectionError(
        f"could not reach fleet host at {address} after {timeout_s}s"
    ) from last


# -- host side ---------------------------------------------------------------


def serve_host(host: HostServer, address: tuple[str, int], *,
               ready_event: threading.Event | None = None) -> None:
    """Serve one coordinator connection until EOF or a ``close`` op.

    Listens on ``address``, accepts exactly one connection (the
    coordinator), and dispatches messages; every op that can block —
    waits, flushes, close, and the enqueueing ops (``submit``/``update``
    block under admission-queue backpressure) — runs on its own thread so
    the loop keeps answering ``depth`` probes while work is in flight.
    """
    lsock = socket.create_server(address)
    if ready_event is not None:
        ready_event.set()
    conn, _ = lsock.accept()
    lsock.close()
    rfile = conn.makefile("rb")
    wfile = conn.makefile("wb")
    wlock = threading.Lock()
    stop = threading.Event()
    # uid -> request object (awaits need the object; flush() reaps it from
    # the server registry, so the rpc layer keeps its own map)
    requests: dict[int, object] = {}
    updates: dict[int, UpdateHandle] = {}
    rlock = threading.Lock()

    def reply(mid: int, **fields) -> None:
        try:
            _send(wfile, wlock, {"id": mid, **fields})
        except OSError:
            stop.set()

    def fail(mid: int, e: BaseException) -> None:
        reply(mid, error=str(e), error_type=type(e).__name__)

    def handle(msg: dict) -> None:
        mid, op = msg["id"], msg["op"]
        try:
            if op == "submit":
                req = host.submit(dec_array(msg["q"]),
                                  deadline_s=msg.get("deadline_s"),
                                  uid=msg.get("uid"),
                                  timeout=msg.get("wait_s"),
                                  trace_id=msg.get("trace_id"),
                                  parent_span=msg.get("parent_span"))
                if not req.done:
                    # shed-on-arrival requests are terminal in this reply
                    # and never awaited — registering them would leak one
                    # query array per shed request for the worker lifetime
                    with rlock:
                        requests[req.uid] = req
                reply(mid, uid=req.uid, status=req.status)
            elif op == "await":
                with rlock:
                    req = requests.get(msg["uid"])
                if req is None:
                    raise KeyError(f"unknown uid {msg['uid']}")
                try:
                    host.wait(req, timeout=msg.get("wait_s"))
                except TimeoutError:
                    reply(mid, timeout=True)
                    return
                with rlock:
                    requests.pop(msg["uid"], None)
                reply(mid, status=req.status, values=enc_array(req.values),
                      overflow=req.overflow,
                      epoch=getattr(req, "epoch", None))
            elif op == "update":
                upd = EpochUpdate(epoch=int(msg["epoch"]),
                                  points_xyz=dec_array(msg.get("points")),
                                  inserts=dec_array(msg.get("inserts")),
                                  deletes=dec_array(msg.get("deletes")),
                                  compact=bool(msg.get("compact", 0)),
                                  trace_id=msg.get("trace_id"),
                                  parent_span=msg.get("parent_span"))
                h = host.submit_update(upd)
                if not h.duplicate:
                    # duplicates are never waited on (and must not clobber
                    # a pending original handle for the same epoch)
                    with rlock:
                        updates[upd.epoch] = h
                reply(mid, ok=1, duplicate=h.duplicate)
            elif op == "update_wait":
                with rlock:
                    h = updates.get(int(msg["epoch"]))
                if h is None:
                    raise KeyError(f"epoch {msg['epoch']} never offered")
                try:
                    host.wait_update(h, timeout=msg.get("wait_s"))
                except TimeoutError:
                    # the timed-out wait WITHDREW the op (epoch gap; the
                    # coordinator drains this host) — the handle is spent,
                    # keeping it would leak one entry per timed-out epoch
                    with rlock:
                        updates.pop(int(msg["epoch"]), None)
                    reply(mid, timeout=True)
                    return
                with rlock:
                    updates.pop(int(msg["epoch"]), None)
                reply(mid, ok=1)
            elif op == "shard_knn":
                d2, z, ovf, epoch = host.shard_knn(dec_array(msg["q"]),
                                                   timeout=msg.get("wait_s"))
                reply(mid, d2=enc_array(d2), z=enc_array(z),
                      overflow=enc_array(ovf), epoch=epoch)
            elif op == "shard_partial":
                swz, sw, epoch = host.shard_partial(
                    dec_array(msg["q"]), dec_array(msg["alpha"]),
                    timeout=msg.get("wait_s"))
                reply(mid, swz=enc_array(swz), sw=enc_array(sw), epoch=epoch)
            elif op == "prewarm":
                reply(mid, status=host.prewarm(
                    wait=bool(msg.get("wait", 1)),
                    timeout=msg.get("wait_s")))
            elif op == "depth":
                reply(mid, depth=host.queue_depth())
            elif op == "probe":
                reply(mid, depth=host.probe())
            elif op == "epoch":
                reply(mid, epoch=host.epoch)
            elif op == "flush":
                host.flush(timeout=msg.get("wait_s"))
                reply(mid, ok=1)
            elif op == "report":
                reply(mid, report=host.report())
            elif op == "metrics":
                reply(mid, text=host.metrics_text(msg.get("prefix", "aidw")),
                      snapshot=host.metrics_snapshot())
            elif op == "spans":
                reply(mid, spans=host.spans(drain=bool(msg.get("drain", 1))))
            elif op == "debugz":
                # diagnostics: inline like report/metrics/spans — never
                # behind the blocking set, so a wedged worker still answers
                reply(mid, bundle=host.debugz())
            elif op == "reset":
                host.reset_telemetry()
                reply(mid, ok=1)
            elif op == "close":
                host.close(timeout=msg.get("wait_s"))
                reply(mid, ok=1)
                stop.set()
                # unblock the dispatch loop's readline — the coordinator
                # may keep its socket half open after the close ack
                try:
                    conn.shutdown(socket.SHUT_RD)
                except OSError:
                    pass
            else:
                raise ValueError(f"unknown rpc op {op!r}")
        except BaseException as e:           # noqa: BLE001 — surface to peer
            fail(mid, e)

    # submit/update can block on a FULL admission queue (backpressure), so
    # they leave the dispatch loop too — a backpressured-but-healthy host
    # must keep answering depth probes or the router drains it.  Enqueue
    # ORDER is still caller-pinned: every enqueueing op replies only after
    # the item is in the FIFO, and callers block on that reply before
    # issuing their next op.
    _BLOCKING = {"await", "flush", "update_wait", "close", "submit",
                 "update", "shard_knn", "shard_partial", "prewarm"}
    try:
        while not stop.is_set():
            line = rfile.readline()
            if not line:
                break
            msg = json.loads(line)
            if msg["op"] in _BLOCKING:
                threading.Thread(target=handle, args=(msg,),
                                 daemon=True).start()
            else:
                handle(msg)
    finally:
        try:
            conn.close()
        except OSError:
            pass


# -- worker-process entry point ----------------------------------------------


def spawn_worker(host_id: int, n_hosts: int, *, points: int, seed: int = 0,
                 control_port: int = 29900, max_batch: int = 4096,
                 query_domain_n: int = 1024,
                 jax_coordinator: str | None = None,
                 shard_of: int = 0,
                 trace_sample_rate: float | None = None,
                 compilation_cache_dir: str | None = None,
                 env: dict | None = None) -> subprocess.Popen:
    """Launch one fleet host as a subprocess running :func:`main`.

    ``shard_of=N`` makes the worker serve shard ``host_id`` of an N-way
    :func:`~repro.serving.cluster.fleet.fleet_partition` of the
    reconstructed dataset instead of a full replica (the
    :class:`~repro.serving.cluster.fleet.ShardedAidwCluster` deployment
    shape)."""
    from ...runtime import refuse_child_if_tpu_held

    refuse_child_if_tpu_held(f"fleet host {host_id}")
    # -c instead of -m: runpy re-executing a module the package __init__
    # already imported would warn (and double-define the rpc classes)
    cmd = [sys.executable, "-c",
           "import sys; from repro.serving.cluster.rpc import main; "
           "main(sys.argv[1:])",
           "--host-id", str(host_id), "--n-hosts", str(n_hosts),
           "--points", str(points), "--seed", str(seed),
           "--control-port", str(control_port),
           "--max-batch", str(max_batch),
           "--query-domain", str(query_domain_n)]
    if shard_of:
        cmd += ["--shard-of", str(shard_of)]
    if jax_coordinator:
        cmd += ["--jax-coordinator", jax_coordinator]
    if trace_sample_rate is not None:
        cmd += ["--trace-sample-rate", str(trace_sample_rate)]
    if compilation_cache_dir:
        cmd += ["--compilation-cache-dir", compilation_cache_dir]
    return subprocess.Popen(cmd, env=env)


def main(argv=None) -> None:
    import argparse

    from repro.data.pipeline import spatial_points, spatial_queries

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--host-id", type=int, required=True)
    p.add_argument("--n-hosts", type=int, required=True)
    p.add_argument("--points", type=int, default=16384)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--control-host", default="127.0.0.1")
    p.add_argument("--control-port", type=int, default=29900)
    p.add_argument("--max-batch", type=int, default=4096)
    p.add_argument("--query-domain", type=int, default=1024,
                   help="query_domain sample count (0 = none); seed fixed "
                        "at 1 so every fleet host plans the same grid")
    p.add_argument("--jax-coordinator", default=None,
                   help="host:port for jax.distributed.initialize "
                        "(omit for a transport-only fleet)")
    p.add_argument("--shard-of", type=int, default=0, metavar="N",
                   help="serve shard <host-id> of an N-way fleet_partition "
                        "of the dataset instead of a full replica")
    p.add_argument("--trace-sample-rate", type=float, default=None,
                   help="obs trace sampling probability for this host "
                        "(omit = tracing off; spans pull over the 'spans' "
                        "rpc op)")
    p.add_argument("--compilation-cache-dir", default=None,
                   help="persistent XLA compilation cache directory "
                        "(directory rules: compile_cache.enable; hosts "
                        "given the same directory share one cache)")
    args = p.parse_args(argv)

    ctx = bootstrap(ClusterConfig(
        n_hosts=args.n_hosts, host_id=args.host_id,
        jax_coordinator=args.jax_coordinator,
        control_host=args.control_host, control_port=args.control_port,
        cache_dir=args.compilation_cache_dir))
    # the dataset replica is reconstructed, not shipped: spatial_points is
    # deterministic in (n, seed), so every host plans the identical grid
    pts = spatial_points(args.points, seed=args.seed)
    qd = spatial_queries(args.query_domain, seed=1) \
        if args.query_domain else None
    if args.shard_of:
        # deterministic partition: the coordinator computes the identical
        # split from the same (n, seed, query_domain) inputs
        from .fleet import fleet_partition

        _, _, members = fleet_partition(pts, args.shard_of,
                                        query_domain=qd)
        pts = pts[members[ctx.host_id]]
    host = HostServer(ctx.host_id, pts, max_batch=args.max_batch,
                      query_domain=qd, mesh=ctx.mesh,
                      trace_sample_rate=args.trace_sample_rate)
    serve_host(host, ctx.cfg.control_address(ctx.host_id))
    # joins the fleet-wide shutdown barrier — the coordinator side calls
    # ctx.shutdown() after closing its proxies, and a worker that skipped
    # it would be declared dead and crash every other fleet process
    ctx.shutdown()


if __name__ == "__main__":
    main()
