"""Worker subprocesses for the serving data path: RemoteExecutor.

``GraftExecutor`` already routes every pool hop through a transport
channel; this module puts the *other end* of those channels in worker
subprocesses, so the serving data path genuinely crosses process (and
socket) boundaries, like the paper's testbed where fragments run behind
a network hop from the clients.

Topology: one worker process per stage pool. The parent listens on an
ephemeral port per worker and the worker **dials back** to the parent's
``advertise_host`` — configurable, so workers on other machines reach a
routable address instead of the historical hard-coded ``127.0.0.1``.
How the worker process starts is a pluggable :class:`WorkerLauncher`:

  * :class:`SubprocessLauncher` — ``python -c`` on this machine (the
    default, byte-identical to the old behavior);
  * :class:`SSHLauncher` — ``ssh <host> env PYTHONPATH=... python -m
    repro.serving.remote --connect <advertise:port>``: the same
    handshake from a genuinely different machine. The ``ssh`` argv
    prefix is injectable, which is also how tests run the launcher
    without an ssh daemon.

Workers run JAX on the parent's platform. An accelerator belongs to one
process, and a parent that has touched JAX holds it, so RemoteExecutor
runs from a CPU parent only; on a chip host the in-process
``GraftExecutor`` is the serving path.

The accepted connection is a persistent framed request/reply channel
(the same ``PoolService`` message vocabulary local pools speak). The
worker builds its jitted fragment program from an ``init`` message
carrying the model config + numpy parameters, then serves
submit/flush/execute/retarget/bind/stats until ``shutdown``.

Two cluster-grade behaviors live in the parent-side plumbing:

  * **Reconnect with backoff.** A dropped dial-back connection (worker
    crash, OOM-kill, network partition) no longer kills the pool: the
    lane that observed the failure triggers :meth:`WorkerProc.recover`,
    which respawns the worker (kill -> exponential backoff -> relaunch
    -> re-``init`` with the stored params/spec/chips) up to
    ``max_respawns`` times. The failed request itself raises
    :class:`WorkerDiedError` — queued state died with the worker, so
    callers (``GraftServer._run_batch``) reroute or finish in-process —
    but the NEXT batch flows through the recovered worker.
  * **Per-front-end channels.** ``open_handle`` used to return the one
    shared dial-back connection, so fleet front-ends' (possibly
    realtime-shaped) uplink submits serialized on a single TCP stream.
    Now the parent keeps the per-worker listener open and an
    ``open_channel`` op makes the worker dial back an *additional*
    connection, served by its own worker thread against the same
    ``PoolService`` (whose lock serializes actual pool execution) —
    front-ends overlap their transfers, the pool stays one resource.

Because workers are keyed by pool identity ``(model, start, end)``,
:meth:`RemoteExecutor.apply_plan` (inherited) keeps surviving workers —
their pid, their compiled XLA program, their queue — alive across a
replan; only genuinely new block ranges pay a process spawn + jax import
+ trace/compile. That is the warm-instance story the plan-differ tells.
"""
from __future__ import annotations

import os
import pickle
import socket
import subprocess
import sys
import threading
import time
import traceback
from typing import Callable, Optional, Union

import numpy as np

from repro.core.plandiff import PoolSpec
from repro.serving.executor import (FragmentInstance, GraftExecutor,
                                    PoolHandle, PoolService, pool_endpoint)
from repro.serving.telemetry import Telemetry
from repro.serving.transport import (
    Channel, DEFAULT_MAX_FRAME, ShapedTransport, SocketChannel,
    SocketTransport, Transport, TruncatedFrameError, _ShapedChannel,
    error_reply, read_frame, write_frame)

WORKER_SPAWN_TIMEOUT_S = 120.0          # jax import on a cold worker is slow
PING_TIMEOUT_S = 5.0                    # liveness probe bound in recover()
RESPAWN_HEAL_WINDOW_S = 300.0           # healthy this long => budget renews

# the source root workers need on PYTHONPATH to import repro.*
SRC_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class WorkerDiedError(RuntimeError):
    """The worker's dial-back connection failed mid-request. The worker
    has been recovered (respawned or the lane re-opened) where possible,
    but THIS request was not delivered — any state queued in the dead
    process is gone, so the caller must reroute or finish in-process."""


def bind_host_for(advertise_host: str) -> str:
    """Where the parent's per-worker listener binds: loopback
    advertisements stay on loopback; any routable advertisement binds
    all interfaces ('') so workers on other machines can reach it."""
    return advertise_host if advertise_host in ("127.0.0.1", "localhost") \
        else ""


# ---------------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------------

class _WorkerState:
    """State shared by every parent connection into one worker process."""

    def __init__(self, connect_addr, max_frame_bytes):
        self.connect_addr = connect_addr      # (host, port) to dial back to
        self.max_frame_bytes = max_frame_bytes
        self.service: Optional[PoolService] = None


def _hello(conn, max_frame_bytes, **fields) -> None:
    write_frame(conn, {"ok": True, "hello": True, "pid": os.getpid(),
                       **fields}, max_frame_bytes=max_frame_bytes)


def _serve_extra(conn, state: _WorkerState) -> None:
    """Serve one extra (per-front-end) lane until it closes. Requests
    hit the same shared PoolService as the main lane — its lock is what
    serializes pool execution server-side while the lanes' socket I/O
    (and the parent-side shaped sleeps) overlap."""
    try:
        while True:
            try:
                msg = read_frame(conn,
                                 max_frame_bytes=state.max_frame_bytes)
            except (TruncatedFrameError, OSError):
                return                       # lane closed: thread exits
            if state.service is None:
                reply = {"ok": False, "error": "worker not initialised"}
            else:
                reply = state.service.handle(msg)
            try:
                write_frame(conn, reply,
                            max_frame_bytes=state.max_frame_bytes)
            except OSError:
                return
    finally:
        conn.close()


def _worker_loop(conn: socket.socket, connect_addr=None,
                 max_frame_bytes: int = DEFAULT_MAX_FRAME) -> int:
    """Serve one pool over ``conn`` (plus dialed-back extra lanes) until
    shutdown."""
    state = _WorkerState(connect_addr, max_frame_bytes)
    _hello(conn, max_frame_bytes)
    while True:
        try:
            msg = read_frame(conn, max_frame_bytes=max_frame_bytes)
        except (TruncatedFrameError, OSError):
            return 0                        # parent went away: exit quietly
        except Exception:                   # anything else must be LOUD
            traceback.print_exc(file=sys.stderr)
            return 1
        op = msg.get("op")
        if op == "shutdown":
            write_frame(conn, {"ok": True, "pid": os.getpid()},
                        max_frame_bytes=max_frame_bytes)
            return 0
        if op == "ping":
            reply = {"ok": True, "pid": os.getpid()}
        elif op == "open_channel":
            # dial an ADDITIONAL lane back to the parent; its serve
            # thread shares this worker's PoolService. Dial before the
            # ok-reply so the parent's accept() can never outwait a
            # connection that was refused.
            try:
                if state.connect_addr is None:
                    raise RuntimeError(
                        "worker has no dial-back address for extra lanes")
                c2 = socket.create_connection(state.connect_addr,
                                              timeout=30.0)
                c2.settimeout(None)     # connect bound; reads idle forever
                c2.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                _hello(c2, max_frame_bytes, extra=True)
                threading.Thread(target=_serve_extra, args=(c2, state),
                                 daemon=True).start()
                reply = {"ok": True, "pid": os.getpid()}
            except Exception as e:
                reply = error_reply(e)
        elif op == "init":
            try:
                cfg = pickle.loads(msg["cfg"])
                spec = PoolSpec(key=tuple(msg["key"]), share=msg["share"],
                                batch=msg["batch"],
                                n_instances=msg["n_instances"],
                                role=msg.get("role", "both"))
                # a worker owns a PRIVATE registry: its state rides back
                # on the stats op (spans drained — the parent takes
                # ownership) and merges parent-side, keyed by pool
                wtel = Telemetry(process=f"worker-{os.getpid()}") \
                    if msg.get("telemetry") else None
                inst = FragmentInstance(msg["params"], cfg, spec,
                                        packed=bool(msg.get("packed", True)),
                                        chips=msg.get("chips"),
                                        telemetry=wtel)
                if wtel is not None:
                    inst.owns_telemetry = True
                state.service = PoolService(inst)
                reply = {"ok": True, "pid": os.getpid()}
            except Exception as e:
                reply = error_reply(e)
        elif state.service is None:
            reply = {"ok": False, "error": "worker not initialised"}
        else:
            reply = state.service.handle(msg)
        write_frame(conn, reply, max_frame_bytes=max_frame_bytes)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="repro.serving.remote")
    ap.add_argument("--connect", required=True, metavar="HOST:PORT",
                    help="parent's per-worker listener to dial back to "
                         "(the parent's --advertise-host)")
    ap.add_argument("--max-frame", type=int, default=DEFAULT_MAX_FRAME,
                    help="frame size cap; must match the parent transport")
    args = ap.parse_args(argv)
    host, port = args.connect.rsplit(":", 1)
    addr = (host, int(port))
    conn = socket.create_connection(addr, timeout=30.0)
    # the 30 s bound applies to the CONNECT only: a persistent socket
    # timeout would make read_frame raise on any >30 s idle stretch and
    # the worker would exit under a perfectly healthy, quiet pool
    conn.settimeout(None)
    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return _worker_loop(conn, connect_addr=addr,
                        max_frame_bytes=args.max_frame)


# ---------------------------------------------------------------------------
# parent side
# ---------------------------------------------------------------------------

def _np_tree(params):
    """Jax param pytree -> nested numpy (msgpack-framable)."""
    import jax
    return jax.tree.map(lambda a: np.asarray(a), params)


class WorkerLauncher:
    """How a pool worker process starts. ``argv(connect, max_frame)``
    builds the command line; the handshake on the other side is always
    the same: dial back to ``connect``, send hello, speak PoolService."""

    def argv(self, connect: str, max_frame_bytes: int) -> list:
        raise NotImplementedError

    def popen_kwargs(self) -> dict:
        return {}

    def launch(self, connect: str,
               max_frame_bytes: int) -> subprocess.Popen:
        return subprocess.Popen(self.argv(connect, max_frame_bytes),
                                **self.popen_kwargs())


class SubprocessLauncher(WorkerLauncher):
    """Worker on THIS machine (the default): same interpreter, source
    tree injected on PYTHONPATH, the parent's environment (and so its
    JAX platform) inherited."""

    def argv(self, connect: str, max_frame_bytes: int) -> list:
        # -c instead of -m: runpy would re-execute this module on top of
        # the copy the package __init__ already imported in the worker
        return [sys.executable, "-c",
                "import sys; from repro.serving.remote import main; "
                "sys.exit(main(sys.argv[1:]))",
                "--connect", connect,
                "--max-frame", str(max_frame_bytes)]

    def popen_kwargs(self) -> dict:
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC_ROOT + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        return {"env": env}


class SSHLauncher(WorkerLauncher):
    """Worker on ANOTHER host: ``ssh <host> env PYTHONPATH=<remote src>
    [JAX_PLATFORMS=<the parent's>] <python> -m repro.serving.remote
    --connect <advertise_host:port>``. The worker inherits the parent's
    ``JAX_PLATFORMS`` when the parent has one set.

    The handshake is identical to the local launcher — the parent only
    ever sees a dial-back connection, so the executor cannot tell (and
    must not care) which machine a pool runs on. ``ssh`` is an argv
    prefix, injectable so tests can substitute a local shim (and so real
    deployments can add ``-o`` options or use a wrapper).
    """

    def __init__(self, host: str, *, python: str = "python3",
                 pythonpath: Optional[str] = SRC_ROOT,
                 ssh: tuple = ("ssh",)):
        self.host = host
        self.python = python
        self.pythonpath = pythonpath
        self.ssh = tuple(ssh)

    def argv(self, connect: str, max_frame_bytes: int) -> list:
        envs = []
        if self.pythonpath:
            envs.append(f"PYTHONPATH={self.pythonpath}")
        if os.environ.get("JAX_PLATFORMS"):
            envs.append(f"JAX_PLATFORMS={os.environ['JAX_PLATFORMS']}")
        remote = (["env", *envs] if envs else []) + [
            self.python, "-m", "repro.serving.remote",
            "--connect", connect, "--max-frame", str(max_frame_bytes)]
        return [*self.ssh, self.host, *remote]


class WorkerChannel(Channel):
    """One lane to a worker that survives worker death.

    The lane lazily (re-)binds to the worker's current generation: after
    a respawn, the next request transparently rides the new process. A
    connection error mid-request triggers :meth:`WorkerProc.recover`
    (respawn with backoff / lane re-open) and then raises
    :class:`WorkerDiedError` — the request was NOT delivered and any
    state queued in the dead worker is gone, which the caller must
    handle; hiding that with a silent retry would strand every
    previously-queued request."""

    def __init__(self, worker: "WorkerProc", *, main: bool):
        super().__init__(f"worker/{worker.key}" + ("" if main else "#lane"))
        self._worker = worker
        self.main = main
        self._inner: Optional[SocketChannel] = None
        self.gen = -1

    def _invalidate(self) -> None:
        self._inner = None

    def _ensure(self) -> SocketChannel:
        w = self._worker
        with w._lock:
            if w._closed:
                raise WorkerDiedError(f"pool {w.key} worker is shut down")
            if self._inner is None or self.gen != w.gen:
                inner = w._main_raw if self.main else w._connect_lane_locked()
                inner.stats = self.stats      # ONE log across respawns
                self._inner = inner
                self.gen = w.gen
            return self._inner

    def request(self, msg: dict) -> dict:
        try:
            inner = self._ensure()
            reply = inner.request(msg)
        except WorkerDiedError:
            raise
        except (TruncatedFrameError, ConnectionError, OSError) as e:
            self._worker.recover(self)
            raise WorkerDiedError(
                f"pool {self._worker.key}: worker connection lost "
                f"({type(e).__name__}: {e}); worker recovered but this "
                f"request was not delivered") from e
        if reply.get("ok"):
            # only APPLIED retargets/binds update the respawn state — a
            # worker-side failure must not make a later respawn re-init
            # with a spec the live pool never adopted
            self._worker.note_op(msg)
        return reply

    def close(self) -> None:
        self._worker._forget(self)
        inner, self._inner = self._inner, None
        if inner is not None and not self.main:
            inner.close()


class WorkerProc:
    """One spawned pool worker: listener, process, and its lanes.

    The parent's listener stays open for the worker's whole life — it is
    the rendezvous for the initial dial-back, every extra per-front-end
    lane, and every respawned process. ``advertise_host`` is the address
    workers are told to dial (bind is derived: loopback advertisements
    bind loopback, anything else binds all interfaces so remote workers
    can actually reach us).
    """

    def __init__(self, key: tuple, max_frame_bytes: int = DEFAULT_MAX_FRAME,
                 *, advertise_host: str = "127.0.0.1",
                 bind_host: Optional[str] = None,
                 launcher: Optional[WorkerLauncher] = None,
                 max_respawns: int = 3, respawn_backoff_s: float = 0.05,
                 on_respawn: Optional[Callable] = None,
                 sleep: Callable[[float], None] = time.sleep):
        self.key = key
        self._max = max_frame_bytes
        self.advertise_host = advertise_host
        if bind_host is None:
            bind_host = bind_host_for(advertise_host)
        self.launcher = launcher if launcher is not None \
            else SubprocessLauncher()
        self.max_respawns = max_respawns
        self.respawn_backoff_s = respawn_backoff_s
        self.on_respawn = on_respawn
        self._sleep = sleep
        self._lock = threading.RLock()
        self.gen = 0
        self.respawns = 0
        self._last_respawn_t = time.monotonic()
        self._closed = False
        self._init_args: Optional[dict] = None
        self._extras: list = []
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind((bind_host, 0))
        self._lsock.listen(16)
        self._lsock.settimeout(WORKER_SPAWN_TIMEOUT_S)
        self._port = self._lsock.getsockname()[1]
        try:
            self._spawn_locked()
        except Exception:
            self._lsock.close()
            raise
        self.channel = WorkerChannel(self, main=True)

    @property
    def connect_str(self) -> str:
        """What workers are told to dial: the ADVERTISED address."""
        return f"{self.advertise_host}:{self._port}"

    # ----------------------------------------------------- spawn / accept
    def _accept_locked(self, *, extra: bool) -> socket.socket:
        """Accept the NEXT matching dial-back, draining mismatches.

        The listener backlog can hold stale connections from a dead
        generation (a worker that dialed an extra lane and died before
        its ok-reply); accepting one of those as the fresh worker's
        main connection would kill a healthy respawn. So: accept,
        validate the hello (direction flag, and pid for extra lanes),
        and DISCARD anything stale until the matching peer shows up or
        the spawn window closes."""
        deadline = time.monotonic() + WORKER_SPAWN_TIMEOUT_S
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                conn = None
            else:
                self._lsock.settimeout(remaining)
                try:
                    conn, _ = self._lsock.accept()
                except (socket.timeout, OSError):
                    conn = None
            if conn is None:
                self.proc.kill()
                rc = self.proc.wait(timeout=10)
                raise RuntimeError(
                    f"worker for pool {self.key} never dialed back to "
                    f"{self.connect_str} within "
                    f"{WORKER_SPAWN_TIMEOUT_S:.0f}s (exit status {rc}); "
                    f"see the worker's stderr above for the crash") \
                    from None
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            conn.settimeout(10.0)        # hello must arrive promptly —
            try:                         # a silent half-open conn must
                hello = read_frame(conn, max_frame_bytes=self._max)
            except Exception:            # not wedge the accept loop
                conn.close()
                continue
            if (not hello.get("hello")
                    or bool(hello.get("extra")) != extra
                    or (extra and hello.get("pid") != self.pid)):
                conn.close()             # stale generation's lane: drain
                continue
            conn.settimeout(None)        # validated: reads idle forever
            if not extra:
                self.pid = int(hello["pid"])
            return conn

    def _spawn_locked(self) -> None:
        self.proc = self.launcher.launch(self.connect_str, self._max)
        try:
            conn = self._accept_locked(extra=False)
        except Exception:
            try:                             # never leak the subprocess
                self.proc.kill()
                self.proc.wait(timeout=10)
            except Exception:
                pass
            raise
        self._main_raw = SocketChannel(f"worker/{self.key}", None,
                                       self._max, sock=conn)

    def _connect_lane_locked(self) -> SocketChannel:
        reply = self._main_raw.request({"op": "open_channel"})
        if not reply.get("ok"):
            # a refusal (worker up, dial-back blocked) honors the SAME
            # typed contract as a death — callers are documented against
            # WorkerDiedError, not a raw RuntimeError
            raise WorkerDiedError(
                f"open_channel on {self.key} refused: "
                f"{reply.get('error')}")
        conn = self._accept_locked(extra=True)
        return SocketChannel(f"worker/{self.key}#lane", None, self._max,
                             sock=conn)

    # ------------------------------------------------------------- lanes
    def open_channel(self) -> WorkerChannel:
        """A NEW dial-back lane to this worker (connected lazily on first
        use, re-connected after respawns). Fleet front-ends each take one
        so their uplink transfers overlap on separate TCP streams."""
        ch = WorkerChannel(self, main=False)
        with self._lock:
            self._extras.append(ch)
        return ch

    def _forget(self, ch: WorkerChannel) -> None:
        with self._lock:
            try:
                self._extras.remove(ch)
            except ValueError:
                pass

    # ------------------------------------------------------------- init
    def init(self, cfg_bytes: bytes, params_np, spec: PoolSpec,
             chips=None, packed: bool = True,
             telemetry: bool = False) -> None:
        with self._lock:
            self._init_args = {"cfg": cfg_bytes, "params": params_np,
                               "spec": spec, "packed": bool(packed),
                               "chips": [int(c) for c in (chips or [])],
                               "telemetry": bool(telemetry)}
            self._init_locked()

    def _init_locked(self) -> None:
        a = self._init_args
        spec = a["spec"]
        reply = self._main_raw.request({
            "op": "init", "cfg": a["cfg"], "params": a["params"],
            "key": list(spec.key), "share": spec.share, "batch": spec.batch,
            "n_instances": spec.n_instances, "role": spec.role,
            "chips": a["chips"],
            "packed": a.get("packed", True),
            "telemetry": a.get("telemetry", False)})
        if not reply.get("ok"):
            raise RuntimeError(f"worker init for {spec.key} failed: "
                               f"{reply.get('error')}")

    def note_op(self, msg: dict) -> None:
        """Track retarget/bind so a respawn re-creates the CURRENT pool
        shape and placement, not the birth-time one."""
        op = msg.get("op")
        if self._init_args is None or op not in ("retarget", "bind"):
            return
        with self._lock:
            if op == "retarget":
                self._init_args["spec"] = PoolSpec(
                    key=tuple(msg["key"]), share=msg["share"],
                    batch=msg["batch"], n_instances=msg["n_instances"],
                    role=msg.get("role", "both"))
            else:
                self._init_args["chips"] = [int(c) for c in msg["chips"]]

    # ---------------------------------------------------------- recovery
    def recover(self, ch: WorkerChannel) -> None:
        """Reconnect-with-backoff after ``ch`` hit a connection error.

        Liveness is verified HERE, not inferred from the failing lane's
        generation: the current process must exist AND answer a ping on
        the main connection, else it is respawned. That check is what
        serializes concurrent lane failures into ONE respawn (the first
        lane in respawns; later ones find the fresh worker answering)
        and what still respawns when the observer is a never-bound lane
        (gen -1) whose connect attempt found the main connection dead —
        a generation comparison alone would discard that observation and
        leave the pool dead. A lane-only drop on a live worker just
        invalidates the lane so its next use re-dials."""
        with self._lock:
            if self._closed:
                ch._invalidate()
                return
            alive = self.proc.poll() is None and self._reachable_locked()
            if not alive:
                self._respawn_locked()
            ch._invalidate()

    def _reachable_locked(self, timeout_s: float = PING_TIMEOUT_S) -> bool:
        """Bounded liveness probe on the main connection. Bounded twice:
        the channel lock acquire (a request wedged against a hung worker
        must read as unreachable, not block recovery forever) and the
        socket read (a worker that accepted the ping but never answers
        is equally dead for our purposes)."""
        ch = self._main_raw
        if not ch._lock.acquire(timeout=timeout_s):
            return False                 # main lane wedged mid-request
        try:
            sock = ch._sock
            old = sock.gettimeout()
            try:
                sock.settimeout(timeout_s)
                write_frame(sock, {"op": "ping"},
                            max_frame_bytes=self._max)
                return bool(read_frame(
                    sock, max_frame_bytes=self._max).get("ok"))
            finally:
                try:
                    sock.settimeout(old)
                except OSError:
                    pass
        except Exception:
            return False
        finally:
            ch._lock.release()

    def _respawn_locked(self) -> None:
        now = time.monotonic()
        if now - self._last_respawn_t > RESPAWN_HEAL_WINDOW_S:
            # the budget bounds CRASH LOOPS, not lifetime faults: a pool
            # that ran healthy for the heal window earns its slots back,
            # so a long-lived deployment survives occasional deaths
            self.respawns = 0
        if self.respawns >= self.max_respawns:
            raise WorkerDiedError(
                f"worker for pool {self.key} died and exceeded "
                f"max_respawns={self.max_respawns} within "
                f"{RESPAWN_HEAL_WINDOW_S:.0f}s")
        self.respawns += 1
        self._last_respawn_t = now
        try:
            self.proc.kill()
            self.proc.wait(timeout=10)
        except Exception:
            pass
        try:
            self._main_raw.close()
        except Exception:
            pass
        delay = min(self.respawn_backoff_s * (2 ** (self.respawns - 1)),
                    1.0)
        if delay > 0:
            self._sleep(delay)
        self.gen += 1
        self._spawn_locked()
        if self._init_args is not None:
            self._init_locked()
        if self.on_respawn is not None:
            try:
                self.on_respawn(self.key, self.gen)
            except Exception:
                pass

    # ---------------------------------------------------------- teardown
    def shutdown(self, timeout: float = 10.0) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            try:
                self._main_raw.request({"op": "shutdown"})
            except Exception:
                pass
            for ch in self._extras:
                inner, ch._inner = ch._inner, None
                if inner is not None:
                    inner.close()
            self._extras.clear()
            self._main_raw.close()
            try:
                self._lsock.close()
            except OSError:
                pass
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(timeout=timeout)


class RemoteExecutor(GraftExecutor):
    """GraftExecutor whose stage pools live in worker subprocesses.

    Only pool creation/retirement differ from the in-process executor —
    serve()/apply_plan()/stats logic is inherited verbatim, so the same
    code path is proven against real process boundaries.

    ``transport`` may be a :class:`SocketTransport` (default) or a
    :class:`ShapedTransport` wrapping one — shaped links apply the
    per-client bandwidth/latency model to every submit hop.

    Multi-host knobs:

    * ``advertise_host`` — the address workers dial back to. Loopback by
      default; set the parent's routable hostname/IP when launchers put
      workers on other machines.
    * ``launcher`` — a :class:`WorkerLauncher`, or a callable
      ``pool_key -> WorkerLauncher`` for heterogeneous placements (some
      pools local, some over ssh).
    * ``max_respawns`` / ``respawn_backoff_s`` — reconnect-with-backoff
      budget per worker; ``respawn_log`` records ``(key, gen)`` per
      recovery.
    * ``beacon_interval_s`` — health beacons: a per-worker poller thread
      issues a periodic ``stats`` request on a dedicated lane (liveness
      ping + telemetry-snapshot piggyback) and a watchdog publishes
      ``beacon/<pool>/age_s`` / ``wedged`` gauges; a beacon stale for
      ``beacon_stale_s`` (default 3x the interval) triggers the same
      ping-verified recovery path a failed request does — catching the
      wedged-but-connected worker no request ever trips over.
    """

    def __init__(self, plan, params, cfg,
                 transport: Optional[Transport] = None, *,
                 advertise_host: str = "127.0.0.1",
                 launcher: Union[WorkerLauncher, Callable, None] = None,
                 max_respawns: int = 3, respawn_backoff_s: float = 0.05,
                 packed: bool = True, telemetry=None,
                 beacon_interval_s: float = 0.0,
                 beacon_stale_s: Optional[float] = None):
        import jax
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                f"RemoteExecutor needs a CPU parent, but this process runs "
                f"JAX on {jax.default_backend()!r}: an accelerator belongs "
                f"to one process, and the parent already holds it, so "
                f"worker pools could not reach it. Serve on the chip with "
                f"the in-process GraftExecutor.")
        self._workers: dict[tuple, WorkerProc] = {}
        self._cfg_bytes = pickle.dumps(cfg)
        self._params_np = _np_tree(params)
        self.spawn_log: list = []               # (key, spawn_wall_s)
        self.respawn_log: list = []             # (key, gen) per recovery
        self.advertise_host = advertise_host
        self._launcher = launcher
        self._max_respawns = max_respawns
        self._respawn_backoff_s = respawn_backoff_s
        # health beacons: per-worker poller threads ride a dedicated
        # dial-back lane; a watchdog turns beacon staleness into a
        # wedged flag + recovery (see _beacon_watchdog)
        self.beacon_interval_s = float(beacon_interval_s)
        self.beacon_stale_s = float(beacon_stale_s) \
            if beacon_stale_s is not None else 3.0 * self.beacon_interval_s
        self.beacon_log: list = []              # (key, kind) staleness events
        self._beacon_seen: dict = {}            # key -> monotonic last-ok
        self._beacon_pollers: dict = {}         # key -> Thread
        self._beacon_recovering: set = set()
        self._beacon_lock = threading.Lock()
        self._beacon_stop = threading.Event()
        tp = transport if transport is not None else SocketTransport()
        base = tp.inner if isinstance(tp, ShapedTransport) else tp
        if not isinstance(base, SocketTransport):
            raise TypeError(
                "RemoteExecutor needs a SocketTransport (optionally "
                f"wrapped in ShapedTransport), got {type(base).__name__}")
        self._shaper = tp if isinstance(tp, ShapedTransport) else None
        self._max_frame = base.max_frame_bytes
        super().__init__(plan, params, cfg, transport=tp, packed=packed,
                         telemetry=telemetry)
        if self.beacon_interval_s > 0:
            t = threading.Thread(target=self._beacon_watchdog,
                                 daemon=True, name="worker-beacons")
            t.start()
            self._beacon_watchdog_thread = t

    def _launcher_for(self, key: tuple) -> Optional[WorkerLauncher]:
        if self._launcher is None or isinstance(self._launcher,
                                                WorkerLauncher):
            return self._launcher
        return self._launcher(key)              # callable: per-pool hosts

    def _spawn_pool(self, spec: PoolSpec) -> PoolHandle:
        t0 = time.perf_counter()
        w = WorkerProc(spec.key, self._max_frame,
                       advertise_host=self.advertise_host,
                       launcher=self._launcher_for(spec.key),
                       max_respawns=self._max_respawns,
                       respawn_backoff_s=self._respawn_backoff_s,
                       on_respawn=self._note_respawn)
        try:
            # a pool added by a migration-aware replan knows its chips at
            # birth (placement is transitioned before _deploy spawns);
            # the initial deploy binds right after packing instead
            w.init(self._cfg_bytes, self._params_np, spec,
                   chips=self.chips_of(spec.key), packed=self.packed,
                   telemetry=self.telemetry.enabled)
        except Exception:
            w.shutdown()                 # the spawned proc must not leak
            raise
        self._workers[spec.key] = w
        self.spawn_log.append((spec.key, time.perf_counter() - t0))
        channel = w.channel
        if self._shaper is not None:
            channel = _ShapedChannel(channel, self._shaper)
        h = PoolHandle(spec.key, channel)
        h.pid = w.pid
        return h

    def _note_respawn(self, key: tuple, gen: int) -> None:
        self.respawn_log.append((key, gen))

    def _spawn_pools(self, specs: list) -> dict:
        """Spawn added workers CONCURRENTLY: each pays its own process
        start + jax import + trace/compile, so a replan that adds k pools
        stalls for the slowest spawn instead of the sum — what keeps a
        live ``GraftServer.apply`` pause bounded while traffic is in
        flight. Each thread touches only its own WorkerProc/listener;
        the shared dicts are appended under the GIL. All-or-nothing like
        the base class: if any spawn fails, workers that did come up are
        shut down instead of leaking as orphan subprocesses."""
        if len(specs) <= 1:
            return super()._spawn_pools(specs)
        from concurrent.futures import ThreadPoolExecutor, as_completed
        handles, first_err = {}, None
        with ThreadPoolExecutor(max_workers=min(len(specs), 8)) as pool:
            futs = [pool.submit(self._spawn_pool, s) for s in specs]
            for f in as_completed(futs):
                try:
                    h = f.result()
                    handles[h.key] = h
                except Exception as e:
                    first_err = first_err or e
        if first_err is not None:
            for h in handles.values():
                try:
                    self._retire_pool(h)
                except Exception:
                    pass
            raise first_err
        return handles

    def open_handle(self, key: tuple) -> PoolHandle:
        """A dedicated dial-back lane to pool ``key``'s worker, so fleet
        front-ends' shaped uplink transfers overlap on separate TCP
        streams (the worker serializes actual execution on its pool
        lock)."""
        w = self._workers[key]
        channel: Channel = w.open_channel()
        if self._shaper is not None:
            channel = _ShapedChannel(channel, self._shaper)
        h = PoolHandle(key, channel)
        h.pid = w.pid
        return h

    def worker(self, key: tuple) -> WorkerProc:
        """The live WorkerProc for pool ``key`` (fault tests kill it)."""
        return self._workers[key]

    # ------------------------------------------------------ health beacons
    def _beacon_poll(self, key: tuple) -> None:
        """One worker's beacon: periodic stats request on a DEDICATED
        dial-back lane (never contends with the deploy channel), whose
        reply piggybacks the worker's telemetry snapshot. Each success
        stamps ``_beacon_seen``; the watchdog turns a stale stamp into
        wedged/recovery. The lane transparently rebinds after respawns,
        so a recovered worker resumes beaconing on its own."""
        lane = None
        while not self._beacon_stop.is_set():
            w = self._workers.get(key)
            if w is None or w._closed:
                break                           # pool retired by a replan
            try:
                if lane is None:
                    lane = w.open_channel()
                reply = lane.request({"op": "stats"})
                if reply.get("ok"):
                    self._beacon_seen[key] = time.monotonic()
                    snap = reply.get("telemetry")
                    if snap and self.telemetry.enabled:
                        label = pool_endpoint(key)[len("pool/"):]
                        self.telemetry.merge_snapshot(
                            snap, source=label, prefix=f"pool/{label}/")
            except WorkerDiedError:
                pass        # recover() already ran; next loop rebinds
            except Exception:
                pass
            self._beacon_stop.wait(self.beacon_interval_s)
        if lane is not None:
            try:
                lane.close()
            except Exception:
                pass

    def _beacon_recover(self, key: tuple) -> None:
        w = self._workers.get(key)
        if w is not None:
            try:
                # ping-verified: a merely-slow worker answers and only
                # the lane is invalidated; a dead/wedged one respawns
                w.recover(w.channel)
            except Exception:
                traceback.print_exc()
        with self._beacon_lock:
            self._beacon_recovering.discard(key)

    def _beacon_watchdog(self) -> None:
        """Separate from the pollers on purpose: a poller blocked inside
        a wedged worker's stats request cannot also be the thing that
        notices the wedge. Each tick re-syncs pollers with the live
        worker set (replans add/retire pools), publishes beacon-age /
        wedged gauges, and kicks recovery when a beacon goes stale."""
        tel = self.telemetry
        while not self._beacon_stop.wait(self.beacon_interval_s):
            now = time.monotonic()
            for key in list(self._workers):
                t = self._beacon_pollers.get(key)
                if t is None or not t.is_alive():
                    self._beacon_seen.setdefault(key, now)
                    t = threading.Thread(target=self._beacon_poll,
                                         args=(key,), daemon=True,
                                         name=f"beacon-{key}")
                    t.start()
                    self._beacon_pollers[key] = t
                label = pool_endpoint(key)
                age = now - self._beacon_seen.get(key, now)
                wedged = age > self.beacon_stale_s
                tel.gauge(f"beacon/{label}/age_s").set(age)
                tel.gauge(f"beacon/{label}/wedged").set(1.0 if wedged
                                                        else 0.0)
                if wedged:
                    with self._beacon_lock:
                        kick = key not in self._beacon_recovering
                        if kick:
                            self._beacon_recovering.add(key)
                    if kick:
                        self.beacon_log.append((key, "stale"))
                        tel.counter("beacon/stale_events").inc()
                        threading.Thread(target=self._beacon_recover,
                                         args=(key,), daemon=True).start()
            for key in list(self._beacon_pollers):
                if key not in self._workers:
                    self._beacon_pollers.pop(key, None)
                    self._beacon_seen.pop(key, None)

    def _retire_pool(self, handle: PoolHandle) -> None:
        w = self._workers.pop(handle.key, None)
        if w is not None:
            w.shutdown()
        else:
            handle.close()

    def close(self) -> None:
        self._beacon_stop.set()
        super().close()
        for key in list(self._workers):         # safety net
            self._workers.pop(key).shutdown()


if __name__ == "__main__":
    raise SystemExit(main())
