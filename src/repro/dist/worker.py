"""Worker-process entrypoint: ``python -m repro.dist.worker --connect ADDR``.

On connect the worker sends a ``hello`` capability handshake — its devices
as JAX reports them (platform, ``device_kind``, count), pid, the
ring-arithmetic envelope it can serve
(the p=2 machine-word fast path plus the general small-modulus path), the
wire codecs it can decode (``protocol.supported_codecs()`` — the master
picks one per connection, so a v0 peer that advertises nothing simply gets
raw frames), and its autotune-cache coverage — then serves ``task``
messages until the master says ``shutdown`` or the socket drops.

A task carries the codeword-ring constructor args, a share index and the
two encoded shares; the worker computes the block product ``h = fa @ gb``
in that ring with XLA's ``Ring.matmul`` (jitted once per ring; the Pallas
``gr_matmul`` kernel only when the master forces it, and then its errors
are the task's errors) and replies with the result encoded in the
connection's codec.  Share ``i`` is computed on local accelerator device
``i mod device_count``, so one worker process spreads its shares over every
chip of its host (a CPU worker computes on its first device).  Workers
never see the operands A and B, only their own shares — exactly the
paper's upload model, and what makes the T-private schemes private
against the pool.

Pipelined streaming: a task header with ``stream: k`` carries no arrays;
``k`` ``chunk`` messages follow (interleavable with other tasks — chunks
are keyed by ``(req, task)``), each holding a slice of ``fa``/``gb`` along
the contraction axis.  The worker computes each chunk's partial product as
it lands and accumulates ``h = ring.add(h, partial)`` — exact, because
partial block products over Z_{p^e}/GR are already reduced and addition is
associative — so master-side encode, socket transfer and worker compute
overlap instead of serializing.

A daemon thread pushes ``heartbeat`` messages every ``--heartbeat``
seconds; the master treats a silent worker as dead after a grace window
and re-dispatches its shares.  ``delay_ms`` in a task header is a
failure-injection knob (tests/CI sleep a victim worker so SIGKILL lands
provably mid-compute); it is ignored unless the master sets it.  An
``echo`` message bounces its payload straight back (``echo_reply``) — the
master's calibration probe for measuring real socket round-trips.
"""
from __future__ import annotations

import argparse
import os
import socket
import sys
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from repro.obs.trace import now as obs_now

from . import chips
from .protocol import (
    PROTOCOL_VERSION,
    ProtocolError,
    connect,
    recv_msg,
    send_msg,
    supported_codecs,
)

__all__ = ["WorkerRuntime", "main"]


def _capabilities(devices: list) -> Dict:
    """The capability handshake payload (devices, rings, codecs, autotune)."""
    import jax

    from repro.kernels.autotune import load_cache

    platform = devices[0].platform
    try:
        cache = load_cache()
        prefix = f"{platform}|"
        coverage = sum(1 for k in cache if k.startswith(prefix))
        entries = len(cache)
    except Exception:  # a corrupt cache must not keep a worker out of the pool
        coverage, entries = 0, 0
    return {
        "protocol": PROTOCOL_VERSION,
        "pid": os.getpid(),
        # the devices as JAX reports them
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax_version": jax.__version__,
        # ring envelope mirrors Ring.__init__'s overflow discipline
        "rings": {"p2_max_e": 32, "general_max_q": 1 << 12},
        # wire codecs this worker can decode; the master negotiates one
        # per connection (absent = v0 peer = raw)
        "codecs": list(supported_codecs()),
        "streaming": True,
        # understands the optional "trace" task-header field and ships
        # compute spans back on result frames (absent = v0 peer: the
        # master synthesizes a span from wall_us instead)
        "tracing": True,
        "autotune": {"entries": entries, "device_entries": coverage},
    }


class _StreamState:
    """Accumulator for one in-flight streamed task."""

    def __init__(self, header: Dict, remaining: int):
        self.header = header  # the original task header (ring, knobs, ids)
        self.remaining = remaining
        self.h: Optional[np.ndarray] = None
        self.wall_us = 0.0
        self.failed = False
        self.t0 = obs_now()  # span start when the task is traced


class WorkerRuntime:
    """One worker's serve loop over an established socket."""

    def __init__(
        self,
        sock: socket.socket,
        name: str = "worker",
        heartbeat_s: float = 1.0,
    ):
        self.sock = sock
        self.name = name
        self.heartbeat_s = heartbeat_s
        self._send_lock = threading.Lock()
        self._stop = threading.Event()
        # (p, e, degrees, use_kernel)
        #     -> (ring, jitted product, jitted add, product implementation)
        self._compute: Dict[Tuple, Tuple] = {}
        self._devices: list = []  # jax.local_devices(), set in serve()
        self.device_tasks: list = []  # shares computed per local device
        # shares computed per product implementation ("xla" / "pallas")
        self.matmul_tasks: Dict[str, int] = {}
        self._compiles = lambda: 0  # running XLA compile count
        # (req, task) -> _StreamState for chunked tasks
        self._streams: Dict[Tuple[int, int], _StreamState] = {}
        self.tasks_done = 0
        self.busy_us = 0.0  # cumulative compute wall, shipped in heartbeats

    # -- ring-matmul closures (jitted once per ring) -----------------------

    def _closure(self, p: int, e: int, degrees: Tuple[int, ...],
                 use_kernel: bool):
        import jax

        from repro.core.galois import make_ring
        from repro.kernels import gr_matmul

        key = (p, e, degrees, use_kernel)
        if key not in self._compute:
            ring = make_ring(p, e, degrees)
            if use_kernel:
                # forced by the master: a ring outside the kernel envelope,
                # or a chip compiler that refuses the kernel, fails the task
                fn, impl = jax.jit(
                    lambda fa, gb: gr_matmul(fa, gb, ring)), "pallas"
            else:
                fn, impl = jax.jit(ring.matmul), "xla"
            # chunk accumulation: partial products are already reduced, so
            # ring addition combines them exactly
            add = jax.jit(ring.add)
            self._compute[key] = (ring, fn, add, impl)
        return self._compute[key]

    # -- messaging ---------------------------------------------------------

    def _send(self, header: Dict, arrays=None, codec: str = "raw") -> None:
        with self._send_lock:
            send_msg(self.sock, header, arrays, codec=codec)

    def _heartbeat_loop(self) -> None:
        while not self._stop.wait(self.heartbeat_s):
            try:
                self._send(self._heartbeat())
            except OSError:
                return  # master gone; the main loop notices on recv

    def _heartbeat(self) -> Dict:
        return {"type": "heartbeat", "t": time.time(),
                "tasks_done": self.tasks_done,
                "busy_us": round(self.busy_us, 1),
                "compiles": self._compiles(),
                "device_tasks": list(self.device_tasks),
                "matmul_tasks": dict(self.matmul_tasks)}

    def _place(self, header: Dict, arrays: Dict):
        """The task's two operands, on the local device of its share."""
        import jax

        dev = self._devices[int(header["i"]) % len(self._devices)]
        return jax.device_put(arrays["fa"], dev), jax.device_put(
            arrays["gb"], dev
        )

    def _task_closure(self, header: Dict):
        ring = header["ring"]
        return self._closure(
            int(ring["p"]), int(ring["e"]),
            tuple(int(d) for d in ring["degrees"]),
            header.get("use_kernel") is True,
        )

    def _reply(self, header: Dict, ok: bool, h=None, err: str = "",
               wall_us: float = 0.0, t0: Optional[float] = None,
               streamed: int = 0) -> None:
        reply = {
            "type": "result",
            "req": header["req"],
            "task": header["task"],
            "i": header["i"],
            "ok": ok,
            "wall_us": wall_us,
        }
        if header.get("trace") and t0 is not None:
            # piggyback the compute span on the result frame; the master
            # re-stamps it with the request's trace id and worker id
            tags: Dict = {"pid": os.getpid(), "ok": ok}
            if streamed:
                # streamed tasks: span covers arrival..final-chunk wall,
                # busy_us is the actual accumulated compute inside it
                tags["streamed"] = streamed
                tags["busy_us"] = round(wall_us, 1)
            reply["spans"] = [{
                "name": "compute", "t0": t0, "t1": obs_now(), "tags": tags,
            }]
        out = {}
        if ok:
            out["h"] = np.asarray(h)
        else:
            reply["err"] = err
        # results travel in the codec the master stamped on the task —
        # the negotiated connection codec, raw for v0-style masters
        self._send(reply, out, codec=header.get("codec", "raw"))
        self.tasks_done += 1
        self.busy_us += wall_us
        if ok:
            self.device_tasks[int(header["i"]) % len(self._devices)] += 1
            impl = self._task_closure(header)[3]
            self.matmul_tasks[impl] = self.matmul_tasks.get(impl, 0) + 1

    def _apply_injection(self, header: Dict) -> None:
        delay_ms = float(header.get("delay_ms", 0.0))
        if delay_ms > 0.0:  # failure-injection knob (see module doc)
            time.sleep(delay_ms / 1e3)
        if header.get("inject_fail"):  # error-injection knob: exercises
            # the master's bounded share-retry path in tests/CI
            raise RuntimeError("injected worker failure")

    def _handle_task(self, header: Dict, arrays: Dict) -> None:
        stream = int(header.get("stream", 0))
        if stream > 0:
            # chunked task: remember the header, accumulate as chunks land
            key = (header["req"], header["task"])
            state = _StreamState(header, stream)
            t0 = time.perf_counter()
            try:
                self._apply_injection(header)
            except Exception as e:
                state.failed = True
                self._reply(header, ok=False,
                            err=f"{type(e).__name__}: {e}",
                            wall_us=(time.perf_counter() - t0) * 1e6,
                            t0=state.t0, streamed=stream)
            self._streams[key] = state
            return
        tw0 = obs_now()
        t0 = time.perf_counter()
        try:
            self._apply_injection(header)
            _, fn, _, _ = self._task_closure(header)
            fa, gb = self._place(header, arrays)
            h = fn(fa, gb)
        except Exception as e:  # computation errors surface at the master
            self._reply(header, ok=False, err=f"{type(e).__name__}: {e}",
                        wall_us=(time.perf_counter() - t0) * 1e6, t0=tw0)
            return
        self._reply(header, ok=True, h=h,
                    wall_us=(time.perf_counter() - t0) * 1e6, t0=tw0)

    def _handle_chunk(self, header: Dict, arrays: Dict) -> None:
        key = (header.get("req"), header.get("task"))
        state = self._streams.get(key)
        if state is None:
            return  # task was re-dispatched elsewhere; drop silently
        state.remaining -= 1
        last = state.remaining <= 0
        if not state.failed:
            t0 = time.perf_counter()
            try:
                _, fn, add, _ = self._task_closure(state.header)
                fa, gb = self._place(state.header, arrays)
                part = fn(fa, gb)
                state.h = part if state.h is None else add(state.h, part)
            except Exception as e:
                state.failed = True
                state.wall_us += (time.perf_counter() - t0) * 1e6
                self._reply(state.header, ok=False,
                            err=f"{type(e).__name__}: {e}",
                            wall_us=state.wall_us, t0=state.t0,
                            streamed=int(state.header.get("stream", 0)))
            else:
                state.wall_us += (time.perf_counter() - t0) * 1e6
        if last:
            self._streams.pop(key, None)
            if not state.failed:
                self._reply(state.header, ok=True, h=state.h,
                            wall_us=state.wall_us, t0=state.t0,
                            streamed=int(state.header.get("stream", 0)))

    def serve(self) -> int:
        import jax

        self._compiles = chips.compile_counter()
        devices = jax.local_devices()
        # shares spread over accelerator devices; a CPU's devices are views
        # of one host's cores, where spreading buys nothing and costs one
        # compile per device
        self._devices = devices[:1] if devices[0].platform == "cpu" else devices
        self.device_tasks = [0] * len(self._devices)
        self._send({"type": "hello", "name": self.name,
                    **_capabilities(devices)})
        hb = threading.Thread(target=self._heartbeat_loop, daemon=True)
        hb.start()
        try:
            while True:
                try:
                    header, arrays = recv_msg(self.sock)
                except (ProtocolError, OSError):
                    return 0  # master hung up: clean exit
                kind = header.get("type")
                if kind == "task":
                    self._handle_task(header, arrays)
                elif kind == "chunk":
                    self._handle_chunk(header, arrays)
                elif kind == "echo":
                    # calibration probe: bounce the payload straight back
                    # so the master can time a real round-trip
                    self._send({"type": "echo_reply",
                                "seq": header.get("seq")}, arrays,
                               codec=header.get("codec", "raw"))
                elif kind == "ping":
                    self._send(self._heartbeat())
                elif kind == "shutdown":
                    return 0
                # unknown types are ignored: forward-compatible masters
        finally:
            self._stop.set()
            try:
                self.sock.close()
            except OSError:
                pass


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--connect", required=True, metavar="ADDR",
        help="master address: tcp:HOST:PORT or unix:/path/to.sock",
    )
    ap.add_argument("--name", default=f"worker-{os.getpid()}")
    ap.add_argument(
        "--heartbeat", type=float, default=1.0, metavar="SECONDS",
        help="heartbeat push interval (default 1s)",
    )
    ap.add_argument(
        "--connect-timeout", type=float, default=30.0, metavar="SECONDS",
    )
    args = ap.parse_args(argv)
    if chips.chip_bound():
        # chip compiles take tens of seconds; CPU pools skip the cache (many
        # CPU workers compile the same programs at once, and JAX's cache
        # writes are not atomic)
        chips.enable_compile_cache()
    sock = connect(args.connect, timeout=args.connect_timeout)
    return WorkerRuntime(sock, args.name, args.heartbeat).serve()


if __name__ == "__main__":
    sys.exit(main())
