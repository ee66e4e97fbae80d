"""The traffic generator: operands from the seed, and a closed loop of clients.

One generator reads every traffic mix (``traffic/<name>.json``), and a
file that holds a key it does not read is refused (:func:`check`):

- ``loop``: ``closed``, the only loop the generator runs;
- ``tokens``: the rows t of each request's activation A; or, for routed
  traffic, ``batch_tokens``, from which one expert's rows are
  ``batch_tokens * num_experts_per_tok / n_routed_experts`` of the
  configuration;
- ``clients``: callers in a closed loop; each sends its next request only
  when its previous product has come back, as a model layer waits for the
  result of its projection;
- ``operand_sets``: distinct activations drawn from the seed; client c's
  j-th request multiplies set ``(c + clients * j) mod operand_sets``, so
  every request carries a full-size operand and requests that share a
  codeword carry different ones.
- ``about``: a line of prose for the reader.

The weight B (``r x s``, the configuration's projection) is one per run, as
a layer's weight is.  The draw is that of ``chip_smoke.Smoke.data``: int8
values lifted to Z_2^32 (``numpy.random.default_rng([seed, stream])``), so
the same seed gives the same operands.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

# the rehearsal's shape: small enough for a CPU, with streamed shares
REHEARSAL_RS = (64, 48)
REHEARSAL_TOKENS = 16


LOOPS = ("closed",)
KEYS = {"loop", "tokens", "batch_tokens", "clients", "operand_sets", "about"}


def check(traffic: Dict) -> None:
    """Raise ValueError unless the generator carries out every setting of
    ``traffic``."""
    unknown = sorted(set(traffic) - KEYS)
    if unknown:
        raise ValueError(f"keys the generator does not read: {unknown}")
    if traffic.get("loop") not in LOOPS:
        raise ValueError(f"loop {traffic.get('loop')!r}: the generator "
                         f"runs {LOOPS}")
    if ("tokens" in traffic) == ("batch_tokens" in traffic):
        raise ValueError("give one of tokens and batch_tokens")
    for key in ("clients", "operand_sets"):
        if int(traffic.get(key, 0)) < 1:
            raise ValueError(f"{key} must be at least 1")


def seed_entropy(seed: int) -> int:
    """A non-negative integer for ``default_rng`` from any whole seed."""
    return int(seed) % (1 << 64)


def shape(config: Dict, traffic: Dict, rehearse: bool = False
          ) -> Tuple[int, int, int]:
    """(t, r, s) of one request."""
    proj = config["projection"]
    r, s = int(config[proj["in"]]), int(config[proj["out"]])
    if "tokens" in traffic:
        t = int(traffic["tokens"])
    else:
        t = (int(traffic["batch_tokens"]) * int(config["num_experts_per_tok"])
             // int(config["n_routed_experts"]))
    if rehearse:
        return (min(t, REHEARSAL_TOKENS),) + REHEARSAL_RS
    return t, r, s


def draw_int8(rng: np.random.Generator, shape2: Tuple[int, int]) -> np.ndarray:
    """int8 values lifted to Z_2^32: uint32 words of shape ``shape2 + (1,)``."""
    return rng.integers(-128, 128, tuple(shape2) + (1,)).astype(np.uint32)


def operands(seed: int, t: int, r: int, s: int, sets: int):
    """(B, [A_0 .. A_{sets-1}]) for one run."""
    ent = seed_entropy(seed)
    B = draw_int8(np.random.default_rng([ent, 0]), (r, s))
    rng = np.random.default_rng([ent, 1])
    return B, [draw_int8(rng, (t, r)) for _ in range(sets)]


@dataclass
class Request:
    client: int
    seq: int
    operand: int  # index into the operand sets
    t_submit: float
    t_done: Optional[float] = None
    result: Optional[np.ndarray] = field(default=None, repr=False)
    error: Optional[str] = None
    trace_id: Optional[str] = None  # the program's trace of the request

    @property
    def answered(self) -> bool:
        return self.t_done is not None and self.error is None

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit


@dataclass
class Window:
    start: float  # the first timed request is submitted here
    close: float  # no request is submitted after this
    requests: List[Request]

    @property
    def end(self) -> float:
        """The last answer of the window (its close if none came): every
        request submitted before the close is waited for, so the window
        holds all the work it started."""
        done = [q.t_done for q in self.requests if q.answered]
        return max(done + [self.close])


def closed_loop(submit: Callable, As: List[np.ndarray], B: np.ndarray,
                clients: int, seconds: float, wait_s: float = 60.0,
                clock: Callable[[], float] = time.perf_counter) -> Window:
    """Run ``clients`` callers for ``seconds``; returns the window.

    ``submit(A, B)`` returns a future.  A request not answered ``wait_s``
    after the close counts as unanswered, as does one that raised.
    """
    requests: List[Request] = []
    lock = threading.Lock()
    start = clock()
    close = start + seconds

    def client(c: int) -> None:
        j = 0
        while clock() < close:
            k = (c + clients * j) % len(As)
            q = Request(client=c, seq=j, operand=k, t_submit=clock())
            with lock:
                requests.append(q)
            try:
                fut = submit(As[k], B)
                q.trace_id = getattr(fut, "trace_id", None)
                q.result = fut.result(timeout=max(close + wait_s - clock(),
                                                  1e-3))
                q.t_done = clock()
            except Exception as e:  # a failed request is counted, not fatal
                q.error = f"{type(e).__name__}: {e}"
                return
            j += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                name=f"client-{c}") for c in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=max(close + wait_s + 30 - clock(), 1.0))
    return Window(start=start, close=close, requests=requests)
