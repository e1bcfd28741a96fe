"""Open-loop load generator: an arrival plan from a traffic file and a
seed, and the client threads that submit it at its due times.

A traffic file (``bench/traffic/<name>.json``) holds only parameters:

  image_px        side of the square image in pixels (latents are /8)
  policy          the cache policy every request asks for: {"name": ...}
                  plus that policy's parameters
  rate_per_s      mean Poisson arrival rate after the backlog
  backlog         requests already due when the window opens; a mix with
                  a backlog is offered above capacity, so every cut is
                  full and only the largest bucket is warmed
  edit_every      every edit_every-th request (on average) is an SDEdit
                  edit of a reference latent; 0 for none
  edit_strength   noise level an edit starts from

The arrival times are one Poisson trace, the same for every seed: the
exponential quantiles of the rate at mid-ranks, in an order drawn once
from a fixed constant.  A batch takes seconds, so with a hundred
requests in a window the order of the gaps decides whether a queue
builds, and a tail read over one order swings by tens of percent from
another.  The seed draws what the requests are: their noise, and which
of them are edits (the same number for every seed).  Request seeds and
edit references are folded into 31 bits so that any ``--seed`` up to
2**63 is accepted.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import threading
import time
from typing import Callable, List, Optional

import numpy as np

VAE_FACTOR = 8
ARRIVALS = 20240229        # draws the one order of the arrival gaps
CLIENTS = 4                # client threads that submit the plan


def fold(seed: int, salt: str) -> int:
    """A 31-bit seed derived from ``seed`` and a purpose label."""
    h = hashlib.blake2b(f"{int(seed)}:{salt}".encode(), digest_size=8)
    return int.from_bytes(h.digest(), "little") % (2 ** 31 - 1)


@dataclasses.dataclass
class Arrival:
    index: int
    due_s: float          # seconds after the window opens
    seed: int             # noise seed of this request
    edit: bool
    # filled by the client threads and the completion callback
    submit_s: Optional[float] = None
    done_s: Optional[float] = None
    result: object = None
    error: Optional[BaseException] = None


def latent_shape(traffic: dict, channels: int) -> tuple:
    side = traffic["image_px"] // VAE_FACTOR
    return (side, side, channels)


def n_arrivals(traffic: dict, seconds: float) -> int:
    """Requests in the plan: the backlog plus enough Poisson arrivals to
    outlast the window by a quarter."""
    return traffic["backlog"] + int(math.ceil(
        traffic["rate_per_s"] * seconds * 1.25)) + 1


def make_plan(traffic: dict, seed: int, seconds: float) -> List[Arrival]:
    n = n_arrivals(traffic, seconds)
    backlog = traffic["backlog"]
    k = n - backlog
    gaps = -np.log1p(-(np.arange(k) + 0.5) / k) / traffic["rate_per_s"]
    gaps = gaps[np.random.RandomState(ARRIVALS).permutation(k)]
    due = np.concatenate([np.zeros(backlog), np.cumsum(gaps)])
    rng = np.random.RandomState(fold(seed, "plan"))
    every = traffic.get("edit_every", 0)
    n_edit = n // every if every else 0
    edits = np.zeros(n, bool)
    edits[rng.choice(n, n_edit, replace=False)] = True
    return [Arrival(index=i, due_s=float(due[i]),
                    seed=fold(seed, f"request{i}"), edit=bool(edits[i]))
            for i in range(n)]


def edit_reference(arrival: Arrival, shape: tuple) -> np.ndarray:
    """Reference latents an edit starts from: smooth seeded data of unit
    scale (a low-frequency field plus fine noise), made on the host."""
    rng = np.random.RandomState(fold(arrival.seed, "edit"))
    h, w, c = shape
    yy, xx = np.meshgrid(np.linspace(0, 1, h), np.linspace(0, 1, w),
                         indexing="ij")
    fx, fy, ph = rng.uniform(0.5, 3.0, (3, c))
    field = np.sin(2 * np.pi * (fx * xx[..., None] + fy * yy[..., None])
                   + 2 * np.pi * ph)
    return (field + 0.1 * rng.standard_normal(shape)).astype(np.float32)


class OpenLoop:
    """``CLIENTS`` threads that submit each arrival at its due time.

    ``submit(arrival)`` must return a ``concurrent.futures.Future``; the
    completion time is stamped from the future's callback.  ``stop()``
    ends the clients: an arrival not yet submitted is never submitted.
    """

    def __init__(self, plan: List[Arrival], submit: Callable,
                 clock=time.perf_counter):
        self.plan = plan
        self._submit = submit
        self._clock = clock
        self._stop = threading.Event()
        self._cv = threading.Condition()
        self.completions: List[float] = []     # done times, in order
        self.t0: Optional[float] = None
        self._threads = [threading.Thread(target=self._client, args=(k,),
                                          name=f"bench-client-{k}",
                                          daemon=True)
                         for k in range(CLIENTS)]

    def start(self) -> float:
        self.t0 = self._clock()
        for th in self._threads:
            th.start()
        return self.t0

    def _client(self, k: int) -> None:
        for a in self.plan[k::len(self._threads)]:
            delay = self.t0 + a.due_s - self._clock()
            if self._stop.wait(max(delay, 0.0)):
                return
            a.submit_s = self._clock() - self.t0
            try:
                fut = self._submit(a)
            except BaseException as e:        # counted as failed
                a.error = e
                continue
            fut.add_done_callback(
                lambda f, a=a: self._done(a, f))

    def _done(self, a: Arrival, fut) -> None:
        t = self._clock() - self.t0
        if fut.cancelled():
            return
        exc = fut.exception()
        with self._cv:
            a.done_s = t
            if exc is not None:
                a.error = exc
            else:
                a.result = fut.result()
                self.completions.append(t)
            self._cv.notify_all()

    def wait_close(self, seconds: float, timeout_s: float) -> float:
        """Block until the first completion at or after ``seconds``;
        return its time (the window's close)."""
        deadline = self._clock() + timeout_s
        with self._cv:
            while True:
                late = [t for t in self.completions if t >= seconds]
                if late:
                    return min(late)
                left = deadline - self._clock()
                if left <= 0:
                    raise TimeoutError(
                        f"no batch completed between {seconds} s and "
                        f"{seconds + timeout_s} s into the window")
                self._cv.wait(min(left, 1.0))

    def stop(self) -> None:
        self._stop.set()
        for th in self._threads:
            th.join()

    def submitted(self) -> List[Arrival]:
        return [a for a in self.plan if a.submit_s is not None]
