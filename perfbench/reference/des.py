"""Plain reference for the fleet DES: one FCFS M/M/n cluster simulated
customer by customer, with a heap of server free times.

It follows the reconfiguration contract of the simulator under test
(``FleetSimulator(engine="vector")``), written out from its documentation
and not from its code:

* Random numbers. Each cluster draws its inter-arrival gaps from the stream
  ``default_rng([seed & 0x7FFFFFFF, 17, *utf8(name)])`` and its service
  times from salt 29, in chunks of 4096 exponential draws at the current
  rate. One arrival is always drawn ahead. A service time is drawn when its
  customer arrives, in arrival order.
* A rate change at an epoch boundary T discards the chunk of the old rate:
  for arrivals the drawn-ahead arrival is superseded by a fresh draw from T;
  for service, the next service draws come from a fresh chunk, and the
  customers still waiting at T have their drawn times scaled by
  mu_old / mu_new.
* A change of the server count n is non-preemptive: customers in service
  finish; of the servers busy at T the n with the latest completions stay,
  the rest retire as they finish, and idle servers are added at T.
* Customers still waiting at T keep their place in line (FCFS).

A customer counts once its service has started: its response time (wait
plus service) is then final. ``dtype`` is float64 for the reference and
float32 for the lower-precision control.
"""
from __future__ import annotations

import heapq

import numpy as np

CHUNK = 4096
ARRIVAL_SALT = 17
SERVICE_SALT = 29


def stream(seed: int, name: str, salt: int) -> np.random.Generator:
    key = list(name.encode("utf-8"))
    return np.random.default_rng([int(seed) & 0x7FFFFFFF, salt, *key])


class _Draws:
    """Chunked exponential draws at a rate; a rate change drops the chunk."""

    def __init__(self, rng: np.random.Generator, rate: float, dtype):
        self.rng, self.rate, self.dtype = rng, float(rate), dtype
        self.buf, self.pos = np.empty(0), 0

    def set_rate(self, rate: float) -> None:
        self.rate = float(rate)
        self.buf, self.pos = np.empty(0), 0

    def next(self):
        if self.pos >= self.buf.shape[0]:
            self.buf = self.rng.exponential(1.0 / self.rate, size=CHUNK)
            self.pos = 0
        v = self.buf[self.pos]
        self.pos += 1
        return self.dtype(v)


def simulate_cluster(seed: int, name: str, epochs, epoch_s: float,
                     dtype=np.float64) -> dict:
    """Simulate one cluster through ``epochs``, a list of (lam, mu, n), each
    lasting ``epoch_s``. Returns per-customer arrays, in arrival order, of
    the customers whose service started within the simulated time:
    ``t_arr``, ``wait``, ``svc`` and ``response``."""
    dtype = np.dtype(dtype).type
    lam0, mu0, n0 = epochs[0]
    gaps = _Draws(stream(seed, name, ARRIVAL_SALT), lam0, dtype)
    svcs = _Draws(stream(seed, name, SERVICE_SALT), mu0, dtype)
    pending = dtype(0.0) + gaps.next()  # the drawn-ahead arrival
    lam, mu = float(lam0), float(mu0)
    free = [dtype(0.0)] * int(n0)  # heap of server free times
    queue: list = []  # waiting customers: (true arrival, service)
    out_t, out_w, out_s = [], [], []
    for e, (lam_e, mu_e, n_e) in enumerate(epochs):
        t0 = dtype(e * epoch_s)
        t1 = dtype((e + 1) * epoch_s)
        if e:
            if float(lam_e) != lam:
                lam = float(lam_e)
                gaps.set_rate(lam)
                pending = t0 + gaps.next()
            if float(mu_e) != mu:
                scale = dtype(mu) / dtype(mu_e)
                queue = [(ta, s * scale) for ta, s in queue]
                mu = float(mu_e)
                svcs.set_rate(mu)
            busy = sorted(f for f in free if f > t0)[-int(n_e):] if int(n_e) else []
            free = busy + [t0] * (int(n_e) - len(busy))
            heapq.heapify(free)
        line = [(t0, ta, s) for ta, s in queue]  # (effective arrival, true, service)
        while pending <= t1:
            line.append((pending, pending, svcs.next()))
            pending = pending + gaps.next()
        queue = []
        for i, (t_eff, t_true, s) in enumerate(line):
            start = max(t_eff, free[0]) if free else None
            if start is None or start > t1:
                queue = [(ta, sv) for _, ta, sv in line[i:]]
                break
            heapq.heapreplace(free, start + s)
            out_t.append(t_true)
            out_w.append(start - t_true)
            out_s.append(s)
    t_arr = np.asarray(out_t, dtype=float)
    wait = np.asarray(out_w, dtype=float)
    svc = np.asarray(out_s, dtype=float)
    return {"t_arr": t_arr, "wait": wait, "svc": svc, "response": wait + svc}
