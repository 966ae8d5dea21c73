"""The general generator of live payment traffic, from the parameters
of a workload file and the run's seed.

Parameters (workloads/<cell>.json "traffic"):
  loop           "closed": `clients` callers, each sends its account's
                 next payment the moment the previous one has its reply
                 "open": independent users; Poisson arrivals at
                 `rate_per_s`, each takes the next idle account
  clients        closed loop: how many accounts take part
  rate_per_s     open loop: arrivals per second (fixed in the file)
  corrupt_every  one submission in this many carries a signature with
                 one bit flipped; the right answer is a refusal
  submit_batch   at most this many submissions between two cranks

The seed draws amounts, destinations, the arrival times and which
submission of each block of `corrupt_every` is the corrupted one; the
number of arrivals and of corrupted ones is the same for every seed.
"""

from __future__ import annotations

from collections import deque
from typing import List, Optional

from ..harness.stats import poisson_arrivals, rng_for


class Request:
    __slots__ = ("k", "account", "due", "corrupt", "amount", "dest",
                 "done", "ledger", "refused", "txid")

    def __init__(self, k: int, account: int, due: float, corrupt: bool,
                 amount: int, dest: int) -> None:
        self.k, self.account, self.due, self.corrupt = \
            k, account, due, corrupt
        self.amount, self.dest = amount, dest
        self.done: Optional[float] = None    # reply on every node
        self.ledger: Optional[int] = None
        self.refused: Optional[bool] = None
        self.txid: Optional[str] = None


class Payments:
    def __init__(self, traffic: dict, seed: int, n_accounts: int,
                 seconds: float) -> None:
        self.n_accounts = n_accounts
        self.seconds = seconds
        self.closed = traffic["loop"] == "closed"
        self.corrupt_every = int(traffic["corrupt_every"])
        self.rng = rng_for(seed, "payments")
        self._crng = rng_for(seed, "payments-corrupt")
        self._corrupt_at = -1
        self.k = 0
        self.requests: List[Request] = []
        self.no_idle_account = 0
        if self.closed:
            self.ready = deque((i, 0.0) for i in
                               range(int(traffic["clients"])))
        else:
            self.arrivals = deque(poisson_arrivals(
                rng_for(seed, "payments-arrivals"),
                float(traffic["rate_per_s"]), seconds))
            self.idle = deque(range(n_accounts))
            self.n_due = len(self.arrivals)

    def _is_corrupt(self, k: int) -> bool:
        if k % self.corrupt_every == 0:
            self._corrupt_at = k + self._crng.randrange(self.corrupt_every)
        return k == self._corrupt_at

    def _make(self, account: int, due: float) -> Request:
        k = self.k
        self.k += 1
        dest = self.rng.randrange(self.n_accounts - 1)
        if dest >= account:
            dest += 1
        req = Request(k, account, due, self._is_corrupt(k),
                      1 + self.rng.randrange(10000), dest)
        self.requests.append(req)
        return req

    def take_due(self, now: float, limit: int) -> List[Request]:
        """Requests due by `now` (seconds since the window opened), at
        most `limit`. Once the window has closed a closed loop sends no
        more; an open loop still owes every arrival that was due inside
        the window and that the generator was too late to send."""
        out: List[Request] = []
        if self.closed:
            while now < self.seconds and self.ready and len(out) < limit:
                account, due = self.ready.popleft()
                out.append(self._make(account, due))
        else:
            while self.arrivals and self.arrivals[0] <= now and \
                    len(out) < limit:
                due = self.arrivals.popleft()
                if not self.idle:
                    self.no_idle_account += 1
                    req = self._make(0, due)
                    req.refused = True      # could not be sent at all
                    continue
                out.append(self._make(self.idle.popleft(), due))
        return out

    def replied(self, req: Request, now: float) -> None:
        """The caller got its reply (applied everywhere, or refused)."""
        if self.closed:
            self.ready.append((req.account, now))
        else:
            self.idle.append(req.account)
