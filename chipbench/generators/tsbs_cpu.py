"""Generator ``tsbs_cpu``: seeded TSBS devops ``cpu-only`` rows, one fleet
of hosts per tenant.

Numpy only: the traffic client imports it without JAX.  Timescale's Time
Series Benchmark Suite (github.com/timescale/tsbs), use case
``cpu-only``: each of ``hosts`` hosts (``--scale``) writes one row of the
``cpu`` measurement every ``log_interval_s`` seconds (``--log-interval``).
Rows come in the order TSBS's simulator emits them, interval-major and
host-minor: row ``r`` is host ``r % hosts`` at ``(r // hosts) *
log_interval_s`` seconds.  A row's values are the 10 fields of
``pkg/data/usecases/devops/cpu.go``, in that file's order.  Each field of
each host is that file's clamped random walk: it starts uniform in
[0, 100), steps by N(0, 1) at each later interval and is clamped to
[0, 100].  The walk runs in float64 and is rounded to float32 on output.

A stream is generated in blocks of whole intervals.  Block ``b``'s steps
are drawn from ``(seed, tenant, b)`` and its walk starts where block
``b - 1``'s ended, so ``rows(lo, hi)`` returns the same numbers whichever
block is read first.
"""

from __future__ import annotations

import numpy as np

FIELDS = ("usage_user", "usage_system", "usage_idle", "usage_nice",
          "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
          "usage_guest", "usage_guest_nice")
BLOCK_ROWS = 1 << 16  # a block is the most whole intervals that fit
LOW, HIGH = 0.0, 100.0  # cpu.go's clamp, and the range of the first value


class TenantStream:
    """One tenant's fleet, ``rows(lo, hi)`` → ``(host int32, ts float32
    seconds, fields float32 (n, 10))`` for rows ``lo .. hi-1``."""

    def __init__(self, seed: int, tenant: int, *, hosts: int,
                 log_interval_s: float):
        self.seed = int(seed)
        self.tenant = int(tenant)
        self.hosts = int(hosts)
        self.interval = float(log_interval_s)
        self.block_intervals = max(1, BLOCK_ROWS // self.hosts)
        self.block_rows = self.block_intervals * self.hosts
        rng = np.random.default_rng((self.seed, self.tenant, 0))
        self._state = LOW + rng.random((self.hosts, len(FIELDS))) * (
            HIGH - LOW)
        self._blocks: list = []

    def _walk(self) -> None:
        """Generate the next block from where the walk stands."""
        b, n = len(self._blocks), self.block_intervals
        steps = np.random.default_rng(
            (self.seed, self.tenant, 1, b)).standard_normal(
                (n, self.hosts, len(FIELDS)))
        vals = np.empty_like(steps)
        for i in range(n):
            vals[i] = self._state
            self._state = np.clip(self._state + steps[i], LOW, HIGH)
        first = b * n
        ts = (first + np.arange(n, dtype=np.float64)) * self.interval
        self._blocks.append((
            np.tile(np.arange(self.hosts, dtype=np.int32), n),
            np.repeat(ts, self.hosts).astype(np.float32),
            vals.reshape(-1, len(FIELDS)).astype(np.float32)))

    def rows(self, lo: int, hi: int) -> tuple:
        if not 0 <= lo <= hi:
            raise ValueError(f"bad row range [{lo}, {hi})")
        first, last = lo // self.block_rows, -(-hi // self.block_rows)
        while len(self._blocks) < last:
            self._walk()
        parts = self._blocks[first:last]
        if not parts:
            return (np.zeros(0, np.int32), np.zeros(0, np.float32),
                    np.zeros((0, len(FIELDS)), np.float32))
        base = first * self.block_rows
        return tuple(np.concatenate([p[j] for p in parts])[lo - base:hi - base]
                     for j in range(3))


def tenant_streams(seed: int, gen: dict) -> list:
    """The configuration's tenants, from its ``generator`` section:
    ``tenants``, ``hosts`` (TSBS ``--scale``) and ``log_interval_s``."""
    return [TenantStream(seed, i, hosts=gen["hosts"],
                         log_interval_s=gen["log_interval_s"])
            for i in range(gen["tenants"])]
