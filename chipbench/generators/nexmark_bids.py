"""Generator ``nexmark_bids``: seeded multi-tenant NEXmark bid streams.

Numpy only: the traffic client imports it without JAX.  Each tenant is
one NEXmark event generator of its own (Apache Beam's
``sdks/java/testing/nexmark``: ``GeneratorConfig``, ``BidGenerator``,
``PriceGenerator``), of which only the bids are kept.  Its stream is a
pure function of ``(seed, tenant)``, generated in fixed blocks on demand,
so a run can read as far into it as the system under test lets it, and
the reference regenerates exactly the rows that were sent.

Bid ``j`` of a tenant is event ``(j // 46) * 50 + 4 + j % 46`` (each 50
events are 1 person, 3 auctions, 46 bids), at event time ``event /
firstEventRate`` seconds.  Its auction is, with probability ``1 -
1 / hotAuctionRatio``, the hot one (the first of the current batch of 100
auctions), else uniform over the last ``numInFlightAuctions`` auctions
and the next 10; ids start at 1000.  Its price is ``round(10 ** (6 U) *
100)`` cents, ``U`` uniform in [0, 1): 100 to 10**8.
"""

from __future__ import annotations

import numpy as np

BLOCK = 1 << 16

# Beam NEXmark's fixed generator constants
PERSON_PROPORTION, AUCTION_PROPORTION, BID_PROPORTION = 1, 3, 46
PROPORTION_DENOMINATOR = 50
FIRST_AUCTION_ID = 1000
HOT_AUCTION_BATCH = 100  # AuctionGenerator's HOT_AUCTION_RATIO
AUCTION_ID_LEAD = 10


def bid_events(j: np.ndarray) -> np.ndarray:
    """The event number of bid ``j`` (bids fill offsets 4..49 of each
    epoch of 50 events)."""
    return ((j // BID_PROPORTION) * PROPORTION_DENOMINATOR
            + PERSON_PROPORTION + AUCTION_PROPORTION + j % BID_PROPORTION)


def last_auction(j: np.ndarray) -> np.ndarray:
    """Base-0 id of the last auction created before bid ``j``
    (``lastBase0AuctionId`` for a bid event: the epoch's last)."""
    return (j // BID_PROPORTION) * AUCTION_PROPORTION + AUCTION_PROPORTION - 1


class TenantStream:
    """One tenant's bids, ``rows(lo, hi)`` → ``(auction int32, ts float32
    seconds, price int32 cents)`` for bids ``lo .. hi-1``."""

    def __init__(self, seed: int, tenant: int, *, first_event_rate: float,
                 hot_auction_ratio: int, in_flight_auctions: int):
        self.seed = int(seed)
        self.tenant = int(tenant)
        self.rate = float(first_event_rate)
        self.hot_ratio = int(hot_auction_ratio)
        self.in_flight = int(in_flight_auctions)
        self._blocks: dict = {}

    def _block(self, b: int) -> tuple:
        if b not in self._blocks:
            rng = np.random.default_rng((self.seed, self.tenant, 2, b))
            j = np.arange(b * BLOCK, (b + 1) * BLOCK, dtype=np.int64)
            last = last_auction(j)
            hot = rng.integers(self.hot_ratio, size=BLOCK) > 0
            lo = np.maximum(last - self.in_flight, 0)
            cold = lo + rng.integers(0, last - lo + 1 + AUCTION_ID_LEAD)
            auction = np.where(hot, last // HOT_AUCTION_BATCH
                               * HOT_AUCTION_BATCH, cold) + FIRST_AUCTION_ID
            price = np.round(10.0 ** (rng.random(BLOCK) * 6.0) * 100.0)
            ts = bid_events(j) / self.rate
            self._blocks[b] = (auction.astype(np.int32),
                               ts.astype(np.float32), price.astype(np.int32))
        return self._blocks[b]

    def rows(self, lo: int, hi: int) -> tuple:
        if not 0 <= lo <= hi:
            raise ValueError(f"bad row range [{lo}, {hi})")
        parts = [self._block(b) for b in range(lo // BLOCK,
                                                (hi + BLOCK - 1) // BLOCK)]
        if not parts:
            return (np.zeros(0, np.int32), np.zeros(0, np.float32),
                    np.zeros(0, np.int32))
        base = (lo // BLOCK) * BLOCK
        return tuple(np.concatenate([p[j] for p in parts])[lo - base:hi - base]
                     for j in range(3))


def tenant_streams(seed: int, gen: dict) -> list:
    """The configuration's tenants, from its ``generator`` section
    (``tenants`` and Beam ``NexmarkConfiguration`` names)."""
    return [TenantStream(seed, i, first_event_rate=gen["firstEventRate"],
                         hot_auction_ratio=gen["hotAuctionRatio"],
                         in_flight_auctions=gen["numInFlightAuctions"])
            for i in range(gen["tenants"])]
