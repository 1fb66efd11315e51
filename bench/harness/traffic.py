"""The one traffic generator: a mix file's parameters in, counts out.

A mix (``bench/traffic/<name>.json``) fixes, per monitored link, a
consumer rate and a producer rate drawn uniformly from
``[rate_min, rate_max]`` items per period, the share of periods that are
blocked at each end (i.i.d.), and the share of links whose consumer rate
steps: it alternates between r and ``step_factor * r`` every
``step_periods`` periods, each stepped link with its own phase.  Counts
are Poisson per end and period.  ``cycle_periods`` periods are drawn;
longer runs repeat them, so ``cycle_periods`` is a multiple of
``2 * step_periods`` and the steps continue seamlessly across a repeat.

Ends are numbered as the fleet monitor publishes them: the n heads
(consumer ends) first, then the n tails (producer ends).  Everything is
drawn from the seed: the link parameters on the host, the counts on the
device in blocks of ``block`` periods.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

__all__ = ["Mix", "Links", "load_mix", "draw_links", "draw_counts",
           "switches"]


@dataclass(frozen=True)
class Mix:
    name: str
    rate_min: float
    rate_max: float
    blocked_share: float
    stepped_share: float
    step_periods: int
    step_factor: float
    cycle_periods: int


@dataclass(frozen=True)
class Links:
    rate: np.ndarray       # (2n,) items/period, heads then tails
    stepped: np.ndarray    # (2n,) bool: a head whose rate steps
    phase: np.ndarray      # (2n,) int: a stepped head is high while
    #                        ((t + phase) // step_periods) is odd


def load_mix(path: Path) -> Mix:
    d = json.loads(Path(path).read_text())
    d.pop("why", None)
    mix = Mix(**d)
    if mix.cycle_periods % (2 * mix.step_periods):
        raise ValueError("cycle_periods must be a multiple of "
                         "2 * step_periods")
    return mix


def _seeds(seed: int):
    host, dev = np.random.SeedSequence(int(seed)).spawn(2)
    return host, dev.generate_state(2, np.uint32)


def draw_links(mix: Mix, n_links: int, seed: int) -> Links:
    rng = np.random.default_rng(_seeds(seed)[0])
    rate = rng.uniform(mix.rate_min, mix.rate_max, 2 * n_links)
    n_step = int(round(mix.stepped_share * n_links))
    stepped = np.zeros(2 * n_links, bool)
    stepped[rng.choice(n_links, n_step, replace=False)] = True
    phase = np.where(stepped,
                     rng.integers(0, mix.step_periods, 2 * n_links), 0)
    return Links(rate, stepped, phase)


def switches(mix: Mix, links: Links, t0: int, t1: int):
    """Every rate step of a stepped head in periods [t0, t1): arrays
    (end, period), ordered by period.  Period 0 is no step."""
    ends = np.flatnonzero(links.stepped)
    out_e, out_t = [], []
    for e in ends:
        first = (-links.phase[e]) % mix.step_periods
        ts = np.arange(first, t1, mix.step_periods)
        ts = ts[(ts >= max(t0, 1))]
        out_e.append(np.full(ts.size, e))
        out_t.append(ts)
    e = np.concatenate(out_e) if out_e else np.zeros(0, int)
    t = np.concatenate(out_t) if out_t else np.zeros(0, int)
    order = np.argsort(t, kind="stable")
    return e[order], t[order]


def draw_counts(mix: Mix, links: Links, seed: int, *, columns=None,
                end_major: bool = False, block: int = 256):
    """All ``cycle_periods`` periods of counts and blocked flags, on the
    host, as a list of per-block arrays.

    Period-major blocks are (block, E) with column k holding end
    ``columns[k]`` (default: public order); ``end_major`` blocks are
    (E, block) in public order."""
    import jax
    import jax.numpy as jnp

    key = jnp.asarray(_seeds(seed)[1])                 # raw threefry key
    s = links.rate.shape[0]
    cols = np.arange(s) if columns is None else np.asarray(columns)
    rate = jnp.asarray(links.rate[cols], jnp.float32)
    stepped = jnp.asarray(links.stepped[cols])
    phase = jnp.asarray(links.phase[cols], jnp.int32)
    if mix.cycle_periods % block:
        raise ValueError("cycle_periods must be a multiple of the block")

    # everything drawn from the seed is an argument, never a constant of
    # the program, so one compiled program serves every seed
    @jax.jit
    def gen(key, b, rate, stepped, phase):
        k_cnt, k_blk = jax.random.split(jax.random.fold_in(key, b))
        t = b * block + jnp.arange(block, dtype=jnp.int32)[:, None]
        high = stepped & ((((t + phase) // mix.step_periods) % 2) == 1)
        lam = rate * jnp.where(high, jnp.float32(mix.step_factor), 1.0)
        cnt = jax.random.poisson(k_cnt, lam, (block, s)).astype(jnp.float32)
        blk = jax.random.bernoulli(k_blk, mix.blocked_share, (block, s))
        if end_major:
            return cnt.T, blk.T
        return cnt, blk

    out = []
    for b in range(mix.cycle_periods // block):
        cnt, blk = gen(key, jnp.int32(b), rate, stepped, phase)
        out.append((np.asarray(cnt), np.asarray(blk)))
    return out


class Recorded:
    """The drawn cycle of counts as a runner holds it, read back by
    period for the comparison.  ``blocks`` come from ``draw_counts``;
    ``pos[e]`` is the column (period-major) or row (end-major) that holds
    public end ``e``."""

    def __init__(self, blocks, pos: np.ndarray, end_major: bool):
        self.blocks = blocks
        self.pos = np.asarray(pos)
        self.end_major = end_major
        self.block = blocks[0][0].shape[1 if end_major else 0]
        self.period = self.block * len(blocks)

    def _cycle(self, ends):
        """(period, len(ends)) counts and blocked flags of one cycle."""
        p = self.pos[np.asarray(ends)]
        if self.end_major:
            cnt = np.concatenate([c[p].T for c, _ in self.blocks])
            blk = np.concatenate([b[p].T for _, b in self.blocks])
        else:
            cnt = np.concatenate([c[:, p] for c, _ in self.blocks])
            blk = np.concatenate([b[:, p] for _, b in self.blocks])
        return cnt, blk

    def rows(self, ends, t0: int, t1: int):
        """Counts (float64) and blocked flags of ``ends`` over periods
        [t0, t1), as (t1 - t0, len(ends)) arrays."""
        cnt, blk = self._cycle(ends)
        t = np.arange(t0, t1) % self.period
        return cnt[t].astype(np.float64), blk[t]

    def blocked_totals(self, t_fed: int) -> np.ndarray:
        """Blocked periods of every end (public order) in [0, t_fed)."""
        axis = 1 if self.end_major else 0
        per_block = np.stack([b.sum(axis=axis) for _, b in self.blocks])
        full, rest = divmod(t_fed, self.period)
        tot = full * per_block.sum(axis=0)
        nb, r = divmod(rest, self.block)
        tot = tot + per_block[:nb].sum(axis=0)
        if r:
            b = self.blocks[nb][1]
            tot = tot + (b[:, :r].sum(axis=1) if self.end_major
                         else b[:r].sum(axis=0))
        return tot[self.pos]

    def recent(self, t_fed: int, k: int):
        """Counts and blocked flags of every end (public order) over the
        last ``k`` periods fed, as (k, E) arrays."""
        cnt, blk = [], []
        for t in range(t_fed - k, t_fed):
            b, r = divmod(t % self.period, self.block)
            c, f = self.blocks[b]
            cnt.append(c[:, r] if self.end_major else c[r])
            blk.append(f[:, r] if self.end_major else f[r])
        return np.stack(cnt)[:, self.pos], np.stack(blk)[:, self.pos]
