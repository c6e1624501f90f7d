"""The one traffic generator: pass data, slot-text files and initial weights
from a mix file and a seed.

A traffic mix (``benchmark/traffic/<name>.json``) is parameters only; this
module is the only code that reads them.  Everything is drawn with numpy's
``default_rng(seed)`` in whole-array calls (one draw per slot for all
passes), never per key.  It imports nothing from the program.

    key_distribution   "zipf" (bounded, inverse CDF over the finite
                       vocabulary, exponent ``zipf_exponent``) | "uniform"
    slot_vocab         list (one vocabulary per sparse slot, the first
                       ``n_slots`` are used) or one int for every slot
    keys_per_slot      [lo, hi]: keys per slot per instance, uniform
    instances_per_pass instances in one pass (a multiple of the batch)
    distinct_passes    passes in the cycle the window walks through
    census_keys        optional: distinct keys of every pass, exactly
    cycle_keys         optional: distinct keys of the whole cycle, exactly
    signal_scale       scale of the latent per-key weights behind the label
    dense_range        dense features are uniform in +-dense_range, on a
                       grid of 0.001 (so their text form is exact)

With ``census_keys`` / ``cycle_keys`` every seed gives the same set of
sizes: the draw's distinct-key counts, which swing by a few tenths of a
percent, are brought down to them by merging a few once-seen keys onto
others (``_fit_census``).  This is an economy of set-up, stated as such in
PERF.md: the program's pass boundary runs eager device programs whose
shapes are those counts, so without it every new seed compiles some 50-100
programs anew (about a second each, PERF.md section 6) in every run of
every check.  It also means such a mix can never show what a new census
size costs inside a window; a mix without the two keys has censuses that
differ from pass to pass and does (PERF.md section 7).

A feasign is a 48-bit hash of (slot offset + rank): hot keys are scattered
over the sorted census the way hashed feasigns are, instead of sitting in
adjacent table rows.
"""

from __future__ import annotations

import dataclasses
import os
from concurrent import futures

import numpy as np

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_GOLD = np.uint64(0x9E3779B97F4A7C15)
KEY_DIGITS = 16  # four chunks of four decimal digits; a feasign is < 2**48
_KEY_FIELD = KEY_DIGITS + 1
_DENSE_FIELD = 7  # "-0.123 "
THREADS = min(8, os.cpu_count() or 1)  # set-up only; the window has none


def mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer on uint64 arrays (wraps on overflow)."""
    with np.errstate(over="ignore"):
        z = x.astype(np.uint64) + _GOLD
        z = (z ^ (z >> np.uint64(30))) * _M1
        z = (z ^ (z >> np.uint64(27))) * _M2
        return z ^ (z >> np.uint64(31))


def feasign(global_rank: np.ndarray) -> np.ndarray:
    """48-bit nonzero feasign of a (slot offset + rank) id."""
    return np.maximum(mix64(global_rank) >> np.uint64(16), np.uint64(1))


@dataclasses.dataclass
class PassData:
    """One pass as arrays, in file order.  ``keys[i, s, k]`` is 0 where
    instance i has fewer than k+1 keys in slot s."""

    keys: np.ndarray  # uint64 [N, S, kmax]
    labels: np.ndarray  # float32 [N]
    dense: np.ndarray  # float32 [N, D]
    dense_q: np.ndarray  # int32 [N, D], dense * 1000 exactly

    @property
    def n(self) -> int:
        return int(self.labels.shape[0])

    def census(self) -> np.ndarray:
        u = np.unique(self.keys)
        return u[1:] if u.shape[0] and u[0] == 0 else u

    def rows(self, lo: int, hi: int) -> "PassData":
        return PassData(self.keys[lo:hi], self.labels[lo:hi],
                        self.dense[lo:hi], self.dense_q[lo:hi])


def slot_vocab(mix: dict, n_slots: int) -> np.ndarray:
    v = mix["slot_vocab"]
    if isinstance(v, int):
        return np.full(n_slots, v, dtype=np.int64)
    if len(v) < n_slots:
        raise ValueError(
            f"mix has {len(v)} slot vocabularies, the configuration has "
            f"{n_slots} sparse slots")
    return np.asarray(v[:n_slots], dtype=np.int64)


def _zipf_cdf(mix: dict, max_vocab: int):
    """Unnormalised CDF of k**-s over ranks 1..max_vocab; a slot with a
    smaller vocabulary uses its prefix (bounded Zipf by inverse CDF over
    the finite vocabulary: no mass piles up on the last key the way a
    clipped unbounded draw does)."""
    if mix["key_distribution"] != "zipf":
        return None
    w = np.arange(1, max_vocab + 1, dtype=np.float64)
    np.power(w, -float(mix["zipf_exponent"]), out=w)
    return np.cumsum(w, out=w)


def _slot_keys(mix: dict, cdf, vocab: int, offset: int, total: int,
               seed: int, slot: int, latent: np.ndarray) -> tuple:
    """uint64 [total, hi] feasigns of one slot (0 = no key) and the mean
    latent weight of each instance's keys in it, from the slot's own
    stream of the seed."""
    rng = np.random.default_rng([int(seed), 0x7A55, slot])
    lo, hi = (int(x) for x in mix["keys_per_slot"])
    kind = mix["key_distribution"]
    if kind == "uniform":
        ranks = rng.integers(0, vocab, size=(total, hi), dtype=np.int64)
    elif kind == "zipf":
        u = rng.random(size=(total, hi))
        u *= cdf[vocab - 1]
        ranks = np.searchsorted(cdf[:vocab], u, side="right")
        np.minimum(ranks, vocab - 1, out=ranks)
    else:
        raise ValueError(f"unknown key_distribution {kind!r}")
    k = feasign(ranks.astype(np.uint64) + np.uint64(offset))
    count = rng.integers(lo, hi + 1, size=total)
    absent = np.arange(hi)[None, :] >= count[:, None]
    k[absent] = 0
    w = latent[(k & np.uint64(0xFFFF)).astype(np.int64)]
    w[absent] = 0.0
    return k, w.sum(axis=1) / count


def make_passes(mix: dict, n_slots: int, dense_dim: int, seed: int,
                n_passes: int | None = None) -> list[PassData]:
    """The cycle's distinct passes, all from one distribution.  Slots are
    drawn side by side in threads, each from its own stream of the seed,
    so the result does not depend on the schedule."""
    rng = np.random.default_rng([int(seed), 0x7A55])
    n_passes = int(mix["distinct_passes"]) if n_passes is None else n_passes
    n = int(mix["instances_per_pass"])
    hi = int(mix["keys_per_slot"][1])
    vocab = slot_vocab(mix, n_slots)
    offsets = np.concatenate([[0], np.cumsum(vocab)[:-1]])
    total = n_passes * n
    cdf = _zipf_cdf(mix, int(vocab.max()))
    keys = np.zeros((total, n_slots, hi), dtype=np.uint64)
    # label: Bernoulli(sigmoid(mean over slots of the mean latent weight of
    # the slot's keys)) -- data/synth.py's rule; the latent weight of a key
    # is a seeded table's entry at the key's low 16 bits
    latent = rng.standard_normal(1 << 16) * float(mix["signal_scale"])

    def fill(s: int) -> np.ndarray:
        keys[:, s, :], w = _slot_keys(
            mix, cdf, int(vocab[s]), int(offsets[s]), total, seed, s, latent)
        return w

    with futures.ThreadPoolExecutor(max_workers=THREADS) as pool:
        logit = sum(pool.map(fill, range(n_slots))) / n_slots
        flats = [keys[p * n:(p + 1) * n].reshape(-1) for p in range(n_passes)]
        if "census_keys" in mix:
            list(pool.map(lambda f: _fit_census(f, int(mix["census_keys"])),
                          flats))
    if "cycle_keys" in mix and n_passes == int(mix["distinct_passes"]):
        _fit_cycle(flats, int(mix["cycle_keys"]))
    labels = (rng.random(total) < 1.0 / (1.0 + np.exp(-logit))).astype(
        np.float32)
    q_max = int(round(float(mix["dense_range"]) * 1000))
    dense_q = rng.integers(-q_max, q_max + 1, size=(total, dense_dim),
                           dtype=np.int32)
    dense = (dense_q / 1000.0).astype(np.float32)
    return [
        PassData(keys[p * n:(p + 1) * n], labels[p * n:(p + 1) * n],
                 dense[p * n:(p + 1) * n], dense_q[p * n:(p + 1) * n])
        for p in range(n_passes)
    ]


def _replace(flat: np.ndarray, old: np.ndarray, new: np.ndarray) -> None:
    """In place: every occurrence of old[i] in ``flat`` becomes new[i]."""
    order = np.argsort(old)
    old, new = old[order], new[order]
    pos = np.minimum(np.searchsorted(old, flat), old.shape[0] - 1)
    hit = old[pos] == flat
    flat[hit] = new[pos[hit]]


def _fit_census(flat: np.ndarray, target: int) -> None:
    """Bring the pass's distinct keys down to ``target`` exactly: the
    surplus once-seen keys each become another once-seen key."""
    u, cnt = np.unique(flat, return_counts=True)
    singles = u[(cnt == 1) & (u != 0)]
    surplus = int((u != 0).sum()) - target
    if surplus < 0 or 2 * surplus > singles.shape[0]:
        raise ValueError(
            f"census_keys={target} is out of reach of this draw "
            f"({int((u != 0).sum())} distinct, {singles.shape[0]} seen once)")
    if surplus:
        _replace(flat, singles[:surplus], singles[surplus: 2 * surplus])


def _fit_cycle(flats: list, target: int) -> None:
    """Bring the cycle's distinct keys down to ``target`` exactly without
    changing any pass's count: in the last pass, keys seen once and in no
    other pass become keys of other passes that the last pass lacks."""
    last = flats[-1]
    u, cnt = np.unique(last, return_counts=True)
    others = np.unique(np.concatenate(
        [np.unique(f) for f in flats[:-1]] or [np.zeros(0, np.uint64)]))
    only_last = np.setdiff1d(u[(cnt == 1) & (u != 0)], others,
                             assume_unique=True)
    incoming = np.setdiff1d(others[others != 0], u, assume_unique=True)
    union = np.union1d(u, others)
    surplus = int((union != 0).sum()) - target
    if surplus < 0 or surplus > min(only_last.shape[0], incoming.shape[0]):
        raise ValueError(
            f"cycle_keys={target} is out of reach of this draw "
            f"({int((union != 0).sum())} distinct in the cycle)")
    if surplus:
        _replace(last, only_last[:surplus], incoming[:surplus])


# --------------------------------------------------------------------- text
_LUT4 = np.array(
    [[ord(c) for c in f"{i:04d}"] for i in range(10000)], dtype=np.uint8)


def _put_key(buf: np.ndarray, col: int, k: np.ndarray) -> None:
    """Zero-padded 16 decimal digits of ``k`` at buf[:, col:col+16];
    blanks where k == 0 (the parser skips runs of spaces)."""
    rest = k
    for c in range(KEY_DIGITS // 4 - 1, -1, -1):
        q = rest // np.uint64(10000)
        buf[:, col + 4 * c: col + 4 * c + 4] = _LUT4[(rest - q * np.uint64(
            10000)).astype(np.int64)]
        rest = q
    buf[k == 0, col: col + KEY_DIGITS] = 32


def to_text(p: PassData) -> np.ndarray:
    """Slot text, one fixed-width line per instance:
    ``1 <label> {<n> k1..kn}*S <D> d1..dD\\n`` (data/slot_parser.py's
    format; label slot first, sparse slots, then one dense slot)."""
    n, S, kmax = p.keys.shape
    D = p.dense.shape[1]
    if not 0 < kmax <= 9 or not 0 < D <= 99:
        raise ValueError("to_text holds 1-9 keys per slot, 1-99 dense")
    head = np.full((n, 4), 32, dtype=np.uint8)
    head[:, 0] = ord("1")
    head[:, 2] = 48 + p.labels.astype(np.uint8)
    blocks = [head]
    counts = (p.keys != 0).sum(axis=2).astype(np.uint8)
    for s in range(S):
        blk = np.full((n, 2 + kmax * _KEY_FIELD), 32, dtype=np.uint8)
        blk[:, 0] = 48 + counts[:, s]
        for k in range(kmax):
            _put_key(blk, 2 + k * _KEY_FIELD, p.keys[:, s, k])
        blocks.append(blk)
    tail = np.full((n, 3 + D * _DENSE_FIELD + 1), 32, dtype=np.uint8)
    d = f"{D:2d}"
    tail[:, 0], tail[:, 1] = ord(d[0]), ord(d[1])
    a = np.abs(p.dense_q)
    if a.max(initial=0) > 999:
        raise ValueError("dense_range above 0.999 has no text form here")
    for j in range(D):
        col = 3 + j * _DENSE_FIELD
        tail[:, col] = np.where(p.dense_q[:, j] < 0, ord("-"), 32)
        tail[:, col + 1] = 48
        tail[:, col + 2] = ord(".")
        tail[:, col + 3: col + 6] = _LUT4[a[:, j]][:, 1:]
    tail[:, -1] = 10
    blocks.append(tail)
    return np.concatenate(blocks, axis=1)


def write_files(p: PassData, out_dir: str, stem: str, n_files: int) -> list:
    """The pass as ``n_files`` slot-text files of consecutive instances,
    formatted and written side by side."""
    os.makedirs(out_dir, exist_ok=True)
    bounds = np.linspace(0, p.n, n_files + 1).astype(int)
    paths = [os.path.join(out_dir, f"{stem}-{i:03d}") for i in range(n_files)]

    def write(i: int) -> None:
        to_text(p.rows(bounds[i], bounds[i + 1])).tofile(paths[i])

    with futures.ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(write, range(n_files)))
    return paths


# ------------------------------------------------------------------ weights
_ROW_BLOCK = 1 << 20


def key_space(mix: dict, n_slots: int) -> np.ndarray:
    """Every feasign the mix can draw, sorted: the deployment's whole key
    space (the sum of the slot vocabularies, less the few 48-bit hash
    collisions).  It does not depend on the seed."""
    total = int(slot_vocab(mix, n_slots).sum())
    k = feasign(np.arange(total, dtype=np.uint64))
    k.sort()
    return k[np.concatenate([[True], k[1:] != k[:-1]])]


def initial_rows(n: int, seed: int, embedding_dim: int,
                 init_range: float = 0.02) -> np.ndarray:
    """``n`` rows ``[show, click, embed..., g2sum]`` for the run's sorted
    keys: every row differs, show and click are whole numbers with click
    <= show (a job some passes old), g2sum is 0 (so its growth after one
    step is the first gradient's mean square exactly, with no float32
    cancellation against an older sum).  Drawn in blocks of 2**20 rows,
    each from its own stream of the seed, side by side."""
    rows = np.empty((n, embedding_dim + 3), dtype=np.float32)

    def fill(b: int) -> None:
        out = rows[b * _ROW_BLOCK:(b + 1) * _ROW_BLOCK]
        rng = np.random.default_rng([int(seed), 0x0520, b])
        rng.random(out=out, dtype=np.float32)
        show = np.floor(-8.0 * np.log1p(-out[:, 0] * 0.999))
        click = np.floor(show * out[:, 1] * 0.5)
        out *= 2.0 * init_range
        out -= init_range
        out[:, 0], out[:, 1], out[:, -1] = show, click, 0.0

    with futures.ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(fill, range(-(-n // _ROW_BLOCK))))
    return rows
