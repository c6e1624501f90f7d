// Native batch planner: resolve a padded key buffer against the pass
// census — the host half of the sparse pull/push (the analog of the
// reference's CopyKeys + DedupKeysAndFillIdx staging,
// box_wrapper_impl.h:95-122, which runs in CUDA because its keys live on
// device; ours live on the host).
//
// The numpy implementation (sparse/table.py plan_keys: np.unique +
// np.searchsorted) costs ~6-15ms per 131k-key batch, dominated by the
// sort inside np.unique.  This version is sort-free:
//
//   * per PASS: one open-addressing hash index over the sorted census
//     (splitmix64 probe; built once in pbx_census_index_build, amortized
//     over every batch of the pass);
//   * per BATCH: one O(K) walk — a local hash dedups occurrences into
//     FIRST-SEEN slot order while each new key does an O(1) census
//     lookup.
//
// Slot numbering therefore differs from numpy's sorted order, but every
// training-visible quantity is identical: idx (per-occurrence pull rows)
// is order-free, and the push's segment-sum -> scatter pipeline permutes
// rows consistently through inverse/uniq_idx, so training results match
// the numpy path BIT-FOR-BIT (pinned end-to-end by test_native_planner).
//
// Contract (order-insensitive form of plan_keys).  The occurrence side
// is K long: the caller's bucket for the batch's occurrences, the first K
// slots of its key buffer (n_real <= K <= the buffer's capacity; the
// caller knows n_real and sizes K before it asks).  The unique side is U
// long, the caller's bucket for the batch's distinct keys (U <= K):
//   idx[occ]      = found ? census_row : dead        (occ < n_real)
//                 = dead                             (padding)
//   uniq_idx[j]   = found ? census_row : min(scratch_base + j, dead)
//                                                    (j < U)
//   inverse[occ]  = first-seen slot of the occurrence; U-1 for padding
//   key_mask[occ] = 1.0 real / 0.0 padding
//   *n_uniq_out   = distinct keys of the batch, found or missing
//   returns n_missing (unique keys absent from the census)
// The walk is what counts the distinct keys, so the caller learns from
// *n_uniq_out whether they fit: the outputs are a plan when n_uniq <= U-1
// (slot U-1 is the padding's and stays non-live) or U == K; otherwise
// slots >= U were not written and the caller asks again with a larger U.
//
// The same library holds the row cache's directory work of a pass boundary
// (sparse/engine/hbm_cache.py lookup / touch): pbx_cache_lookup and
// pbx_cache_touch, at the end of this file.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <system_error>
#include <thread>
#include <vector>

namespace {

inline unsigned long long splitmix64(unsigned long long x) {
  x += 0x9E3779B97F4A7C15ULL;
  x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ULL;
  x = (x ^ (x >> 27)) * 0x94D049BB133111EBULL;
  return x ^ (x >> 31);
}

inline unsigned long long pow2_at_least(unsigned long long n) {
  unsigned long long c = 64;
  while (c < n) c <<= 1;
  return c;
}

constexpr unsigned int kEmpty = 0xFFFFFFFFu;

struct CensusIndex {
  const unsigned long long* keys;  // borrowed (the table's census array)
  long long n;
  unsigned long long mask;
  std::vector<unsigned int> slot;  // census row per hash cell, kEmpty free
};

}  // namespace

extern "C" {

// Build the per-pass census index.  ``census`` must outlive the handle
// (the table owns its sorted pass-key array for the whole pass).
void* pbx_census_index_build(const unsigned long long* census,
                             long long n_pass) {
  auto* ix = new CensusIndex();
  ix->keys = census;
  ix->n = n_pass;
  unsigned long long cap = pow2_at_least(
      (unsigned long long)(n_pass > 0 ? 2 * n_pass : 1));
  ix->mask = cap - 1;
  ix->slot.assign(cap, kEmpty);
  for (long long i = 0; i < n_pass; ++i) {
    unsigned long long h = splitmix64(census[i]) & ix->mask;
    while (ix->slot[h] != kEmpty) h = (h + 1) & ix->mask;
    ix->slot[h] = (unsigned int)i;
  }
  return ix;
}

void pbx_census_index_free(void* handle) {
  delete static_cast<CensusIndex*>(handle);
}

// Resolve one batch against a built census index.  Outputs are
// preallocated by the caller; see the contract above.
long long pbx_plan_resolve(
    void* handle,
    const unsigned long long* keys, long long K, long long n_real,
    int dead, int scratch_base, long long U,
    int* idx, int* uniq_idx, int* inverse, float* key_mask,
    long long* n_uniq_out) {
  if (n_real < 0 || n_real > K || U < 0 || U > K) return -1;
  const CensusIndex* ix = static_cast<CensusIndex*>(handle);
  *n_uniq_out = 0;

  // padding defaults (tail slots + tail occurrences)
  for (long long j = 0; j < U; ++j) {
    long long scratch = (long long)scratch_base + j;
    uniq_idx[j] = (int)(scratch < dead ? scratch : dead);
  }
  for (long long o = n_real; o < K; ++o) {
    idx[o] = dead;
    inverse[o] = (int)(U - 1);
    key_mask[o] = 0.0f;
  }
  if (n_real == 0) return 0;

  // local dedup hash: cell -> slot; keys of the slots live in uniq_key
  unsigned long long lmask = pow2_at_least((unsigned long long)(2 * n_real)) - 1;
  std::vector<unsigned int> lslot((size_t)lmask + 1, kEmpty);
  std::vector<unsigned long long> uniq_key((size_t)n_real);
  std::vector<int> pull_row((size_t)n_real);  // per slot

  long long n_uniq = 0;
  long long n_missing = 0;
  for (long long o = 0; o < n_real; ++o) {
    const unsigned long long k = keys[o];
    unsigned long long h = splitmix64(k) & lmask;
    long long slot = -1;
    while (true) {
      unsigned int s = lslot[h];
      if (s == kEmpty) break;
      if (uniq_key[s] == k) {
        slot = (long long)s;
        break;
      }
      h = (h + 1) & lmask;
    }
    if (slot < 0) {  // first occurrence: census lookup
      slot = n_uniq++;
      lslot[h] = (unsigned int)slot;
      uniq_key[(size_t)slot] = k;
      long long row = -1;
      unsigned long long ch = splitmix64(k) & ix->mask;
      while (true) {
        unsigned int c = ix->slot[ch];
        if (c == kEmpty) break;
        if (ix->keys[c] == k) {
          row = (long long)c;
          break;
        }
        ch = (ch + 1) & ix->mask;
      }
      if (row >= 0) {
        pull_row[(size_t)slot] = (int)row;
        if (slot < U) uniq_idx[slot] = (int)row;
      } else {
        pull_row[(size_t)slot] = dead;
        ++n_missing;  // uniq_idx keeps the slot's scratch default
      }
    }
    idx[o] = pull_row[(size_t)slot];
    inverse[o] = (int)slot;
    key_mask[o] = 1.0f;
  }
  *n_uniq_out = n_uniq;
  return n_missing;
}

}  // extern "C"

extern "C" {

// Sharded-path resolve: dedup occurrences (first-seen slot order) and look
// every unique key up in the census index — WITHOUT the single-chip plan's
// scratch/dead semantics (the sharded planner derives owner shards and
// within-shard rows itself from the census position).
//
// Outputs (preallocated, length K):
//   inverse[occ]   = slot of the occurrence (occ < n_real; tail untouched)
//   uniq_key[j]    = the slot's key                     (j < n_uniq)
//   uniq_pos[j]    = census position or -1 when absent  (j < n_uniq)
// Returns n_uniq (or -1 on bad arguments).
long long pbx_census_lookup_unique(
    void* handle,
    const unsigned long long* keys, long long K, long long n_real,
    int* inverse, unsigned long long* uniq_key, long long* uniq_pos) {
  if (n_real < 0 || n_real > K) return -1;
  const CensusIndex* ix = static_cast<CensusIndex*>(handle);
  if (n_real == 0) return 0;

  unsigned long long lmask =
      pow2_at_least((unsigned long long)(2 * n_real)) - 1;
  std::vector<unsigned int> lslot((size_t)lmask + 1, kEmpty);

  long long n_uniq = 0;
  for (long long o = 0; o < n_real; ++o) {
    const unsigned long long k = keys[o];
    unsigned long long h = splitmix64(k) & lmask;
    long long slot = -1;
    while (true) {
      unsigned int s = lslot[h];
      if (s == kEmpty) break;
      if (uniq_key[s] == k) {
        slot = (long long)s;
        break;
      }
      h = (h + 1) & lmask;
    }
    if (slot < 0) {
      slot = n_uniq++;
      lslot[h] = (unsigned int)slot;
      uniq_key[(size_t)slot] = k;
      long long row = -1;
      unsigned long long ch = splitmix64(k) & ix->mask;
      while (true) {
        unsigned int c = ix->slot[ch];
        if (c == kEmpty) break;
        if (ix->keys[c] == k) {
          row = (long long)c;
          break;
        }
        ch = (ch + 1) & ix->mask;
      }
      uniq_pos[slot] = row;
    }
    inverse[o] = (int)slot;
  }
  return n_uniq;
}

}  // extern "C"

extern "C" {

// Row dedup for the sharded serve side: first-seen-order unique of an
// int32 row-id buffer (no census involved).  Replaces per-shard
// np.unique(serve_rows, return_inverse=True) on the plan_group hot path.
//
// Outputs (preallocated, length n):
//   inverse[i] = slot of rows[i]
//   uniq[j]    = the slot's row id (j < n_uniq)
// Returns n_uniq.
long long pbx_dedup_rows(const int* rows, long long n,
                         int* inverse, int* uniq) {
  if (n <= 0) return 0;
  unsigned long long lmask = pow2_at_least((unsigned long long)(2 * n)) - 1;
  std::vector<unsigned int> lslot((size_t)lmask + 1, kEmpty);
  long long n_uniq = 0;
  for (long long i = 0; i < n; ++i) {
    const int r = rows[i];
    unsigned long long h =
        splitmix64((unsigned long long)(unsigned int)r) & lmask;
    long long slot = -1;
    while (true) {
      unsigned int s = lslot[h];
      if (s == kEmpty) break;
      if (uniq[s] == r) {
        slot = (long long)s;
        break;
      }
      h = (h + 1) & lmask;
    }
    if (slot < 0) {
      slot = n_uniq++;
      lslot[h] = (unsigned int)slot;
      uniq[slot] = r;
    }
    inverse[i] = (int)slot;
  }
  return n_uniq;
}

}  // extern "C"

// --------------------------------------------------------------------- //
// The row cache's directory at a pass boundary (HbmCache.lookup / touch).
//
// lookup: a sorted unique census against the directory's sorted view
// (keys ascending, a slot beside each).  numpy's form is one binary search
// a census key, ~25 dependent cache misses each through an array far
// beyond the caches.  Both arrays ascend, so this is a merge: the census
// walks forward through ``sample`` (every kDirStride-th directory key, a
// sequential read), which names the one block of kDirStride keys that can
// hold the key; the block's key and slot lines are prefetched kDirAhead
// census keys before they are read, so the misses overlap instead of
// queueing.  A long census is cut into a few ranges, one a thread: each
// finds its start with one binary search of the sample, writes hit_mask in
// place and its hits at its range's offset, and the hits are closed up
// after the join.  Output equals numpy's to the element.
//
// touch: the two indexed writes over the hits' slots (distinct, so the
// ranges of two threads never meet), prefetched the same way.
// --------------------------------------------------------------------- //

namespace {

constexpr long long kDirStride = 16;  // _native.DIRECTORY_STRIDE
constexpr int kDirAhead = 32;         // a power of two
constexpr long long kPerThread = 32768;  // census keys that pay for a thread
constexpr long long kMaxThreads = 4;

int threads_for(long long n) {
  const long long cores = (long long)std::thread::hardware_concurrency();
  const long long t = std::min({kMaxThreads, n / kPerThread, cores});
  return (int)std::max(1LL, t);
}

// f(t, a, b) over T contiguous ranges of [0, n), range 0 on this thread
template <class F>
void run_ranges(int T, long long n, F f) {
  std::vector<std::thread> workers;
  workers.reserve((size_t)T);
  for (int t = 1; t < T; ++t) {
    const long long a = n * t / T, b = n * (t + 1) / T;
    try {
      workers.emplace_back(f, t, a, b);
    } catch (const std::system_error&) {  // no thread to be had: run it here
      f(t, a, b);
    }
  }
  f(0, 0, n / T);
  for (auto& w : workers) w.join();
}

// census [a, b): hit[] in place, the hits' positions and slots from index a
long long lookup_range(const unsigned long long* sk, const int* ss,
                       long long N, const unsigned long long* sample,
                       long long M, const unsigned long long* pk,
                       long long a, long long b, unsigned char* hit,
                       int* hit_pos, int* hit_slots) {
  // j: the last sample <= the key being placed, -1 below the first (and
  // for every key of an empty directory)
  long long j = (std::upper_bound(sample, sample + M, pk[a]) - sample) - 1;
  auto place = [&](unsigned long long k) {
    while (j + 1 < M && sample[j + 1] <= k) ++j;
    if (j >= 0) {
      const long long base = j * kDirStride;
      __builtin_prefetch(sk + base);
      __builtin_prefetch(sk + base + kDirStride / 2);
      __builtin_prefetch(sk + base + kDirStride - 1);
      __builtin_prefetch(ss + base);
      __builtin_prefetch(ss + base + kDirStride - 1);
    }
    return j;
  };
  long long block[kDirAhead];  // ring: the block of census key i + r
  for (long long t = 0; t < kDirAhead && a + t < b; ++t)
    block[t] = place(pk[a + t]);
  long long h = a;
  for (long long i = a; i < b; ++i) {
    const unsigned long long k = pk[i];
    const long long r = (i - a) & (kDirAhead - 1);
    const long long jj = block[r];
    if (i + kDirAhead < b) block[r] = place(pk[i + kDirAhead]);
    bool found = false;
    long long pos = 0;
    if (jj >= 0) {
      const long long base = jj * kDirStride;
      const long long end = std::min(N, base + kDirStride);
      pos = base;
      for (long long q = base; q < end; ++q) pos += sk[q] < k;
      found = pos < end && sk[pos] == k;
    }
    hit[i] = found;
    if (found) {
      hit_pos[h] = (int)i;
      hit_slots[h] = ss[pos];
      ++h;
    }
  }
  return h - a;
}

}  // namespace

extern "C" {

// Outputs (preallocated, length n): hit_mask[i] for every census key;
// hit_pos / hit_slots filled for the first H entries.  Returns H, or -1
// when ``sample`` is not every kDirStride-th of the n_dir keys.
long long pbx_cache_lookup(
    const unsigned long long* sorted_keys, const int* sorted_slots,
    long long n_dir, const unsigned long long* sample, long long n_sample,
    const unsigned long long* census, long long n,
    unsigned char* hit_mask, int* hit_pos, int* hit_slots) {
  if (n < 0 || n_dir < 0 ||
      n_sample != (n_dir + kDirStride - 1) / kDirStride)
    return -1;
  if (n == 0) return 0;
  const int T = threads_for(n);
  std::vector<long long> hits((size_t)T, 0);
  run_ranges(T, n, [&](int t, long long a, long long b) {
    hits[(size_t)t] = lookup_range(sorted_keys, sorted_slots, n_dir, sample,
                                   n_sample, census, a, b, hit_mask, hit_pos,
                                   hit_slots);
  });
  long long h = hits[0];
  for (int t = 1; t < T; ++t) {  // close the hits up: range t began at a
    const long long a = n * t / T, c = hits[(size_t)t];
    if (a != h) {
      std::memmove(hit_pos + h, hit_pos + a, (size_t)c * sizeof(int));
      std::memmove(hit_slots + h, hit_slots + a, (size_t)c * sizeof(int));
    }
    h += c;
  }
  return h;
}

// freq[slots] += unit; last_seen[slots] = tick, over distinct slots.
void pbx_cache_touch(double* freq, long long* last_seen, const int* slots,
                     long long n, double unit, long long tick) {
  if (n <= 0) return;
  run_ranges(threads_for(n), n, [=](int, long long a, long long b) {
    for (long long i = a; i < b; ++i) {
      if (i + kDirAhead < b) {
        __builtin_prefetch(freq + slots[i + kDirAhead], 1);
        __builtin_prefetch(last_seen + slots[i + kDirAhead], 1);
      }
      freq[slots[i]] += unit;
      last_seen[slots[i]] = tick;
    }
  });
}

}  // extern "C"
