// sketch_update — one batch of dense group ids folded into the state
// observatory's two sketches (obs/statewatch.py StateWatch.update): the
// Space-Saving slots (keys / counts / errs) and the HyperLogLog registers,
// in place, leaving exactly the state the NumPy kernels of ops/sketches.py
// leave (_aggregate_gids → ss_admit, Hll.update); tests/
// test_statewatch_native.py holds the two equal.
//
// Three steps a call:
//   1. one pass over the rows: insert-or-increment of the id in an
//      open-addressed scratch table of (id, count) entries.  A slot is
//      listed when it is first written, and that is also where the id is
//      hashed (splitmix64, ops/sketches.py _mix64) and its HLL register
//      raised: the registers are a max, so once an id is as good as once a
//      row.
//   2. hits: each of the K tracked keys is looked up in that table — K
//      probes, not one a distinct id — its count (put in row units when the
//      batch was sampled) added to its slot, the entry marked taken.  Then
//      one pass over the listed slots: each entry is read and zeroed (the
//      table is all zeros again when the call returns) and, unless taken,
//      held against the min(K, misses) best newcomers so far.
//   3. admission as ss_admit does it: newcomers by descending count, ties
//      by ascending id, against victims by ascending (count, slot), each
//      pair under the guard base <= base[0] + count.
//
// Ids are dense and non-negative (the interners' contract); both int32 and
// int64 arrive.  A block of rows has its slots prefetched before it is
// resolved, as native/interner.cpp does.  C ABI for ctypes; the caller owns
// every array, the scratch included (one a watch: two watches may run at
// once).

#include <algorithm>
#include <cstdint>
#include <limits>

// native_test.cpp builds every component in one translation unit: the
// names below are this file's own
namespace {
namespace sketch {

constexpr int64_t kBlock = 16;  // rows whose slots are prefetched ahead
constexpr int64_t kAhead = 8;   // listed slots prefetched ahead

inline uint64_t mix64(uint64_t x) {
  uint64_t z = x + 0x9E3779B97F4A7C15ull;
  z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ull;
  z = (z ^ (z >> 27)) * 0x94D049BB133111EBull;
  return z ^ (z >> 31);
}

inline int log2_at_least(int64_t v) {
  int b = 4;
  while (((int64_t)1 << b) < v) ++b;
  return b;
}

// an int32 id keeps its count in 32 bits too (a count never passes the row
// cap): eight bytes an entry, the whole table 256 KB at a 16,384-row cap
template <typename IdT>
struct Entry {
  IdT id;
  IdT count;  // 0 = empty; negative = a tracked key's, already added
};

// a newcomer (count, id) or a victim (count, slot)
struct Pair {
  int64_t count;
  int64_t at;
};

// orders, as functors so that the sorts inline them.
// Newcomers: larger count first, then smaller id
struct NewcomerFirst {
  bool operator()(const Pair& a, const Pair& b) const {
    return a.count > b.count || (a.count == b.count && a.at < b.at);
  }
};

// victims: smaller count first, then smaller slot
struct VictimFirst {
  bool operator()(const Pair& a, const Pair& b) const {
    return a.count < b.count || (a.count == b.count && a.at < b.at);
  }
};

// round half to even, as np.rint, for 0 <= x < 2^51: adding 2^52 leaves
// no fraction bits, so the sum is rounded in the current (nearest-even)
// mode — without libm's call a distinct id
inline int64_t rint_small(double x) {
  constexpr double k2p52 = 4503599627370496.0;
  volatile double sum = x + k2p52;  // not to be folded away
  return (int64_t)(sum - k2p52);
}

inline int64_t align64(int64_t v) { return (v + 63) & ~(int64_t)63; }

// the scratch, carved: [table | listed slots | newcomers | victims]; the
// table is sized for the wider entry
struct Scratch {
  uint8_t* table = nullptr;
  int32_t* listed = nullptr;
  Pair* newcomers = nullptr;
  Pair* victims = nullptr;
  int64_t bytes;

  // base == nullptr: the size alone
  Scratch(uint8_t* base, int64_t cap, int64_t K) {
    const int64_t at_listed = align64(
        ((int64_t)1 << log2_at_least(2 * cap)) *
        (int64_t)sizeof(Entry<int64_t>));
    const int64_t at_newcomers =
        at_listed + align64(cap * (int64_t)sizeof(int32_t));
    const int64_t at_victims =
        at_newcomers + align64(2 * K * (int64_t)sizeof(Pair));
    bytes = at_victims + align64(K * (int64_t)sizeof(Pair));
    if (!base) return;
    table = base;
    listed = reinterpret_cast<int32_t*>(base + at_listed);
    newcomers = reinterpret_cast<Pair*>(base + at_newcomers);
    victims = reinterpret_cast<Pair*>(base + at_victims);
  }
};

// an id's home slot in a table of 2^bits entries: the top bits of one
// multiply, which spreads the interners' consecutive ids evenly
inline uint64_t home(int64_t id, int bits) {
  return ((uint64_t)id * 0x9E3779B97F4A7C15ull) >> (64 - bits);
}

template <typename IdT>
int64_t fold_rows(const IdT* ids, int64_t m, Entry<IdT>* table, int bits,
                  int32_t* listed, uint8_t* regs, int32_t p) {
  const uint64_t mask = ((uint64_t)1 << bits) - 1;
  const int width = 64 - p;
  const uint64_t low = ((uint64_t)1 << width) - 1;
  int64_t n_listed = 0;
  uint64_t at[kBlock];
  for (int64_t base = 0; base < m; base += kBlock) {
    const int64_t nb = std::min(kBlock, m - base);
    for (int64_t j = 0; j < nb; ++j) {
      at[j] = home((int64_t)ids[base + j], bits);
      __builtin_prefetch(&table[at[j]], 1);
    }
    for (int64_t j = 0; j < nb; ++j) {
      const IdT id = ids[base + j];
      uint64_t s = at[j];
      for (;;) {
        Entry<IdT>& e = table[s];
        if (e.count == 0) {
          e.id = id;
          e.count = 1;
          listed[n_listed++] = (int32_t)s;
          // register = the top p bits; rank = leading zeros of the other
          // 64 - p, plus one (all zero: 64 - p + 1)
          const uint64_t h = mix64((uint64_t)(int64_t)id);
          const uint64_t w = h & low;
          const int rho = w ? __builtin_clzll(w) - p + 1 : width + 1;
          uint8_t& r = regs[h >> width];
          if (r < rho) r = (uint8_t)rho;
          break;
        }
        if (e.id == id) {
          ++e.count;
          break;
        }
        s = (s + 1) & mask;
      }
    }
  }
  return n_listed;
}

template <typename IdT>
void admit(Entry<IdT>* table, int bits, int64_t n_listed, double scale,
           bool sampled, int64_t* keys, int64_t* counts, int64_t* errs,
           int64_t K, Scratch& sc) {
  const uint64_t mask = ((uint64_t)1 << bits) - 1;
  auto in_rows = [&](int64_t c) {
    return sampled ? rint_small((double)c * scale) : c;
  };
  // hits.  An empty slot's -1 is never looked up, nor a key no IdT holds;
  // of two slots with one key the lower takes the count (searchsorted
  // over a stable argsort finds that one) and the other finds it taken
  auto sought = [&](int64_t key) {
    return key >= 0 && key <= (int64_t)std::numeric_limits<IdT>::max();
  };
  for (int64_t k = 0; k < K; ++k)
    if (sought(keys[k])) __builtin_prefetch(&table[home(keys[k], bits)], 1);
  for (int64_t k = 0; k < K; ++k) {
    if (!sought(keys[k])) continue;
    const IdT key = (IdT)keys[k];
    for (uint64_t s = home(keys[k], bits); table[s].count != 0;
         s = (s + 1) & mask) {
      Entry<IdT>& e = table[s];
      if (e.id != key) continue;
      if (e.count > 0) {
        counts[k] += in_rows((int64_t)e.count);
        e.count = (IdT)-e.count;
      }
      break;
    }
  }
  // misses: the best K of them, kept in 2K places — a miss that beats the
  // bar (the K-th best when the places last filled up) is appended, and
  // full places are cut back to their best K, which raises the bar.  With
  // thousands of misses a batch nearly all fail the one compare.
  Pair* best = sc.newcomers;
  int64_t n_best = 0;
  bool barred = false;
  Pair bar{0, 0};
  for (int64_t i = 0; i < n_listed; ++i) {
    if (i + kAhead < n_listed)
      __builtin_prefetch(&table[sc.listed[i + kAhead]], 1);
    Entry<IdT>& e = table[sc.listed[i]];
    const Pair x{(int64_t)e.count, (int64_t)e.id};
    e.id = 0;
    e.count = 0;
    if (x.count < 0) continue;
    const Pair y{in_rows(x.count), x.at};
    if (barred && !NewcomerFirst()(y, bar)) continue;
    best[n_best++] = y;
    if (n_best == 2 * K) {
      std::nth_element(best, best + K - 1, best + n_best, NewcomerFirst());
      bar = best[K - 1];
      barred = true;
      n_best = K;
    }
  }
  if (n_best == 0) return;
  const int64_t n_new = std::min(n_best, K);
  if (n_best > K)
    std::nth_element(best, best + K, best + n_best, NewcomerFirst());
  std::sort(best, best + n_new, NewcomerFirst());
  // victims are chosen after the hits have landed
  for (int64_t k = 0; k < K; ++k) sc.victims[k] = Pair{counts[k], k};
  std::sort(sc.victims, sc.victims + K, VictimFirst());
  const int64_t floor0 = sc.victims[0].count;
  for (int64_t i = 0; i < n_new; ++i) {
    const Pair& v = sc.victims[i];
    const Pair& x = best[i];
    if (v.count > floor0 + x.count) continue;
    keys[v.at] = x.at;
    errs[v.at] = v.count;
    counts[v.at] = v.count + x.count;
  }
}

template <typename IdT>
int64_t update(const IdT* ids, int64_t m, int64_t rows, uint8_t* regs,
               int32_t p, int64_t* keys, int64_t* counts, int64_t* errs,
               int64_t K, Scratch& sc) {
  Entry<IdT>* table = reinterpret_cast<Entry<IdT>*>(sc.table);
  // as small a table as keeps it half empty: a short batch stays in L1
  const int bits = log2_at_least(2 * m);
  const int64_t n_listed = fold_rows(ids, m, table, bits, sc.listed, regs, p);
  admit(table, bits, n_listed, (double)rows / (double)m, rows != m, keys,
        counts, errs, K, sc);
  return n_listed;
}

}  // namespace sketch
}  // namespace

extern "C" {

// bytes of scratch a watch with K slots needs for batches of up to cap
// rows; the caller hands them over zeroed, once
int64_t sketch_scratch_bytes(int64_t cap, int32_t K) {
  if (cap < 1 || K < 1) return -1;
  return sketch::Scratch(nullptr, cap, K).bytes;
}

// Fold ids[0:m] — id_bytes 4 (int32) or 8 (int64), contiguous — standing
// for a batch of `rows` rows (rows > m: a block sample, counts rescaled by
// rows / m) into regs (2^p uint8 HLL registers) and the K slots of keys /
// counts / errs (int64 each).  `scratch` is sketch_scratch_bytes(cap, K)
// bytes, zeroed before the first call and left as found by every call.
// Returns the number of distinct ids, or -1 for arguments it will not take
// (nothing is touched then).
int64_t sketch_update(const void* ids, int32_t id_bytes, int64_t m,
                      int64_t rows, uint8_t* regs, int32_t p, int64_t* keys,
                      int64_t* counts, int64_t* errs, int32_t K,
                      uint8_t* scratch, int64_t cap) {
  if (m < 0 || m > cap || rows < m || K < 1 || p < 4 || p > 16) return -1;
  if (id_bytes != 4 && id_bytes != 8) return -1;
  if (m == 0) return 0;
  sketch::Scratch sc(scratch, cap, K);
  if (id_bytes == 4)
    return sketch::update(static_cast<const int32_t*>(ids), m, rows, regs, p,
                          keys, counts, errs, K, sc);
  return sketch::update(static_cast<const int64_t*>(ids), m, rows, regs, p,
                        keys, counts, errs, K, sc);
}

}  // extern "C"
