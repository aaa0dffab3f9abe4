// interner — string interning: key bytes → dense int32 ids, in first-seen
// order.
//
// Native hot path for group-key interning (the GroupValues-equivalent; see
// ops/interner.py).  The hot lane is intern_offsets: a StringColumn's own
// offsets + UTF-8 bytes, one foreign call per batch and no Python object
// per key.  intern_many takes fixed-width zero-padded rows (the numpy 'S'
// layout) and intern_pyobjects an object array (checkpoint restore,
// non-columnar sources).  The three lanes share one table, so a column
// may mix them.
//
// The table is open addressing (linear probing) over 32-byte slots, two
// to a cache line.  A slot holds everything that decides a hit for a key
// of up to 23 bytes — a 32-bit tag from the hash bits the slot index
// never uses, the id, and the key itself as three zero-padded
// little-endian words with its length in the top byte — so a lookup of a
// known short key touches one cache line.  A longer key keeps
// {length, hash, marker} in the slot and its bytes in the arena; it pays
// the arena compare only when all three match.  Either way a hit is
// decided by comparing every byte of the key: the tag only rejects.
//
// Rows are processed in blocks: hash a block of keys and prefetch their
// slots, then resolve the block strictly in row order, so a batch's
// cache misses overlap while a first-seen key still takes the next
// dense id.  arena / offsets / arena_w are the id-ordered store the
// reverse lookups read; they are written only when a key is new.
// C ABI for ctypes.

#ifdef INTERN_HAVE_PYTHON
// must precede the standard headers per CPython's include rules
#include <Python.h>
#endif

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <vector>

namespace {

constexpr uint32_t kInlineMax = 23;            // longest key a slot holds
constexpr uint64_t kOverflow = 0xFFull << 56;  // w[2] of an arena-held key
constexpr uint64_t kBlock = 16;                // rows hashed ahead

// NULL keys get a dedicated 1-byte key (0xFF — impossible in valid
// UTF-8), so null groups never collide with the string 'None', the
// reverse lookup can reconstruct real None, and the offsets lane and the
// PyObject lane agree on a column that mixes them
const uint8_t kNullKey[1] = {0xFF};

struct alignas(32) Slot {
  uint32_t id1;   // id + 1; 0 = empty
  uint32_t tag;   // hash >> 32
  uint64_t w[3];  // inline: key bytes, length in the top byte of w[2];
                  // overflow: {length, hash, kOverflow}
};

// one row's key, hashed and laid out as a slot would hold it
struct Key {
  uint64_t h;
  uint64_t w[3];
  const uint8_t* p;
};

inline uint64_t load8(const uint8_t* p) {
  uint64_t v;
  memcpy(&v, p, 8);
  return v;
}

// the n (1..7) bytes at p as a little-endian word, reading nothing
// outside them
inline uint64_t load_tail(const uint8_t* p, uint32_t n) {
  if (n >= 4) {
    uint32_t a, b;
    memcpy(&a, p, 4);
    memcpy(&b, p + n - 4, 4);
    return a | ((uint64_t)b << (8 * (n - 4)));
  }
  if (n >= 2) {
    uint16_t a, b;
    memcpy(&a, p, 2);
    memcpy(&b, p + n - 2, 2);
    return a | ((uint64_t)b << (8 * (n - 2)));
  }
  return p[0];
}

// the low n (0..8) bytes of v
inline uint64_t low_bytes(uint64_t v, uint32_t n) {
  return n >= 8 ? v : v & ((1ull << (8 * n)) - 1);
}

// word k of a len-byte key at p, zero-padded, reading nothing beyond len
inline uint64_t word_at(const uint8_t* p, uint32_t len, uint32_t k) {
  if (len >= 8 * k + 8) return load8(p + 8 * k);
  return len > 8 * k ? load_tail(p + 8 * k, len - 8 * k) : 0;
}

inline uint64_t mix(uint64_t h, uint64_t k) {
  h = (h ^ k) * 0x9E3779B97F4A7C15ull;
  return h ^ (h >> 29);
}

// a multiply only carries a difference upwards: without this, keys that
// differ in their last bytes alone would share the low bits the slot
// index is cut from
inline uint64_t finish(uint64_t h) {
  h = (h ^ (h >> 32)) * 0xD6E8FEB86659FD93ull;
  return h ^ (h >> 32);
}

constexpr uint64_t kSeed = 1469598103934665603ull;

inline uint64_t hash_words(const uint64_t w[3]) {
  return finish(mix(mix(mix(kSeed, w[0]), w[1]), w[2]));
}

// `wide`: 24 bytes are readable at p (the caller's buffer reaches that
// far), so the words load whole and are masked to the key's length
inline void prepare(Key& k, const uint8_t* p, uint32_t len, bool wide) {
  k.p = p;
  if (len > kInlineMax) {
    uint64_t h = kSeed ^ len;
    const uint8_t* q = p;
    uint32_t r = len;
    for (; r >= 8; q += 8, r -= 8) h = mix(h, load8(q));
    if (r) h = mix(h, load_tail(q, r));
    k.h = finish(h);
    k.w[0] = len;
    k.w[1] = k.h;
    k.w[2] = kOverflow;
    return;
  }
  if (wide) {
    k.w[0] = low_bytes(load8(p), len);
    k.w[1] = low_bytes(load8(p + 8), len > 8 ? len - 8 : 0);
    k.w[2] = low_bytes(load8(p + 16), len > 16 ? len - 16 : 0);
  } else {
    k.w[0] = word_at(p, len, 0);
    k.w[1] = word_at(p, len, 1);
    k.w[2] = word_at(p, len, 2);
  }
  k.w[2] |= (uint64_t)len << 56;
  k.h = hash_words(k.w);
}

struct Interner {
  std::vector<Slot> slots;
  uint64_t mask = 0;
  uint64_t count = 0;
  // id-ordered key store, appended to when a key is new
  std::vector<uint8_t> arena;     // concatenated key bytes
  std::vector<uint64_t> offsets;  // arena offset per id
  std::vector<uint32_t> arena_w;  // key length per id
  // tallies (intern_stats), added once per call from the lanes' locals;
  // like count they may be read from another thread mid-call: aligned
  // words, monotone, at worst one call behind
  uint64_t rows = 0;
  uint64_t extra_probes = 0;   // slots visited beyond a row's first
  uint64_t overflow_rows = 0;  // rows whose key is longer than kInlineMax
#ifdef INTERN_HAVE_PYTHON
  // pointer-identity lookaside: PyObject* → id.  Group keys repeat the
  // SAME string objects heavily (dictionary-style sources, reused pools),
  // and str is immutable — so a pointer hit skips the UTF-8 fetch, the
  // content hash and the probe entirely.  Cached objects are
  // INCREF-pinned so the pointer can never be reused for a different
  // string.
  std::vector<uint64_t> pkeys;  // ptr, 0 = empty
  std::vector<uint32_t> pids;   // id + 1
  uint64_t pmask = 0;
  uint64_t pcount = 0;
#endif

  // double the table, rehashing from the slots themselves
  void grow() {
    size_t ncap = slots.empty() ? 1024 : slots.size() * 2;
    std::vector<Slot> ns(ncap);
    uint64_t nmask = ncap - 1;
    for (const Slot& s : slots) {
      if (!s.id1) continue;
      uint64_t i = (s.w[2] == kOverflow ? s.w[1] : hash_words(s.w)) & nmask;
      while (ns[i].id1) i = (i + 1) & nmask;
      ns[i] = s;
    }
    slots.swap(ns);
    mask = nmask;
  }

  // first-seen key: the next dense id, into the empty slot the probe found
  int32_t insert(const Key& k, uint64_t i) {
    if ((count + 1) * 4 >= slots.size() * 3) {
      grow();
      i = k.h & mask;
      while (slots[i].id1) i = (i + 1) & mask;
    }
    uint32_t len = (uint32_t)(k.w[2] == kOverflow ? k.w[0] : k.w[2] >> 56);
    offsets.push_back(arena.size());
    arena.insert(arena.end(), k.p, k.p + len);
    arena_w.push_back(len);
    slots[i] = Slot{(uint32_t)(count + 1), (uint32_t)(k.h >> 32),
                    {k.w[0], k.w[1], k.w[2]}};
    return (int32_t)count++;
  }

  // prepared key → dense id; `extra` counts the slots visited beyond the
  // first
  int32_t resolve(const Key& k, uint64_t& extra) {
    const uint32_t tag = (uint32_t)(k.h >> 32);
    for (uint64_t i = k.h & mask;; i = (i + 1) & mask, extra++) {
      const Slot& s = slots[i];
      if (!s.id1) return insert(k, i);
      if (s.tag == tag && s.w[0] == k.w[0] && s.w[1] == k.w[1] &&
          s.w[2] == k.w[2] &&
          (k.w[2] != kOverflow ||
           memcmp(arena.data() + offsets[s.id1 - 1], k.p, (size_t)k.w[0]) ==
               0))
        return (int32_t)(s.id1 - 1);
    }
  }

  // n rows in blocks: hash a block and prefetch its slots, then resolve
  // it in row order.  key_at(i, k) prepares row i's key.
  template <class KeyAt>
  void intern_rows(uint64_t n, int32_t* out_ids, KeyAt key_at) {
    Key keys[kBlock];
    uint64_t extra = 0, overflow = 0;
    for (uint64_t base = 0; base < n; base += kBlock) {
      const uint64_t m = n - base < kBlock ? n - base : kBlock;
      for (uint64_t j = 0; j < m; j++) {
        key_at(base + j, keys[j]);
        overflow += keys[j].w[2] == kOverflow;
        __builtin_prefetch(&slots[keys[j].h & mask]);
      }
      for (uint64_t j = 0; j < m; j++)
        out_ids[base + j] = resolve(keys[j], extra);
    }
    rows += n;
    extra_probes += extra;
    overflow_rows += overflow;
  }
};

// trailing NULs strip in every lane: fixed-width storage cannot hold
// them, so keys that differ only there are one key
inline uint32_t strip_nuls(const uint8_t* key, uint32_t len) {
  while (len > 0 && key[len - 1] == 0) len--;
  return len;
}

}  // namespace

extern "C" {

void* intern_create() {
  Interner* in = new Interner();
  in->grow();
  return in;
}

void intern_destroy(void* h) { delete static_cast<Interner*>(h); }

uint64_t intern_count(void* h) { return static_cast<Interner*>(h)->count; }

// out[3] = {rows interned, slots visited beyond a row's first, rows whose
// key was longer than the inline width}, since intern_create
void intern_stats(void* h, uint64_t* out) {
  Interner* in = static_cast<Interner*>(h);
  out[0] = in->rows;
  out[1] = in->extra_probes;
  out[2] = in->overflow_rows;
}

// Intern n fixed-width keys (width w, buffer n*w bytes) → out_ids[n].
// Shorter strings are zero-padded to w (numpy 'S' does this); the padding
// strips, so the same key under another width is the same key.
void intern_many(void* h, const uint8_t* data, uint64_t n, uint32_t w,
                 int32_t* out_ids) {
  const uint64_t end = n * w;
  static_cast<Interner*>(h)->intern_rows(
      n, out_ids, [=](uint64_t i, Key& k) {
        const uint8_t* key = data + i * w;
        prepare(k, key, strip_nuls(key, w), i * w + 24 <= end);
      });
}

// Intern n variable-length keys given as one contiguous UTF-8 buffer plus
// u64 offsets (n+1 entries) — the Arrow string-column layout, so a
// StringColumn interns straight off its own buffers with NO Python str
// materialization.  valid may be NULL (all valid); invalid slots intern
// the NULL key.
void intern_offsets(void* h, const uint8_t* bytes, const uint64_t* offsets,
                    const uint8_t* valid, uint64_t n, int32_t* out_ids) {
  // offsets[n] ends the last key, so the buffer reaches at least there
  const uint64_t end = n ? offsets[n] : 0;
  static_cast<Interner*>(h)->intern_rows(
      n, out_ids, [=](uint64_t i, Key& k) {
        if (valid != nullptr && !valid[i]) {
          prepare(k, kNullKey, 1, false);
          return;
        }
        const uint64_t off = offsets[i];
        const uint8_t* key = bytes + off;
        uint32_t len = strip_nuls(key, (uint32_t)(offsets[i + 1] - off));
        prepare(k, key, len, off + 24 <= end);
      });
}

#ifdef INTERN_HAVE_PYTHON
// Direct PyObject path: hash each numpy-object-array slot's string content
// (CPython-cached UTF-8) with NO fixed-width conversion and NO new Python
// objects.  Must be called through ctypes.PyDLL (the GIL stays held).
namespace {

constexpr uint64_t kPtrCacheCap = 1u << 20;  // bound pinned objects

inline void pcache_grow(Interner* c) {
  size_t ncap = c->pkeys.empty() ? 4096 : c->pkeys.size() * 2;
  std::vector<uint64_t> nk(ncap, 0);
  std::vector<uint32_t> ni(ncap, 0);
  uint64_t nmask = ncap - 1;
  for (size_t i = 0; i < c->pkeys.size(); i++) {
    if (!c->pkeys[i]) continue;
    uint64_t slot = (c->pkeys[i] * 0x9E3779B97F4A7C15ull >> 17) & nmask;
    while (nk[slot]) slot = (slot + 1) & nmask;
    nk[slot] = c->pkeys[i];
    ni[slot] = c->pids[i];
  }
  c->pkeys.swap(nk);
  c->pids.swap(ni);
  c->pmask = nmask;
}

}  // namespace

int intern_pyobjects(void* h, PyObject** objs, uint64_t n, int32_t* out_ids) {
  Interner* c = static_cast<Interner*>(h);
  if (c->pkeys.empty()) pcache_grow(c);
  Key k;
  uint64_t extra = 0, overflow = 0;
  // one row through the shared table; the object's buffer ends with the
  // key, so its words load byte-exact
  auto intern_one = [&](const uint8_t* key, uint32_t len) {
    prepare(k, key, len, false);
    overflow += k.w[2] == kOverflow;
    return c->resolve(k, extra);
  };
  int rc = 0;
  uint64_t i = 0;
  for (; i < n; i++) {
    PyObject* o = objs[i];
    // pointer lookaside first
    uint64_t ptr = (uint64_t)(uintptr_t)o;
    uint64_t slot = (ptr * 0x9E3779B97F4A7C15ull >> 17) & c->pmask;
    bool hit = false;
    while (c->pkeys[slot]) {
      if (c->pkeys[slot] == ptr) {
        out_ids[i] = (int32_t)(c->pids[slot] - 1);
        hit = true;
        break;
      }
      slot = (slot + 1) & c->pmask;
    }
    if (hit) continue;
    Py_ssize_t len = 0;
    const char* s = nullptr;
    PyObject* tmp = nullptr;
    if (o == Py_None) {
      out_ids[i] = intern_one(kNullKey, 1);
      continue;
    }
    if (PyUnicode_Check(o)) {
      s = PyUnicode_AsUTF8AndSize(o, &len);
      if (s == nullptr) {
        // lone surrogates etc.: match the engine-wide errors='replace'
        // policy instead of aborting the stream
        PyErr_Clear();
        tmp = PyUnicode_AsEncodedString(o, "utf-8", "replace");
        if (tmp) {
          char* bs = nullptr;
          if (PyBytes_AsStringAndSize(tmp, &bs, &len) == 0) s = bs;
        }
      }
    } else {
      // non-string key (numbers in an object column): match the
      // fallback path's str() normalization
      PyObject* as_str = PyObject_Str(o);
      if (as_str) {
        s = PyUnicode_AsUTF8AndSize(as_str, &len);
        tmp = as_str;
      }
    }
    if (s == nullptr) {
      Py_XDECREF(tmp);
      rc = -1;  // propagate: caller raises the pending Python error
      break;
    }
    const uint8_t* key = (const uint8_t*)s;
    int32_t id = intern_one(key, strip_nuls(key, (uint32_t)len));
    out_ids[i] = id;
    Py_XDECREF(tmp);
    // Cache only plain strs that show evidence of POOLING: a per-row str
    // freshly minted by a decoder is held by nothing but the batch array
    // (refcount 1 + the borrowed array slot), so pinning it would retain
    // dead objects forever for zero hits.  Reused/pooled keys (the case
    // the cache exists for) carry extra references.
    if (tmp == nullptr && Py_REFCNT(o) >= 2 && c->pcount < kPtrCacheCap) {
      if ((c->pcount + 1) * 4 >= c->pkeys.size() * 3) pcache_grow(c);
      uint64_t s2 = (ptr * 0x9E3779B97F4A7C15ull >> 17) & c->pmask;
      while (c->pkeys[s2]) s2 = (s2 + 1) & c->pmask;
      c->pkeys[s2] = ptr;
      c->pids[s2] = (uint32_t)(id + 1);
      c->pcount++;
      Py_INCREF(o);
    }
  }
  c->rows += i;
  c->extra_probes += extra;
  c->overflow_rows += overflow;
  return rc;
}

// release the pointer cache's pins — MUST be called through ctypes.PyDLL
// (needs the GIL) before intern_destroy
void intern_py_release(void* h) {
  Interner* c = static_cast<Interner*>(h);
  for (size_t i = 0; i < c->pkeys.size(); i++)
    if (c->pkeys[i]) Py_DECREF((PyObject*)(uintptr_t)c->pkeys[i]);
  c->pkeys.clear();
  c->pids.clear();
  c->pmask = 0;
  c->pcount = 0;
}
#endif  // INTERN_HAVE_PYTHON

// bulk reverse lookup: copy the arena slice and offsets for ids in
// [start, end) — one call per batch instead of one per key
int64_t intern_keys_range(void* h, uint64_t start, uint64_t end,
                          uint8_t** bytes_out, uint64_t** offsets_out) {
  Interner* c = static_cast<Interner*>(h);
  if (start > end || end > c->count) return -1;
  uint64_t n = end - start;
  uint64_t base = start >= c->offsets.size() ? c->arena.size()
                                             : c->offsets[start];
  uint64_t total =
      (end == c->count ? c->arena.size() : c->offsets[end]) - base;
  uint8_t* bytes = (uint8_t*)malloc(total ? total : 1);
  uint64_t* offs = (uint64_t*)malloc((n + 1) * sizeof(uint64_t));
  memcpy(bytes, c->arena.data() + base, total);
  for (uint64_t i = 0; i < n; i++) offs[i] = c->offsets[start + i] - base;
  offs[n] = total;
  *bytes_out = bytes;
  *offsets_out = offs;
  return (int64_t)n;
}

void intern_free(void* p) { free(p); }

// copy key bytes for one id (for reverse lookup); returns length
uint32_t intern_key(void* h, uint64_t id, uint8_t* out, uint32_t cap) {
  Interner* c = static_cast<Interner*>(h);
  if (id >= c->count) return 0;
  uint32_t w = c->arena_w[id];
  uint32_t n = w < cap ? w : cap;
  memcpy(out, c->arena.data() + c->offsets[id], n);
  return w;
}

}  // extern "C"
