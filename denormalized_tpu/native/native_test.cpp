// native_test — self-contained exercises of the C++ components, built with
// -fsanitize=address,undefined by tests/test_native_sanitizers.py.  The
// reference ships no sanitizer coverage at all (SURVEY.md §5: "race
// detection/sanitizers: none"); this is our answer for the native runtime.
//
// Exercises: LSM store (put/get/delete/recovery/compaction), string
// interner (growth, duplicates, width changes, keys around the inline
// width, block boundaries, the id store), JSON parser (escapes, nulls,
// duplicates, malformed rows), the state observatory's sketch pass (empty
// and refused calls, eight slots, ids at the top of their width, a table
// as full as it gets, sampled counts, watches on threads of their own),
// the window operator's time arithmetic (floor division before the epoch
// and at both ends of int64, no rows, a buffer longer than the batch, the
// late / dropped / straddle counts) and the host reducer behind it.

#include <atomic>
#include <cassert>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>
#include <thread>
#include <vector>

// single-TU build: include the component sources directly
#include "avro_parser.cpp"
#include "interner.cpp"
#include "json_parser.cpp"
#include "kafka_client.cpp"
#include "lsmkv.cpp"
#include "partial_agg.cpp"
#include "sketch_update.cpp"

static void test_lsm(const char* dir) {
  void* s = lsm_open(dir);
  assert(s);
  for (int i = 0; i < 2000; i++) {
    char k[32], v[64];
    int kl = snprintf(k, sizeof k, "key-%d", i % 500);
    int vl = snprintf(v, sizeof v, "value-%d-%d", i, i * 7);
    assert(lsm_put(s, (const uint8_t*)k, kl, (const uint8_t*)v, vl) == 0);
  }
  for (int i = 0; i < 100; i += 2) {
    char k[32];
    int kl = snprintf(k, sizeof k, "key-%d", i);
    lsm_delete(s, (const uint8_t*)k, kl);
  }
  assert(lsm_count(s) == 450);
  uint8_t* out = nullptr;
  int64_t n = lsm_get(s, (const uint8_t*)"key-1", 5, &out);
  assert(n > 0);
  lsm_free(out);
  assert(lsm_get(s, (const uint8_t*)"key-0", 5, &out) == -1);
  lsm_flush(s);
  lsm_close(s);
  // reopen (recovery) + compaction
  s = lsm_open(dir);
  assert(lsm_count(s) == 450);
  assert(lsm_compact(s) == 0);
  assert(lsm_count(s) == 450);
  n = lsm_get(s, (const uint8_t*)"key-499", 7, &out);
  assert(n > 0);
  lsm_free(out);
  lsm_close(s);
  printf("lsm ok\n");
}

// -- interner helpers: keys in exactly-sized heap buffers, so ASAN sees
// any read past a caller's last byte
struct OffsetsBatch {
  uint8_t* bytes;
  std::vector<uint64_t> offsets;
  explicit OffsetsBatch(const std::vector<std::string>& keys) {
    size_t total = 0;
    offsets.push_back(0);
    for (auto& k : keys) {
      total += k.size();
      offsets.push_back(total);
    }
    bytes = (uint8_t*)malloc(total ? total : 1);
    size_t at = 0;
    for (auto& k : keys) {
      memcpy(bytes + at, k.data(), k.size());
      at += k.size();
    }
  }
  ~OffsetsBatch() { free(bytes); }
  std::vector<int32_t> intern(void* h, const uint8_t* valid = nullptr) {
    uint64_t n = offsets.size() - 1;
    std::vector<int32_t> ids(n ? n : 1, -7);
    intern_offsets(h, bytes, offsets.data(), valid, n, ids.data());
    ids.resize(n);
    return ids;
  }
};

static std::vector<int32_t> intern_fixed(
    void* h, const std::vector<std::string>& keys, uint32_t w) {
  size_t n = keys.size();
  uint8_t* buf = (uint8_t*)calloc(n * w != 0 ? n * w : 1, 1);
  for (size_t i = 0; i < n; i++) {
    assert(keys[i].size() <= w);
    memcpy(buf + i * w, keys[i].data(), keys[i].size());
  }
  std::vector<int32_t> ids(n ? n : 1, -7);
  intern_many(h, buf, n, w, ids.data());
  free(buf);
  ids.resize(n);
  return ids;
}

static std::string key_of_len(size_t len, int salt) {
  std::string k(len, 'a');
  for (size_t i = 0; i < len; i++)
    k[i] = (char)('a' + (i * 7 + (size_t)salt * 5 + len) % 26);
  return k;
}

static void test_interner() {
  void* h = intern_create();
  const uint32_t w = 12;
  std::vector<uint8_t> buf;
  std::vector<int32_t> ids;
  const int N = 50000;
  buf.resize((size_t)N * w, 0);
  ids.resize(N);
  for (int i = 0; i < N; i++) {
    char tmp[16];
    int len = snprintf(tmp, sizeof tmp, "k%d", i % 7000);
    memcpy(buf.data() + (size_t)i * w, tmp, (size_t)len);
  }
  intern_many(h, buf.data(), N, w, ids.data());
  assert(intern_count(h) == 7000);
  // dense, first seen first
  for (int i = 0; i < 7000; i++) assert(ids[i] == i);
  // stability: same keys → same ids
  std::vector<int32_t> ids2(N);
  intern_many(h, buf.data(), N, w, ids2.data());
  assert(memcmp(ids.data(), ids2.data(), N * 4) == 0);
  // width change re-lookup
  const uint32_t w2 = 20;
  std::vector<uint8_t> buf2((size_t)N * w2, 0);
  for (int i = 0; i < N; i++) {
    char tmp[16];
    int len = snprintf(tmp, sizeof tmp, "k%d", i % 7000);
    memcpy(buf2.data() + (size_t)i * w2, tmp, (size_t)len);
  }
  std::vector<int32_t> ids3(N);
  intern_many(h, buf2.data(), N, w2, ids3.data());
  assert(memcmp(ids.data(), ids3.data(), N * 4) == 0);
  uint8_t key[64];
  uint32_t kl = intern_key(h, ids[0], key, sizeof key);
  assert(kl == 2 && memcmp(key, "k0", 2) == 0);
  intern_destroy(h);

  // (a) keys on both sides of the inline width (23) and exactly at it,
  // (b) through the offsets lane, the fixed-width lane, and read back
  // from the id store
  const size_t lens[] = {0, 1, 2, 3, 4, 7, 8, 9, 15, 16, 17, 22,
                         23, 24, 25, 31, 32, 33, 200};
  std::vector<std::string> keys;
  for (size_t len : lens)
    for (int salt = 0; salt < (len ? 3 : 1); salt++)
      keys.push_back(key_of_len(len, salt));
  // same first 23 bytes, different tails: the arena compare decides
  keys.push_back(key_of_len(40, 0));
  keys.push_back(key_of_len(40, 0));
  keys.back()[39] ^= 1;
  // same bytes, one NUL apart in length and inside: distinct keys
  keys.push_back(std::string("a\0b", 3));
  keys.push_back(std::string("a\0\0b", 4));
  const size_t K = keys.size();
  h = intern_create();
  OffsetsBatch ob(keys);
  std::vector<int32_t> a = ob.intern(h);
  for (size_t i = 0; i < K; i++) assert(a[i] == (int32_t)i);
  assert(intern_count(h) == K);
  assert(ob.intern(h) == a);
  assert(intern_fixed(h, keys, 200) == a);
  assert(intern_fixed(h, keys, 233) == a);
  assert(intern_count(h) == K);
  // trailing NULs strip in the offsets lane too
  {
    std::vector<std::string> padded;
    for (auto& k : keys) padded.push_back(k + std::string(3, '\0'));
    assert(OffsetsBatch(padded).intern(h) == a);
  }
  // a second interner fed through the fixed-width lane first agrees
  {
    void* h2 = intern_create();
    assert(intern_fixed(h2, keys, 200) == a);
    assert(ob.intern(h2) == a);
    intern_destroy(h2);
  }
  // the id store: one key at a time, and in bulk
  for (size_t i = 0; i < K; i++) {
    std::vector<uint8_t> out(keys[i].size() + 1);
    uint32_t n = intern_key(h, i, out.data(), (uint32_t)out.size());
    assert(n == keys[i].size());
    assert(memcmp(out.data(), keys[i].data(), n) == 0);
  }
  {
    uint8_t* bytes = nullptr;
    uint64_t* offs = nullptr;
    assert(intern_keys_range(h, 0, K, &bytes, &offs) == (int64_t)K);
    for (size_t i = 0; i < K; i++) {
      assert(offs[i + 1] - offs[i] == keys[i].size());
      assert(memcmp(bytes + offs[i], keys[i].data(), keys[i].size()) == 0);
    }
    intern_free(bytes);
    intern_free(offs);
    assert(intern_keys_range(h, 3, 5, &bytes, &offs) == 2);
    assert(offs[2] == keys[3].size() + keys[4].size());
    intern_free(bytes);
    intern_free(offs);
    assert(intern_keys_range(h, 0, K + 1, &bytes, &offs) == -1);
  }
  // NULL rows intern the 0xFF key, whatever bytes sit in their slot
  {
    std::vector<std::string> two = {keys[5], keys[5], "\xff"};
    const uint8_t valid[3] = {1, 0, 1};
    std::vector<int32_t> v = OffsetsBatch(two).intern(h, valid);
    assert(v[0] == a[5] && v[1] == (int32_t)K && v[2] == v[1]);
  }
  // the tallies: every row counted once, overflow rows are the long ones
  {
    uint64_t st[3];
    intern_stats(h, st);
    size_t long_keys = 0;
    for (auto& k : keys) long_keys += k.size() > 23;
    assert(st[0] == 5 * K + 3);
    assert(st[2] == 5 * long_keys);
  }
  intern_destroy(h);

  // (c) block boundaries: n = 0, 1, one short of a block, a block, one
  // over, several blocks and a tail — each against a row-at-a-time twin
  for (size_t n : {0, 1, 15, 16, 17, 33, 100}) {
    std::vector<std::string> rows;
    for (size_t i = 0; i < n; i++)
      rows.push_back(key_of_len(5 + (i * 11) % 30, (int)(i % 9)));
    void* hb = intern_create();
    void* h1 = intern_create();
    std::vector<int32_t> blocked = OffsetsBatch(rows).intern(hb);
    assert(blocked.size() == n);
    for (size_t i = 0; i < n; i++) {
      std::vector<int32_t> one = OffsetsBatch({rows[i]}).intern(h1);
      assert(one[0] == blocked[i]);
    }
    assert(intern_count(hb) == intern_count(h1));
    assert(intern_fixed(hb, rows, 40) == blocked);
    intern_destroy(hb);
    intern_destroy(h1);
  }

  // (d) a first-seen key repeated inside one block takes the first
  // occurrence's id, short and long alike
  {
    std::string s = key_of_len(9, 1), l = key_of_len(50, 1);
    std::vector<std::string> rows = {s, l, s, "x", l, s, "y", "x", l};
    void* hd = intern_create();
    std::vector<int32_t> got = OffsetsBatch(rows).intern(hd);
    const int32_t want[] = {0, 1, 0, 2, 1, 0, 3, 2, 1};
    for (size_t i = 0; i < rows.size(); i++) assert(got[i] == want[i]);
    assert(intern_count(hd) == 4);
    intern_destroy(hd);
  }

  // (e) growth across several doublings (1024 → 32768 slots), keys of
  // both kinds arriving inside the blocks that grow the table; every id
  // re-found afterwards, and none taken twice
  {
    const int G = 20000;
    std::vector<std::string> rows;
    for (int i = 0; i < G; i++) {
      char tmp[48];
      int len = i % 3 == 2
          ? snprintf(tmp, sizeof tmp, "grow-a-rather-long-key-%012d", i)
          : snprintf(tmp, sizeof tmp, "g%d", i);
      rows.emplace_back(tmp, (size_t)len);
    }
    void* hg = intern_create();
    OffsetsBatch gb(rows);
    std::vector<int32_t> first = gb.intern(hg);
    for (int i = 0; i < G; i++) assert(first[i] == i);
    assert(intern_count(hg) == (uint64_t)G);
    assert(gb.intern(hg) == first);
    assert(intern_fixed(hg, rows, 36) == first);
    uint64_t st[3];
    intern_stats(hg, st);
    assert(st[0] == 3 * (uint64_t)G);
    assert(st[2] == 3 * (uint64_t)(G / 3));
    // collision pressure stays that of a table at most 3/4 full
    assert(st[1] < st[0] * 4);
    intern_destroy(hg);
  }

  // (f) keys that differ in their last bytes alone (the top of the last
  // hashed word) still spread over the table: no probe chain through
  // all of them
  for (size_t len : {7, 8, 23, 24, 47}) {
    std::vector<std::string> rows;
    for (int i = 0; i < 600; i++) {
      std::string k(len, 'q');
      k[len - 1] = (char)('0' + i % 25);
      k[len - 2] = (char)('0' + i / 25);
      rows.push_back(k);
    }
    void* hc = intern_create();
    OffsetsBatch cb(rows);
    cb.intern(hc);
    cb.intern(hc);
    uint64_t st[3];
    intern_stats(hc, st);
    assert(intern_count(hc) == 600 && st[0] == 1200);
    assert(st[1] < 2 * st[0]);  // 600 keys in 1024 slots
    intern_destroy(hc);
  }
  printf("interner ok\n");
}

static void test_json() {
  const char* names[3] = {"a", "s", "f"};
  int types[3] = {0, 3, 1};
  void* p = jp_create(3, names, types);
  std::string rows;
  std::vector<uint64_t> offs{0};
  auto add = [&](const char* r) {
    rows += r;
    offs.push_back(rows.size());
  };
  add("{\"a\": 42, \"s\": \"he\\u00e9llo\", \"f\": -1.5e3}");
  add("{\"s\": null, \"a\": -7, \"extra\": {\"x\": [1, 2, {}]}, \"f\": 0.25}");
  add("{\"a\": 1, \"a\": 2, \"s\": \"dup\", \"f\": 1}");
  add("{}");
  int rc = jp_parse(p, (const uint8_t*)rows.data(), offs.data(),
                    offs.size() - 1);
  assert(rc == 0);
  assert(jp_nrows(p) == 4);
  const int64_t* av = jp_col_i64(p, 0);
  assert(av[0] == 42 && av[1] == -7 && av[2] == 2);
  const uint8_t* valid = jp_col_valid(p, 1);
  assert(valid[0] == 1 && valid[1] == 0 && valid[3] == 0);
  uint64_t nb;
  jp_col_str_bytes(p, 1, &nb);
  assert(nb > 0);
  // malformed input reports an error (fresh parser)
  jp_clear(p);
  std::string bad = "{\"a\": nope}";
  uint64_t boffs[2] = {0, bad.size()};
  assert(jp_parse(p, (const uint8_t*)bad.data(), boffs, 1) == -1);
  assert(strlen(jp_error(p)) > 0);
  // payload truncated MID-NUMBER at the exact end of the arena: the number
  // scan must stop at the boundary (ASan redzones on the heap-exact buffer
  // catch any strtoll/strtod overread) and the row must error cleanly
  for (const char* t : {"{\"a\": 123", "{\"f\": -1.5e", "{\"a\": "}) {
    jp_clear(p);
    std::string tr = t;
    std::vector<uint8_t> exact(tr.begin(), tr.end());
    uint64_t toffs[2] = {0, tr.size()};
    assert(jp_parse(p, exact.data(), toffs, 1) == -1);
  }
  // partial-consumption tokens must fail the row, not silently truncate
  // ("1e5" on an int column would otherwise store 1)
  for (const char* t : {"{\"a\": 1e5}", "{\"a\": 12.5}", "{\"f\": 1.2.3}"}) {
    jp_clear(p);
    std::string tr = t;
    std::vector<uint8_t> exact(tr.begin(), tr.end());
    uint64_t toffs[2] = {0, tr.size()};
    assert(jp_parse(p, exact.data(), toffs, 1) == -1);
  }
  // a long-but-legal numeric token (>47 chars) still parses — arbitrary
  // precision decimals are valid JSON
  {
    jp_clear(p);
    std::string lng =
        "{\"a\": 7, \"s\": \"x\", \"f\": 1" + std::string(60, '0') + ".5}";
    std::vector<uint8_t> exact(lng.begin(), lng.end());
    uint64_t loffs[2] = {0, lng.size()};
    assert(jp_parse(p, exact.data(), loffs, 1) == 0);
    assert(jp_col_f64(p, 2)[0] == 1e60);
  }
  jp_destroy(p);
  printf("json ok\n");
}

static void test_json_fast_layout() {
  // the adaptive-layout fast path: identical-shape rows adopt a layout
  // after the first general-path parse; deviating rows roll back and
  // reparse.  Heap-exact buffers put ASan redzones right at every row
  // boundary, so any fast-path overread (memcmp/memchr/num scan) traps.
  const char* names[3] = {"a", "s", "f"};
  int types[3] = {0, 3, 1};
  void* p = jp_create(3, names, types);
  std::string rows;
  std::vector<uint64_t> offs{0};
  auto add = [&](const std::string& r) {
    rows += r;
    offs.push_back(rows.size());
  };
  // 32 identical-shape rows (fast path from row 1 on)
  for (int i = 0; i < 32; i++)
    add("{\"a\":" + std::to_string(i) + ",\"s\":\"k" + std::to_string(i) +
        "\",\"f\":" + std::to_string(i) + ".5}");
  // deviations mid-stream: reorder, escape in string, null value,
  // missing key, unknown key, json.dumps spacing — each must fall back
  // (rollback) and reparse correctly, then re-adopt
  add("{\"s\":\"re\",\"a\":900,\"f\":1.0}");
  add("{\"a\":901,\"s\":\"q\\\"x\\\\y\",\"f\":2.0}");
  add("{\"a\":null,\"s\":\"n\",\"f\":3.0}");
  add("{\"a\":903,\"f\":4.0}");
  add("{\"a\":904,\"s\":\"u\",\"zz\":[1,{\"q\":2}],\"f\":5.0}");
  add("{\"a\": 905, \"s\": \"sp\", \"f\": 6.0}");
  // back to the fast shape
  for (int i = 0; i < 8; i++)
    add("{\"a\":" + std::to_string(1000 + i) + ",\"s\":\"t\",\"f\":0.25}");
  {
    std::vector<uint8_t> exact(rows.begin(), rows.end());
    int rc = jp_parse(p, exact.data(), offs.data(), offs.size() - 1);
    assert(rc == 0);
    assert(jp_nrows(p) == 32 + 6 + 8);
    const int64_t* av = jp_col_i64(p, 0);
    const uint8_t* valid = jp_col_valid(p, 0);
    for (int i = 0; i < 32; i++) assert(av[i] == i);
    assert(av[32] == 900 && av[33] == 901);
    assert(valid[34] == 0);            // null a
    assert(av[35] == 903 && av[36] == 904 && av[37] == 905);
    for (int i = 0; i < 8; i++) assert(av[38 + i] == 1000 + i);
    const uint8_t* svalid = jp_col_valid(p, 1);
    assert(svalid[35] == 0);           // missing s
    const double* fv = jp_col_f64(p, 2);
    assert(fv[33] == 2.0 && fv[45] == 0.25);
  }
  // truncated rows WITH an armed layout: fast path must stop at the row
  // boundary, roll back, and the general path reports the error
  for (const char* t :
       {"{\"a\":7,\"s\":\"x\",\"f\":1.", "{\"a\":7,\"s\":\"x", "{\"a\":7,"}) {
    jp_clear(p);
    // re-arm the layout on the fast shape first
    std::string warm = "{\"a\":1,\"s\":\"w\",\"f\":2.0}";
    std::string tr = t;
    std::string both = warm + tr;
    std::vector<uint8_t> exact(both.begin(), both.end());
    uint64_t toffs[3] = {0, warm.size(), both.size()};
    assert(jp_parse(p, exact.data(), toffs, 2) == -1);
    assert(strlen(jp_error(p)) > 0);
  }
  jp_destroy(p);
  printf("json fast layout ok\n");
}

static void test_json_tree() {
  // the shredded node-tree ABI: nested structs to depth 2, a list of
  // strings, null/missing/duplicate/unknown-key handling, and the
  // adaptive layout over nested shapes.  Heap-exact buffers put ASan
  // redzones at every row boundary.
  //   0 id(str)  1 imu(struct)  2 ts(i64, p=1)  3 gps(struct, p=1)
  //   4 lat(f64, p=3)  5 spd(f64, p=3)  6 tags(list<str>)
  const char* names[7] = {"id", "imu", "ts", "gps", "lat", "spd", "tags"};
  int types[7] = {3, 4, 0, 4, 1, 1, 5};
  int etypes[7] = {-1, -1, -1, -1, -1, -1, 3};
  int parents[7] = {-1, -1, 1, 1, 3, 3, -1};
  void* p = jp_create_tree(7, names, types, etypes, parents);
  std::string rows;
  std::vector<uint64_t> offs{0};
  auto add = [&](const std::string& r) {
    rows += r;
    offs.push_back(rows.size());
  };
  // fixed nested shape — layout adoption must cover leaves inside structs
  for (int i = 0; i < 16; i++)
    add("{\"id\":\"d" + std::to_string(i) + "\",\"imu\":{\"ts\":" +
        std::to_string(i) + ",\"gps\":{\"lat\":1.5,\"spd\":2.5}},\"tags\":"
        "[\"a\",\"b\"]}");
  add("{\"id\":\"x\",\"imu\":null,\"tags\":[]}");               // null struct
  add("{\"id\":\"y\",\"imu\":{\"gps\":null},\"tags\":null}");   // inner null
  add("{\"id\":\"z\",\"imu\":{\"ts\":7,\"gps\":{\"lat\":9.5,\"spd\":8.5},"
      "\"junk\":{\"a\":[1]}},\"tags\":[\"q\",null]}");          // unknown key
  add("{\"imu\":{\"ts\":1,\"gps\":{\"lat\":0.0,\"spd\":0.0}},"
      "\"imu\":{\"ts\":99,\"gps\":{\"lat\":7.5,\"spd\":6.5}},"
      "\"id\":\"dup\",\"tags\":[\"w\"]}");                      // dup struct
  {
    std::vector<uint8_t> exact(rows.begin(), rows.end());
    assert(jp_parse(p, exact.data(), offs.data(), offs.size() - 1) == 0);
    uint64_t n = jp_nrows(p);
    assert(n == 20);
    const int64_t* ts = jp_col_i64(p, 2);
    const uint8_t* tsv = jp_col_valid(p, 2);
    for (int i = 0; i < 16; i++) assert(ts[i] == i && tsv[i] == 1);
    assert(tsv[16] == 0 && tsv[17] == 0);  // null imu / missing ts
    const uint8_t* imup = jp_col_valid(p, 1);
    const uint8_t* gpsp = jp_col_valid(p, 3);
    assert(imup[16] == 0 && gpsp[16] == 0);
    assert(imup[17] == 1 && gpsp[17] == 0);
    assert(ts[18] == 7 && ts[19] == 99);  // dup: last wins
    const double* lat = jp_col_f64(p, 4);
    assert(lat[18] == 9.5 && lat[19] == 7.5);
    const uint64_t* lo = jp_col_list_offsets(p, 6);
    assert(lo[16] - lo[0] == 32);          // 16 rows x 2 elems
    assert(lo[17] == lo[16]);              // []
    assert(lo[18] == lo[17]);              // null list
    assert(lo[19] - lo[18] == 2);          // ["q", null]
    const uint8_t* ev = jp_col_list_evalid(p, 6);
    assert(ev[lo[18]] == 1 && ev[lo[18] + 1] == 0);
    const uint8_t* lv = jp_col_valid(p, 6);
    assert(lv[16] == 1 && lv[17] == 0);
    assert(jp_col_list_nelems(p, 6) == lo[20]);
  }
  // truncation inside a nested value with an armed layout
  for (const char* t :
       {"{\"id\":\"t\",\"imu\":{\"ts\":1,\"gps\":{\"lat\":1.5,",
        "{\"id\":\"t\",\"imu\":{\"ts\":1", "{\"id\":\"t\",\"tags\":[\"a\""}) {
    jp_clear(p);
    std::string warm =
        "{\"id\":\"w\",\"imu\":{\"ts\":0,\"gps\":{\"lat\":1.5,\"spd\":2.5}},"
        "\"tags\":[\"a\",\"b\"]}";
    std::string both = warm + t;
    std::vector<uint8_t> exact(both.begin(), both.end());
    uint64_t toffs[3] = {0, warm.size(), both.size()};
    assert(jp_parse(p, exact.data(), toffs, 2) == -1);
    assert(strlen(jp_error(p)) > 0);
  }
  jp_destroy(p);
  printf("json tree ok\n");
}

static void test_json_generic_lists() {
  // type-6 generic lists (PR 2): list-of-struct and list-of-list with
  // null elements, missing/duplicate keys inside elements, layout
  // adoption over the opaque list units, and mid-list truncation
  // rollback.  Heap-exact buffers put ASan redzones at the row ends.
  //   0 id(i64)  1 evts(list<struct>)  2 item(struct,p=1)  3 k(i64,p=2)
  //   4 s(str,p=2)  5 m(list<list<i64>>)  6 inner(list<i64>,p=5)
  const char* names[7] = {"id", "evts", "item", "k", "s", "m", "inner"};
  int types[7] = {0, 6, 4, 0, 3, 6, 5};
  int etypes[7] = {-1, -1, -1, -1, -1, -1, 0};
  int parents[7] = {-1, -1, 1, 2, 2, -1, 5};
  void* p = jp_create_tree(7, names, types, etypes, parents);
  std::string rows;
  std::vector<uint64_t> offs{0};
  auto add = [&](const std::string& r) {
    rows += r;
    offs.push_back(rows.size());
  };
  for (int i = 0; i < 12; i++)  // fixed shape: layout adoption
    add("{\"id\":" + std::to_string(i) +
        ",\"evts\":[{\"k\":1,\"s\":\"a\"},{\"k\":2,\"s\":\"b\"}],"
        "\"m\":[[1,2],[3]]}");
  add("{\"id\":100,\"evts\":[],\"m\":[]}");
  add("{\"id\":101,\"evts\":null,\"m\":null}");
  add("{\"id\":102,\"evts\":[null,{\"s\":\"y\",\"zz\":7}],"
      "\"m\":[null,[4,null]]}");  // null elem, missing k, unknown key
  add("{\"id\":103,\"evts\":[{\"k\":5,\"k\":6}],\"m\":[[]]}");  // dup in elem
  {
    std::vector<uint8_t> exact(rows.begin(), rows.end());
    assert(jp_parse(p, exact.data(), offs.data(), offs.size() - 1) == 0);
    assert(jp_nrows(p) == 16);
    const uint64_t* eo = jp_col_list_offsets(p, 1);
    assert(eo[12] == 24 && eo[13] == 24);   // 12 x 2 elems, then []
    assert(eo[14] == 24);                   // null list: no elems
    assert(eo[15] - eo[14] == 2 && eo[16] - eo[15] == 1);
    const uint8_t* ep = jp_col_valid(p, 2);  // element struct presence
    assert(ep[24] == 0 && ep[25] == 1);      // [null, {...}]
    const int64_t* kv = jp_col_i64(p, 3);
    const uint8_t* kvv = jp_col_valid(p, 3);
    assert(kvv[25] == 0);                    // missing k -> null leaf
    assert(kv[26] == 6 && kvv[26] == 1);     // dup key: last wins
    const uint8_t* lv = jp_col_valid(p, 1);
    assert(lv[12] == 1 && lv[13] == 0 && lv[14] == 1);
    // list-of-list: outer offsets index INNER list entries
    const uint64_t* mo = jp_col_list_offsets(p, 5);
    const uint64_t* io = jp_col_list_offsets(p, 6);
    const uint8_t* iv = jp_col_valid(p, 6);
    assert(mo[12] == 24);                    // 12 x 2 inner lists
    assert(mo[15] - mo[14] == 2);            // [null, [4, null]]
    assert(iv[mo[14]] == 0 && iv[mo[14] + 1] == 1);
    uint64_t in0 = mo[14] + 1;               // the [4, null] inner entry
    assert(io[in0 + 1] - io[in0] == 2);
    const uint8_t* iev = jp_col_list_evalid(p, 6);
    assert(iev[io[in0]] == 1 && iev[io[in0] + 1] == 0);
    assert(jp_col_i64(p, 6)[io[in0]] == 4);
  }
  // truncation mid-element with an armed layout: rollback must trim the
  // whole nested subtree (trim_node through offsets), caught by ASan if
  // any vector is left inconsistent
  for (const char* t :
       {"{\"id\":1,\"evts\":[{\"k\":1,\"s\":\"a\"},{\"k\":",
        "{\"id\":1,\"m\":[[1,", "{\"id\":1,\"evts\":[null,"}) {
    jp_clear(p);
    std::string warm =
        "{\"id\":0,\"evts\":[{\"k\":1,\"s\":\"a\"},{\"k\":2,\"s\":\"b\"}],"
        "\"m\":[[1,2],[3]]}";
    std::string both = warm + t;
    std::vector<uint8_t> exact(both.begin(), both.end());
    uint64_t toffs[3] = {0, warm.size(), both.size()};
    assert(jp_parse(p, exact.data(), toffs, 2) == -1);
    assert(jp_nrows(p) == 1);  // the warm row survived the rollback
  }
  jp_destroy(p);
  printf("json generic lists ok\n");
}

static void zz(std::vector<uint8_t>& out, int64_t v) {
  uint64_t z = ((uint64_t)v << 1) ^ (uint64_t)(v >> 63);
  while (z >= 0x80) {
    out.push_back((uint8_t)(z | 0x80));
    z >>= 7;
  }
  out.push_back((uint8_t)z);
}

static void test_avro() {
  // schema: long ts, nullable double v, string name, bool ok
  int types[4] = {0, 1, 3, 2};
  int nulls[4] = {0, 1, 0, 0};
  void* p = ap_create(4, types, nulls);
  std::vector<uint8_t> arena;
  std::vector<uint64_t> offs{0};
  auto rec = [&](int64_t ts, bool has_v, double v, const char* s, bool ok) {
    zz(arena, ts);
    zz(arena, has_v ? 1 : 0);
    if (has_v) {
      const uint8_t* b = (const uint8_t*)&v;
      arena.insert(arena.end(), b, b + 8);
    }
    zz(arena, (int64_t)strlen(s));
    arena.insert(arena.end(), (const uint8_t*)s, (const uint8_t*)s + strlen(s));
    arena.push_back(ok ? 1 : 0);
    offs.push_back(arena.size());
  };
  rec(1700000000000LL, true, 2.5, "alpha", true);
  rec(-42, false, 0, "", false);
  rec(7, true, -1.25, "日本", true);
  assert(ap_parse(p, arena.data(), offs.data(), 3) == 0);
  assert(ap_nrows(p) == 3);
  const int64_t* ts = ap_col_i64(p, 0);
  assert(ts[0] == 1700000000000LL && ts[1] == -42 && ts[2] == 7);
  const uint8_t* valid = ap_col_valid(p, 1);
  assert(valid[0] == 1 && valid[1] == 0 && valid[2] == 1);
  const double* v = ap_col_f64(p, 1);
  assert(v[0] == 2.5 && v[2] == -1.25);
  const uint8_t* okc = ap_col_bool(p, 3);
  assert(okc[0] == 1 && okc[1] == 0 && okc[2] == 1);
  // trailing garbage after the last field must fail the parse
  ap_clear(p);
  std::vector<uint8_t> bad(arena.begin(), arena.begin() + (long)offs[1]);
  bad.push_back(0xAB);
  uint64_t boffs[2] = {0, bad.size()};
  assert(ap_parse(p, bad.data(), boffs, 1) == -1);
  // sanitizer fuzz: truncations + single-byte corruptions of a valid arena
  for (uint64_t n = 0; n <= offs[1]; n++) {
    ap_clear(p);
    uint64_t toffs[2] = {0, n};
    std::vector<uint8_t> exact(arena.begin(), arena.begin() + (long)n);
    ap_parse(p, exact.data(), toffs, 1);
  }
  for (size_t i = 0; i < offs[1]; i++)
    for (uint8_t x : {uint8_t{0xFF}, uint8_t{0x80}, uint8_t{0x01}}) {
      ap_clear(p);
      std::vector<uint8_t> m(arena.begin(), arena.begin() + (long)offs[1]);
      m[i] ^= x;
      uint64_t moffs[2] = {0, m.size()};
      ap_parse(p, m.data(), moffs, 1);
    }
  ap_destroy(p);
  printf("avro ok\n");
}

static void test_avro_tree() {
  // the schema-tree ABI (PR 2): nested records, arrays of records,
  // arrays of arrays, nullable at every level; block-encoded arrays
  // with negative counts; truncation rollback; count-bomb rejection.
  //   0 id(i64)  1 imu(rec,nullable)  2 ts(i64,p=1)  3 gps(rec,p=1,nul)
  //   4 lat(f64,p=3)  5 readings(list,p=-1)  6 elem(rec,p=5)
  //   7 k(i64,p=6)  8 m(list)  9 inner(list,p=8)  10 x(i64,p=9)
  int types[11] = {0, 5, 0, 5, 1, 6, 5, 0, 6, 6, 0};
  int nulls[11] = {0, 1, 0, 1, 0, 0, 0, 1, 0, 1, 0};
  int parents[11] = {-1, -1, 1, 1, 3, -1, 5, 6, -1, 8, 9};
  void* p = ap_create_tree(11, types, nulls, parents);
  std::vector<uint8_t> arena;
  std::vector<uint64_t> offs{0};
  auto rec = [&](int64_t id, bool imu_null, bool gps_null, int nread,
                 int ninner) {
    zz(arena, id);
    zz(arena, imu_null ? 0 : 1);  // imu union branch
    if (!imu_null) {
      zz(arena, 42);              // ts
      zz(arena, gps_null ? 0 : 1);
      if (!gps_null) {
        double lat = 1.5;
        const uint8_t* b = (const uint8_t*)&lat;
        arena.insert(arena.end(), b, b + 8);
      }
    }
    if (nread) {
      zz(arena, nread);
      for (int i = 0; i < nread; i++) {
        zz(arena, i % 2);          // k union branch: alternate null
        if (i % 2) zz(arena, 7);
      }
    }
    zz(arena, 0);                  // readings terminator
    if (ninner) {
      zz(arena, -ninner);          // negative block count + byte size
      zz(arena, 1);                // (size not validated, items decoded)
      for (int i = 0; i < ninner; i++) {
        zz(arena, 1);              // inner union branch: present
        zz(arena, 2);              // one element
        zz(arena, (int64_t)i);
        zz(arena, (int64_t)-i);
        zz(arena, 0);              // inner terminator
      }
    }
    zz(arena, 0);                  // m terminator
    offs.push_back(arena.size());
  };
  rec(1, false, false, 2, 2);
  rec(2, true, false, 0, 0);
  rec(3, false, true, 3, 1);
  {
    std::vector<uint8_t> exact(arena);
    assert(ap_parse(p, exact.data(), offs.data(), 3) == 0);
    assert(ap_nrows(p) == 3);
    const uint8_t* imup = ap_col_valid(p, 1);
    assert(imup[0] == 1 && imup[1] == 0 && imup[2] == 1);
    const uint8_t* gpsp = ap_col_valid(p, 3);
    assert(gpsp[0] == 1 && gpsp[1] == 0 && gpsp[2] == 0);
    assert(ap_col_f64(p, 4)[0] == 1.5);
    const uint64_t* ro = ap_col_list_offsets(p, 5);
    assert(ro[1] == 2 && ro[2] == 2 && ro[3] == 5);
    const uint8_t* kp = ap_col_valid(p, 7);
    assert(kp[0] == 0 && kp[1] == 1);  // alternating null ks
    assert(ap_col_i64(p, 7)[1] == 7);
    const uint64_t* mo = ap_col_list_offsets(p, 8);
    assert(mo[1] == 2 && mo[3] == 3);  // 2 + 0 + 1 inner lists
    const uint64_t* io = ap_col_list_offsets(p, 9);
    assert(io[1] == 2 && ap_col_i64(p, 10)[0] == 0);
    assert(ap_col_i64(p, 10)[1] == 0);  // -0 zigzag
  }
  // truncations at every byte boundary of the arena: rollback must keep
  // every node subtree consistent (ASan catches stale sizes)
  for (size_t cut = 0; cut < offs[1]; cut++) {
    ap_clear(p);
    std::vector<uint8_t> exact(arena.begin(), arena.begin() + cut);
    uint64_t toffs[2] = {0, cut};
    assert(ap_parse(p, exact.data(), toffs, 1) == -1);
    assert(ap_nrows(p) == 0);
  }
  // array count bomb: tiny payload declaring 2^30 items must fail, not
  // allocate
  {
    ap_clear(p);
    std::vector<uint8_t> bomb;
    zz(bomb, 9);       // id
    zz(bomb, 0);       // imu null
    zz(bomb, 1 << 30); // readings count
    uint64_t boffs[2] = {0, bomb.size()};
    std::vector<uint8_t> exact(bomb);
    assert(ap_parse(p, exact.data(), boffs, 1) == -1);
  }
  ap_destroy(p);
  // repeated-block bomb (review-found): array<empty record> elements
  // consume ZERO wire bytes, so the per-block remaining-bytes cap admits
  // 65536 items per ~3-byte block forever — the cumulative per-record
  // element budget must stop it after the first block
  {
    int types2[2] = {6, 5};
    int nulls2[2] = {0, 0};
    int parents2[2] = {-1, 0};
    void* p2 = ap_create_tree(2, types2, nulls2, parents2);
    std::vector<uint8_t> bomb;
    for (int b = 0; b < 200; b++) zz(bomb, 65536);
    zz(bomb, 0);
    uint64_t boffs[2] = {0, bomb.size()};
    std::vector<uint8_t> exact(bomb);
    assert(ap_parse(p2, exact.data(), boffs, 1) == -1);
    // a small array of empty records stays legal
    ap_clear(p2);
    std::vector<uint8_t> ok;
    zz(ok, 3);
    zz(ok, 0);
    uint64_t ooffs[2] = {0, ok.size()};
    std::vector<uint8_t> exact2(ok);
    assert(ap_parse(p2, exact2.data(), ooffs, 1) == 0);
    assert(ap_col_list_offsets(p2, 0)[1] == 3);
    ap_destroy(p2);
  }
  printf("avro tree ok\n");
}

static void test_codecs() {
  // valid raw-snappy: "hellohellohello!" via literal + overlapping copy
  std::string want = "hellohellohello!";
  std::vector<uint8_t> sn;
  sn.push_back((uint8_t)want.size());     // uvarint len (16)
  sn.push_back((5 - 1) << 2);             // literal "hello"
  sn.insert(sn.end(), want.begin(), want.begin() + 5);
  sn.push_back(((10 - 4) << 2) | 1);      // type-1 copy off=5 len=10
  sn.push_back(5);
  sn.push_back((1 - 1) << 2);             // literal "!"
  sn.push_back('!');
  std::vector<uint8_t> out;
  assert(snappy_decompress(sn.data(), sn.size(), out));
  assert(std::string(out.begin(), out.end()) == want);

  // valid lz4 frame: one block, literals + match(off=2,len=8) + literals
  std::string lw = "ababababab-tail";
  std::vector<uint8_t> blk;
  blk.push_back((2 << 4) | (8 - 4));      // lit 2, match 8
  blk.push_back('a');
  blk.push_back('b');
  blk.push_back(2);                       // offset LE16 = 2
  blk.push_back(0);
  blk.push_back(5 << 4);                  // last sequence: 5 literals
  const char* tail = "-tail";
  blk.insert(blk.end(), tail, tail + 5);
  std::vector<uint8_t> fr;
  uint32_t magic = 0x184D2204u;
  for (int i = 0; i < 4; i++) fr.push_back((uint8_t)(magic >> (8 * i)));
  fr.push_back(0x40);  // FLG v1
  fr.push_back(0x40);  // BD
  fr.push_back(0x00);  // header checksum (not validated)
  uint32_t bsz = (uint32_t)blk.size();
  for (int i = 0; i < 4; i++) fr.push_back((uint8_t)(bsz >> (8 * i)));
  fr.insert(fr.end(), blk.begin(), blk.end());
  for (int i = 0; i < 4; i++) fr.push_back(0);  // EndMark
  out.clear();
  assert(lz4f_decompress(fr.data(), fr.size(), out));
  assert(std::string(out.begin(), out.end()) == lw);

  // sanitizer fuzz: every truncation and every single-byte corruption of
  // the valid streams must return cleanly (true or false), never read or
  // write out of bounds — this is untrusted broker data
  auto hammer = [&](const std::vector<uint8_t>& v,
                    bool (*fn)(const uint8_t*, size_t,
                               std::vector<uint8_t>&)) {
    std::vector<uint8_t> o;
    for (size_t n = 0; n <= v.size(); n++) fn(v.data(), n, o);
    std::vector<uint8_t> m;
    for (size_t i = 0; i < v.size(); i++)
      for (uint8_t x : {uint8_t{0xFF}, uint8_t{0x80}, uint8_t{0x01}, uint8_t{0x00}}) {
        m = v;
        m[i] ^= x;
        fn(m.data(), m.size(), o);
      }
  };
  hammer(sn, snappy_decompress);
  hammer(fr, lz4f_decompress);
  // xerial-framed snappy, same hammering
  std::vector<uint8_t> xr = {0x82, 'S', 'N', 'A', 'P', 'P', 'Y', 0,
                             0, 0, 0, 1, 0, 0, 0, 1};
  uint32_t bl = (uint32_t)sn.size();
  for (int i = 3; i >= 0; i--) xr.push_back((uint8_t)(bl >> (8 * i)));
  xr.insert(xr.end(), sn.begin(), sn.end());
  out.clear();
  assert(snappy_decompress(xr.data(), xr.size(), out));
  assert(std::string(out.begin(), out.end()) == want);
  hammer(xr, snappy_decompress);
  printf("codecs ok\n");
}

// ---- threaded hammers ----------------------------------------------------
// The engine calls these components from prefetch worker threads with the
// GIL released — the sanitizer build that matters most here is
// -fsanitize=thread (tests/test_native_sanitizers.py builds all of this
// under TSan and under ASan/UBSan; the hammers also run in the plain
// build as ordinary correctness tests).

static void test_lsm_hammer(const char* dir) {
  // one store, 4 threads of put/get/flush on overlapping key sets: the
  // store's internal mutex is the contract (state/checkpoint snapshots
  // and LSM maintenance can touch the global store from several threads)
  std::string d = std::string(dir) + "-hammer";
  void* s = lsm_open(d.c_str());
  assert(s);
  std::vector<std::thread> ts;
  for (int t = 0; t < 4; t++) {
    ts.emplace_back([s, t] {
      char k[32], v[64];
      for (int i = 0; i < 3000; i++) {
        int kl;
        if (i % 3 == 0)  // cross-thread contended keys
          kl = snprintf(k, sizeof k, "shared-%d", i % 50);
        else  // per-thread keys (the common partition-isolated shape)
          kl = snprintf(k, sizeof k, "h%d-%d", t, i % 250);
        int vl = snprintf(v, sizeof v, "val-%d-%d-%d", t, i, i * 31);
        assert(lsm_put(s, (const uint8_t*)k, (uint32_t)kl,
                       (const uint8_t*)v, (uint32_t)vl) == 0);
        if (i % 7 == 0) {
          uint8_t* out = nullptr;
          int64_t n = lsm_get(s, (const uint8_t*)k, (uint32_t)kl, &out);
          assert(n > 0);  // nothing ever deletes these keys
          lsm_free(out);
        }
        if (i % 500 == 499) lsm_flush(s);
      }
    });
  }
  for (auto& th : ts) th.join();
  // the final key population is deterministic even though values race
  assert(lsm_count(s) == 50 + 4 * 250);
  lsm_close(s);
  s = lsm_open(d.c_str());  // recovery after concurrent writes
  assert(lsm_count(s) == 50 + 4 * 250);
  lsm_close(s);
  printf("lsm hammer ok\n");
}

// -- loopback mini-broker: just enough Produce v3 / Fetch v4 to drive the
// real client wire paths from concurrent threads without a Kafka --------
static bool h_recv_all(int fd, uint8_t* d, size_t n) {
  while (n) {
    ssize_t r = ::recv(fd, d, n, 0);
    if (r <= 0) return false;
    d += r;
    n -= (size_t)r;
  }
  return true;
}

static bool h_send_all(int fd, const uint8_t* d, size_t n) {
  while (n) {
    ssize_t w = ::send(fd, d, n, MSG_NOSIGNAL);
    if (w <= 0) return false;
    d += w;
    n -= (size_t)w;
  }
  return true;
}

static void hammer_payloads(int nrec, std::string& data,
                            std::vector<uint64_t>& offs) {
  data.clear();
  offs.assign(1, 0);
  for (int i = 0; i < nrec; i++) {
    char buf[32];
    int n = snprintf(buf, sizeof buf, "hammer-%d", i);
    data.append(buf, (size_t)n);
    offs.push_back(data.size());
  }
}

static void hammer_broker_conn(int fd, int nrec) {
  std::string data;
  std::vector<uint64_t> offs;
  hammer_payloads(nrec, data, offs);
  for (;;) {
    uint8_t szb[4];
    if (!h_recv_all(fd, szb, 4)) break;
    uint32_t sz_n;  // memcpy, not a type-punned cast: szb is 1-aligned
    memcpy(&sz_n, szb, 4);
    uint32_t sz = ntohl(sz_n);
    if (sz < 8 || sz > (1u << 24)) break;
    std::vector<uint8_t> req(sz);
    if (!h_recv_all(fd, req.data(), sz)) break;
    uint16_t api_n;
    memcpy(&api_n, req.data(), 2);
    int16_t api = (int16_t)ntohs(api_n);
    uint32_t corr_n;
    memcpy(&corr_n, req.data() + 4, 4);
    Writer body;
    if (api == 0) {  // Produce v3: echo success for topic/partition 0
      body.i32(1);
      body.str("hammer");
      body.i32(1);
      body.i32(0);   // partition
      body.i16(0);   // err
      body.i64(0);   // base offset
      body.i64(-1);  // log append time
    } else {  // Fetch v4: one batch of nrec records from offset 0
      body.i32(0);  // throttle
      body.i32(1);
      body.str("hammer");
      body.i32(1);
      body.i32(0);          // partition
      body.i16(0);          // err
      body.i64(nrec);       // high watermark
      body.i64(nrec);       // last stable offset
      body.i32(0);          // aborted txns
      build_record_batch(body, (const uint8_t*)data.data(), offs.data(),
                         nrec, 1700000000000LL);  // writes i32 len + blob
    }
    Writer resp;
    resp.i32((int32_t)(body.buf.size() + 4));
    resp.append(&corr_n, 4);  // echo correlation id verbatim
    resp.append(body.buf.data(), body.buf.size());
    if (!h_send_all(fd, resp.buf.data(), resp.buf.size())) break;
  }
  close(fd);
}

static void test_kafka_hammer() {
  const int NREC = 5, ITERS = 40, NTHREADS = 4;
  int lfd = socket(AF_INET, SOCK_STREAM, 0);
  assert(lfd >= 0);
  int one = 1;
  setsockopt(lfd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof one);
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
  addr.sin_port = 0;
  assert(bind(lfd, (sockaddr*)&addr, sizeof addr) == 0);
  assert(listen(lfd, 8) == 0);
  socklen_t alen = sizeof addr;
  assert(getsockname(lfd, (sockaddr*)&addr, &alen) == 0);
  int port = (int)ntohs(addr.sin_port);

  std::atomic<bool> stop{false};
  std::vector<std::thread> conns;
  std::mutex conns_mu;
  std::thread server([&] {
    for (;;) {
      int cfd = accept(lfd, nullptr, nullptr);
      if (cfd < 0) return;  // listen fd closed: shutdown
      std::lock_guard<std::mutex> g(conns_mu);
      if (stop.load()) {
        close(cfd);
        return;
      }
      conns.emplace_back(hammer_broker_conn, cfd, NREC);
    }
  });

  // concurrent init of the dlopen'd TLS surface (std::call_once path —
  // the hand-rolled flag it replaced was a real data race)
  std::atomic<void*> tls_seen{nullptr};
  std::vector<std::thread> tls_threads;
  for (int t = 0; t < NTHREADS; t++) {
    tls_threads.emplace_back([&] {
      void* p = (void*)tls_api();
      void* prev = tls_seen.exchange(p);
      assert(prev == nullptr || prev == p);  // one consistent answer
    });
  }
  for (auto& th : tls_threads) th.join();

  // 4 client objects (the engine's per-partition-reader ownership model)
  // produce+fetch concurrently against the mini-broker: shared process
  // state (crc table, codec statics, TLS api) must be race-free
  std::string data;
  std::vector<uint64_t> offs;
  hammer_payloads(NREC, data, offs);
  std::vector<std::thread> clients;
  for (int t = 0; t < NTHREADS; t++) {
    clients.emplace_back([&, t] {
      char err[256];
      void* h = kc_connect("127.0.0.1", port, err, sizeof err);
      assert(h);
      for (int k = 0; k < ITERS; k++) {
        assert(kc_produce(h, "hammer", 0, (const uint8_t*)data.data(),
                          offs.data(), NREC, 1700000000000LL) == 0);
        int n = kc_fetch(h, "hammer", 0, 0, 1 << 20, 100);
        assert(n == NREC);
        uint64_t nb = 0;
        const uint8_t* rb = kc_rec_bytes(h, &nb);
        const uint64_t* ro = kc_rec_offsets(h);
        assert(nb == data.size());
        for (int i = 0; i < NREC; i++) {
          assert(ro[i + 1] - ro[i] == offs[i + 1] - offs[i]);
          assert(memcmp(rb + ro[i], data.data() + offs[i],
                        (size_t)(offs[i + 1] - offs[i])) == 0);
        }
        assert(kc_next_offset(h) == NREC);
        assert(kc_high_watermark(h) == NREC);
      }
      kc_close(h);
      (void)t;
    });
  }
  for (auto& th : clients) th.join();

  stop.store(true);
  // close(lfd) alone does NOT unblock a thread parked in accept() on
  // Linux — wake it with a throwaway connection, which it will close
  // and exit on (stop is set)
  int wake = socket(AF_INET, SOCK_STREAM, 0);
  if (wake >= 0) {
    connect(wake, (sockaddr*)&addr, sizeof addr);
    close(wake);
  }
  server.join();
  close(lfd);
  {
    std::lock_guard<std::mutex> g(conns_mu);
    for (auto& th : conns) th.join();
  }
  printf("kafka hammer ok\n");
}

static void test_interner_hammer() {
  // one interner per thread (the engine's ownership model: interners are
  // operator-local) — this still hammers the shared allocator under
  // contention, where TSan would catch any accidental global state
  std::vector<std::thread> ts;
  for (int t = 0; t < 4; t++) {
    ts.emplace_back([t] {
      void* h = intern_create();
      const uint32_t w = 12;
      const int N = 20000;
      std::vector<uint8_t> buf((size_t)N * w, 0);
      std::vector<int32_t> ids(N);
      for (int i = 0; i < N; i++) {
        char tmp[16];
        int len = snprintf(tmp, sizeof tmp, "t%d-%d", t, i % 3000);
        memcpy(buf.data() + (size_t)i * w, tmp, (size_t)len);
      }
      intern_many(h, buf.data(), N, w, ids.data());
      assert(intern_count(h) == 3000);
      intern_destroy(h);
    });
  }
  for (auto& th : ts) th.join();
  printf("interner hammer ok\n");
}

// one watch's arrays, as obs/statewatch.py owns them
struct SketchWatch {
  int64_t cap;
  int32_t K;
  std::vector<uint8_t> scratch, regs;
  std::vector<int64_t> keys, counts, errs;
  SketchWatch(int64_t cap_, int32_t K_)
      : cap(cap_), K(K_),
        scratch((size_t)sketch_scratch_bytes(cap_, K_), 0),
        regs(1u << 12, 0), keys((size_t)K_, -1), counts((size_t)K_, 0),
        errs((size_t)K_, 0) {}
  int64_t fold(const void* ids, int32_t id_bytes, int64_t m, int64_t rows) {
    const int64_t got =
        sketch_update(ids, id_bytes, m, rows, regs.data(), 12, keys.data(),
                      counts.data(), errs.data(), K, scratch.data(), cap);
    // every call leaves the table as it found it: all zeros
    const size_t table_bytes =
        (size_t)((uint8_t*)sketch::Scratch(scratch.data(), cap, K).listed -
                 scratch.data());
    for (size_t i = 0; i < table_bytes; i++) assert(scratch[i] == 0);
    return got;
  }
  int64_t count_of(int64_t key) const {
    for (size_t k = 0; k < keys.size(); k++)
      if (keys[k] == key) return counts[k];
    return -1;
  }
};

static void test_sketch_hammer() {
  {
    // no rows: nothing happens; arguments it will not take: refused
    SketchWatch w(256, 8);
    int32_t none = 0;
    assert(w.fold(&none, 4, 0, 0) == 0);
    assert(w.fold(&none, 4, 257, 257) == -1);  // more rows than the cap
    assert(w.fold(&none, 3, 1, 1) == -1);      // no such id width
    assert(w.fold(&none, 4, 2, 1) == -1);      // fewer rows than ids
    assert(sketch_update(&none, 4, 1, 1, w.regs.data(), 3, w.keys.data(),
                         w.counts.data(), w.errs.data(), 8,
                         w.scratch.data(), 256) == -1);  // no such p
    assert(sketch_scratch_bytes(0, 8) == -1);
    for (int64_t k : w.keys) assert(k == -1);
    for (uint8_t r : w.regs) assert(r == 0);
  }
  {
    // eight slots, five ids at the top of int32: all tracked, exact
    SketchWatch w(256, 8);
    std::vector<int32_t> ids(250);
    for (size_t i = 0; i < ids.size(); i++)
      ids[i] = INT32_MAX - (int32_t)(i % 5);
    assert(w.fold(ids.data(), 4, 250, 250) == 5);
    for (int j = 0; j < 5; j++) assert(w.count_of(INT32_MAX - j) == 50);
    // again, sampled at one row in three: hits, in row units
    assert(w.fold(ids.data(), 4, 250, 750) == 5);
    for (int j = 0; j < 5; j++) assert(w.count_of(INT32_MAX - j) == 200);
    for (int64_t e : w.errs) assert(e == 0);
  }
  {
    // as many distinct ids as the cap allows, both widths: the table is
    // as full as it gets; every count is one, so the eight smallest ids
    // are admitted
    SketchWatch w(4096, 8);
    std::vector<int32_t> a(4096);
    std::vector<int64_t> b(4096);
    for (size_t i = 0; i < a.size(); i++) {
      a[i] = (int32_t)(i * 7919u + 11u);
      b[i] = ((int64_t)1 << 40) + (int64_t)i * 7919;
    }
    assert(w.fold(a.data(), 4, 4096, 4096) == 4096);
    for (int64_t j = 0; j < 8; j++) assert(w.count_of(j * 7919 + 11) == 1);
    assert(w.fold(b.data(), 8, 4096, 4096) == 4096);
    // each newcomer evicts a slot of count one and inherits it
    for (int64_t j = 0; j < 8; j++)
      assert(w.count_of(((int64_t)1 << 40) + j * 7919) == 2);
    for (int64_t e : w.errs) assert(e == 1);
  }
  // watches on threads of their own, each with its scratch: two of the
  // four fold the same stream and must end equal
  std::vector<SketchWatch> ws(4, SketchWatch(16384, 64));
  std::vector<std::thread> ts;
  for (int t = 0; t < 4; t++) {
    ts.emplace_back([t, &ws] {
      SketchWatch& w = ws[(size_t)t];
      uint64_t x = 88172645463325252ull + (uint64_t)(t / 2);
      std::vector<int32_t> ids(16384);
      for (int round = 0; round < 60; round++) {
        const uint64_t span = round % 3 == 0 ? 10 : 100000;
        for (auto& id : ids) {
          x ^= x << 13, x ^= x >> 7, x ^= x << 17;
          id = (int32_t)(x % span);
        }
        const int64_t m = 1 + (int64_t)(x % 16384);
        assert(w.fold(ids.data(), 4, m, m + (round % 2) * 1000) > 0);
      }
    });
  }
  for (auto& th : ts) th.join();
  for (int t = 0; t < 4; t += 2) {
    assert(ws[(size_t)t].keys == ws[(size_t)t + 1].keys);
    assert(ws[(size_t)t].counts == ws[(size_t)t + 1].counts);
    assert(ws[(size_t)t].errs == ws[(size_t)t + 1].errs);
    assert(ws[(size_t)t].regs == ws[(size_t)t + 1].regs);
  }
  assert(ws[0].counts != ws[2].counts);
  printf("sketch hammer ok\n");
}

// floor(t / s) and t - floor(t / s) * s, the plain way, for s > 0
static void floor_divmod(int64_t t, int64_t s, int64_t* q, int64_t* r) {
  *q = t / s;
  *r = t % s;
  if (*r < 0) {
    *r += s;
    --*q;
  }
}

static void test_window_project() {
  const int64_t GUARD = 0x5a5a5a5a5a5a5a5a;
  {
    // no rows, and a slide the pass does not take: nothing is written
    int64_t units[1] = {GUARD}, stats[3] = {GUARD, GUARD, GUARD};
    int32_t rem[1] = {-7};
    int64_t t = 5;
    window_project_units(&t, 0, 200, units, rem, stats);
    window_project_units(&t, 1, 0, units, rem, stats);
    assert(units[0] == GUARD && rem[0] == -7 && stats[0] == GUARD);
    int64_t rstats[3];
    window_project_rebase(units, 0, 3, 1, 4, units, nullptr, rstats);
    assert(rstats[0] == 0 && rstats[1] == 0 && rstats[2] == 0);
  }
  // event times before the epoch, across it, unsorted, and at both ends of
  // int64 (a slide of 1 keeps the extremes as units); the buffers are
  // longer than the batch and must stay untouched past it
  const int64_t times[] = {-1,        0,         -200,      -201,
                           199,       200,       -1000001,  7,
                           INT64_MIN, INT64_MAX, INT64_MIN + 1, -1};
  const int64_t n = (int64_t)(sizeof times / sizeof times[0]);
  const int64_t slides[] = {1, 7, 200, 1000, 10000, INT64_MAX};
  std::vector<int64_t> units((size_t)n + 4), win_rel((size_t)n + 4);
  std::vector<int32_t> rem((size_t)n + 4);
  std::vector<uint8_t> keep((size_t)n + 4);
  for (int64_t slide : slides) {
    for (int64_t m : {n, n - 4, (int64_t)1}) {  // the same buffers, reused
      std::fill(units.begin(), units.end(), GUARD);
      std::fill(rem.begin(), rem.end(), -7);
      int64_t stats[4] = {0, 0, 0, GUARD};
      window_project_units(times, m, slide, units.data(), rem.data(), stats);
      assert(stats[3] == GUARD);  // three numbers, no more
      int64_t u_min = INT64_MAX, u_max = INT64_MIN, t_min = INT64_MAX;
      for (int64_t i = 0; i < m; i++) {
        int64_t q, r;
        floor_divmod(times[i], slide, &q, &r);
        assert(units[(size_t)i] == q);
        assert(rem[(size_t)i] == (int32_t)r);
        u_min = q < u_min ? q : u_min;
        u_max = q > u_max ? q : u_max;
        t_min = times[i] < t_min ? times[i] : t_min;
      }
      assert(stats[0] == u_min && stats[1] == u_max && stats[2] == t_min);
      for (size_t i = (size_t)m; i < units.size(); i++)
        assert(units[i] == GUARD && rem[i] == -7);
    }
  }
  {
    // the rebase: units 10..15 against first = 12, two windows closable,
    // each unit reaching four windows back
    const int64_t u[] = {15, 10, 12, 13, 14, 11, 14};
    int64_t stats[3];
    std::fill(win_rel.begin(), win_rel.end(), GUARD);
    std::fill(keep.begin(), keep.end(), (uint8_t)9);
    window_project_rebase(u, 7, 12, 2, 4, win_rel.data(), keep.data(), stats);
    const int64_t want[] = {3, -2, 0, 1, 2, -1, 2};
    for (size_t i = 0; i < 7; i++) {
      assert(win_rel[i] == want[i]);
      assert(keep[i] == (want[i] >= 2));
    }
    assert(win_rel[7] == GUARD && keep[7] == 9);
    // two behind first, four behind the watermark; the kept rows at 2 and
    // 3 reach back to windows -2 and -1... 0 and 1 are closable: straddle
    assert(stats[0] == 2 && stats[1] == 4 && stats[2] == 1);
    // nothing closable: nothing straddles, whatever reaches behind first
    window_project_rebase(u, 7, 12, 0, 4, win_rel.data(), nullptr, stats);
    assert(stats[0] == 2 && stats[1] == 2 && stats[2] == 0);
    // every kept row clear of the closable windows
    window_project_rebase(u, 7, 4, 2, 4, win_rel.data(), nullptr, stats);
    assert(stats[0] == 0 && stats[1] == 0 && stats[2] == 0);
    // a window length of one unit never straddles
    window_project_rebase(u, 7, 12, 2, 0, win_rel.data(), nullptr, stats);
    assert(stats[1] == 4 && stats[2] == 0);
  }
  {
    // the reducer takes the units as they are and rebases them by u_off;
    // with two subs a row's remainder picks its sub
    const int64_t u[] = {102, 100, 101, 100, 99, 104};
    const int32_t r[] = {10, 150, 99, 100, 0, 0};
    const int32_t g[] = {1, 0, 1, 0, 0, 0};
    const double v[] = {1.0, 2.0, 3.0, 4.0, 5.0, 6.0};
    const int32_t U = 3, SUB = 2, G = 2;
    std::vector<double> rec((size_t)(U * SUB * G) * 5);
    for (size_t c = 0; c < rec.size() / 5; c++) {
      rec[c * 5] = rec[c * 5 + 1] = rec[c * 5 + 2] = 0.0;
      rec[c * 5 + 3] = INFINITY;
      rec[c * 5 + 4] = -INFINITY;
    }
    std::vector<int64_t> touched(16);
    int64_t nt = 0;
    // units 99 and 104 fall outside [100, 103): skipped
    assert(partial_window_agg(u, 100, r, 100, g, v, nullptr, 6, 1, U, SUB, G,
                              rec.data(), touched.data(), &nt) == 4);
    assert(nt == 3);
    auto cell = [&](int64_t unit, int64_t sub, int64_t gid) {
      return rec.data() + (((unit * SUB) + sub) * G + gid) * 5;
    };
    assert(cell(0, 1, 0)[0] == 2.0 && cell(0, 1, 0)[2] == 6.0);  // rem 150, 100
    assert(cell(0, 1, 0)[3] == 2.0 && cell(0, 1, 0)[4] == 4.0);
    assert(cell(1, 0, 1)[0] == 1.0 && cell(1, 0, 1)[2] == 3.0);  // rem 99
    assert(cell(2, 0, 1)[0] == 1.0 && cell(2, 0, 1)[2] == 1.0);
    assert(cell(0, 0, 0)[0] == 0.0);
    // one sub: the remainders are not read
    int64_t nt1 = 0;
    std::vector<double> rec1((size_t)(U * G) * 5, 0.0);
    assert(partial_window_agg(u, 100, nullptr, 0, g, v, nullptr, 6, 1, U, 1,
                              G, rec1.data(), touched.data(), &nt1) == 4);
    assert(nt1 == 3 && rec1[0] == 2.0);
  }
  printf("window project ok\n");
}

int main(int argc, char** argv) {
  const char* dir = argc > 1 ? argv[1] : "/tmp/native_test_lsm";
  test_lsm(dir);
  test_interner();
  test_json();
  test_json_fast_layout();
  test_json_tree();
  test_json_generic_lists();
  test_avro();
  test_avro_tree();
  test_codecs();
  test_lsm_hammer(dir);
  test_kafka_hammer();
  test_interner_hammer();
  test_sketch_hammer();
  test_window_project();
  printf("ALL NATIVE TESTS PASSED\n");
  return 0;
}
