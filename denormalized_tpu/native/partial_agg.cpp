// Host-edge partial window aggregation — the native single-pass reducer
// behind the "partial_merge" device strategy.
//
// Why this exists: a streaming engine feeding an accelerator should ship
// the SMALLEST sufficient statistics across the host->device link, not raw
// rows.  This kernel reduces a decoded batch to per-(slide-unit, sub,
// group) partials (row count; per value column: valid count, sum, min,
// max) in one pass over the rows.  The device then folds the partials into
// its HBM-resident window ring (sliding fan-out included) — the same
// Partial/Final split the reference applies across CPU partitions
// (crates/core/src/planner/streaming_window.rs:133-153), applied across
// the host/accelerator boundary.
//
// The `sub` axis splits each slide unit in two when window length is not a
// multiple of the slide: rows with rem < L - (k-1)*S belong to all k
// overlapping windows (sub 0), the rest to only the first k-1 (sub 1).
// With L % S == 0 every row is sub 0 and SUB == 1.
//
// Accumulation is f64 on host — strictly more precise than the per-row
// f32 device scatter it replaces.

#include <cstdint>
#include <cmath>
#include <cstring>

extern "C" {

// One pass over n rows.  Inputs are dense C-order:
//   units:   (n) int64  — slide-unit index of every row; the row's unit in
//            the stripe is units[i] - u_off, taken here so the caller
//            rebases nothing.  Rows outside [0, U) are skipped (late /
//            overflow)
//   rem:     (n) int32 or NULL — ts - unit*slide; a row with
//            rem >= edge is sub 1.  NULL (SUB == 1) = all sub 0
//   gid:     (n) int32  — dense group ids in [0, G)
//   values:  (n, V) f64 — value matrix (row-major)
//   colvalid:(n, V) uint8 or NULL — per-cell validity; NULL = all valid
// Output: rec, (U * SUB * G, 1 + 4 * V) f64 — ONE RECORD PER CELL, cell
//   ((u*SUB)+s)*G+g, fields
//     [0]            rows in the cell (count(*))
//     [1 + 4*v + 0]  valid values of column v
//     [1 + 4*v + 1]  their sum
//     [1 + 4*v + 2]  their min (caller inits to +inf)
//     [1 + 4*v + 3]  their max (caller inits to -inf)
//   Counts are f64 (exact far beyond a stripe's 2^24 rows).  A record is
//   40 bytes for one column: a row costs one cache line where five
//   parallel planes cost five — at ten million groups every one a miss —
//   and the cells of the next rows are prefetched while this one folds.
//   touched: int64 — the flat index of every cell whose row count this call
//            raised from 0, appended at touched[*n_touched] in the order met
//            (the caller keeps room for n more).  Between two resets the
//            list holds each written cell once: packing and resetting a
//            stripe then cost what it touched, not what it could hold.
// Returns number of rows folded (excludes skipped).
int64_t partial_window_agg(
    const int64_t* units,
    int64_t u_off,
    const int32_t* rem,
    int64_t edge,
    const int32_t* gid,
    const double* values,
    const uint8_t* colvalid,
    int64_t n,
    int32_t V,
    int32_t U,
    int32_t SUB,
    int32_t G,
    double* rec,
    int64_t* touched,
    int64_t* n_touched) {
  const int64_t R = 1 + 4 * (int64_t)V;
  constexpr int64_t AHEAD = 16;
  int64_t folded = 0;
  int64_t nt = *n_touched;
  auto cell_of = [&](int64_t i) -> int64_t {
    // unsigned: a unit far from the stripe wraps, it does not overflow
    const int64_t u = (int64_t)((uint64_t)units[i] - (uint64_t)u_off);
    const int32_t g = gid[i];
    if (u < 0 || u >= U || g < 0 || g >= G) return -1;
    const int32_t s = rem ? (int32_t)(rem[i] >= edge) : 0;
    return ((u * SUB) + s) * G + g;
  };
  for (int64_t i = 0; i < n; ++i) {
    if (i + AHEAD < n) {
      const int64_t ahead = cell_of(i + AHEAD);
      if (ahead >= 0) {  // a record may straddle two lines
        __builtin_prefetch(rec + ahead * R, 1);
        __builtin_prefetch(rec + ahead * R + R - 1, 1);
      }
    }
    const int64_t cell = cell_of(i);
    if (cell < 0) continue;
    double* r = rec + cell * R;
    if (r[0] == 0.0) touched[nt++] = cell;
    r[0] += 1.0;
    ++folded;
    for (int32_t v = 0; v < V; ++v) {
      if (colvalid && !colvalid[i * V + v]) continue;
      const double x = values[i * V + v];
      double* f = r + 1 + 4 * v;
      f[0] += 1.0;
      f[1] += x;
      // NaN propagates (parity with the device scatter path and numpy
      // fallback): a plain `x < mn` comparison would silently skip NaN
      if (x != x || x < f[2]) f[2] = x;
      if (x != x || x > f[3]) f[3] = x;
    }
  }
  *n_touched = nt;
  return folded;
}

// Pack and reset the active cells of ONE slide unit in one pass over
// their records (host_partial.py, compact layout): for cell i of the n
// ascending flat indices `cells`, write
//   packed[0][i]              = cells[i] - cell_base   (index in the unit)
//   packed[1 + row][i]        = the record's fields as f32 bit patterns —
//     field fields[k] for each of the n_fields planes walked; a plane with
//     split[k] != 0 is a sum and takes TWO rows, (hi, lo) with
//     hi = f32(x), lo = f32(x - hi): the f64 host sum survives transit;
//     a sum that is not finite in f32 ships lo = 0 (inf - inf would be NaN)
// then put the record back to `neutral`.  `stride` is packed's row length
// in int32s.  Returns how many finite f64 sums overflowed f32 (the caller
// refuses them for f64 accumulators).
int64_t partial_pack_cells(
    const int64_t* cells,
    int64_t n,
    int64_t cell_base,
    double* rec,
    int32_t R,
    const int32_t* fields,
    const uint8_t* split,
    int32_t n_fields,
    int32_t* packed,
    int64_t stride,
    const double* neutral) {
  constexpr int64_t AHEAD = 16;
  int64_t overflowed = 0;
  auto bits = [](float f) { int32_t b; std::memcpy(&b, &f, 4); return b; };
  for (int64_t i = 0; i < n; ++i) {
    if (i + AHEAD < n) {
      __builtin_prefetch(rec + cells[i + AHEAD] * R, 1);
      __builtin_prefetch(rec + cells[i + AHEAD] * R + R - 1, 1);
    }
    double* r = rec + cells[i] * R;
    packed[i] = (int32_t)(cells[i] - cell_base);
    int64_t row = 1;
    for (int32_t k = 0; k < n_fields; ++k) {
      const double x = r[fields[k]];
      const float hi = (float)x;
      packed[row++ * stride + i] = bits(hi);
      if (split[k]) {
        float lo = (float)(x - (double)hi);
        if (!std::isfinite(hi)) {
          lo = 0.0f;
          if (std::isfinite(x)) ++overflowed;
        }
        packed[row++ * stride + i] = bits(lo);
      }
    }
    for (int32_t f = 0; f < R; ++f) r[f] = neutral[f];
  }
  return overflowed;
}

// The window operator's batch time arithmetic (ops/window_project.py), so
// that a batch's timestamps are scanned once.
//
// window_project_units: for each of the n event times ts[i] (ms) and a
// slide > 0, the slide unit floor(ts / slide) and the remainder
// ts - unit * slide in [0, slide) — FLOOR division, NumPy's divmod, for
// event times before the epoch too (C's `/` truncates) — written into
// units (int64) and rem (int32, the low 32 bits), buffers the caller owns
// with room for n.  stats[0..2] = the least unit, the greatest unit, the
// least ts.  Nothing is written when n == 0.
//
// A stream runs forward, so nearly every row lies in the unit of the row
// the last division was paid for: that is a subtraction and a compare
// against that row (t0, its remainder r0), exact over the whole of int64,
// and the division is paid only where the unit changes.  Shuffled input
// pays it most rows and gets the same numbers.
void window_project_units(
    const int64_t* ts,
    int64_t n,
    int64_t slide,
    int64_t* units,
    int32_t* rem,
    int64_t* stats) {
  if (n <= 0 || slide <= 0) return;
  int64_t u_min = INT64_MAX, u_max = INT64_MIN, t_min = INT64_MAX;
  int64_t t0 = 0, q = 0;
  uint64_t r0 = 0;
  bool have = false;
  for (int64_t i = 0; i < n; ++i) {
    const int64_t t = ts[i];
    uint64_t r;
    bool same;
    if (t >= t0) {
      const uint64_t du = (uint64_t)t - (uint64_t)t0;
      same = du < (uint64_t)slide - r0;
      r = r0 + du;
    } else {
      const uint64_t du = (uint64_t)t0 - (uint64_t)t;
      same = du <= r0;
      r = r0 - du;
    }
    if (!(same && have)) {
      q = t / slide;
      int64_t m = t % slide;
      if (m < 0) {  // slide > 0: q > INT64_MIN here
        m += slide;
        --q;
      }
      t0 = t;
      r0 = r = (uint64_t)m;
      have = true;
      if (q < u_min) u_min = q;
      if (q > u_max) u_max = q;
    }
    units[i] = q;
    rem[i] = (int32_t)r;
    if (t < t_min) t_min = t;
  }
  stats[0] = u_min;
  stats[1] = u_max;
  stats[2] = t_min;
}

// window_project_rebase: once the operator knows `first` (its lowest open
// window) and `closable` (how many windows from there the watermark has
// closed): per row w = win_rel[i] = units[i] - first, and in the same pass
//   stats[0]  late      rows with w < 0          (behind first)
//   stats[1]  behind    rows with w < closable   (behind the watermark:
//                       what a host-reducing backend drops)
//   stats[2]  straddle  0 / 1: a KEPT row (w >= closable) with
//                       w - span < closable — it reaches back into a
//                       closable window (where there is one)
// keep[i] = w >= closable where keep is given; the caller gives it only
// where a row will be dropped (the batch's least unit says so).
void window_project_rebase(
    const int64_t* units,
    int64_t n,
    int64_t first,
    int64_t closable,
    int64_t span,
    int64_t* win_rel,
    uint8_t* keep,
    int64_t* stats) {
  int64_t late = 0, behind = 0, straddle = 0;
  for (int64_t i = 0; i < n; ++i) {
    // wraps as NumPy's int64 does, here and at `- span`
    const int64_t w = (int64_t)((uint64_t)units[i] - (uint64_t)first);
    win_rel[i] = w;
    late += w < 0;
    behind += w < closable;
    straddle |= (w >= closable) &
                ((int64_t)((uint64_t)w - (uint64_t)span) < closable);
  }
  if (keep)
    for (int64_t i = 0; i < n; ++i) keep[i] = win_rel[i] >= closable;
  stats[0] = late;
  stats[1] = behind;
  stats[2] = straddle && closable > 0;
}

}  // extern "C"
