// Native ingest scatter kernels.
//
// The columnar import path (pilosa_tpu/ingest, API.import_columns)
// is host-bound in numpy on two scatters that vectorize poorly:
// np.bitwise_or.at (~40ns/bit) and the per-plane BSI column
// selection.  The reference's equivalent hot loops are Go word
// writes (fragment.go importValue / roaring container ops); these
// are the same loops as tight C.  Loaded via ctypes
// (pilosa_tpu/storage/native_ingest.py); every function has a numpy
// fallback so the engine still runs without a toolchain.

#include <cstdint>
#if defined(__SSE2__) && !defined(PT_PORTABLE)
#include <emmintrin.h>
#endif

extern "C" {

// OR a 1-bit at each column id into the packed word array.
// cols must be < width; words has width/32 entries.
void pt_or_bits(uint32_t *words, const int64_t *cols, int64_t n) {
    for (int64_t j = 0; j < n; j++) {
        int64_t c = cols[j];
        words[c >> 5] |= (uint32_t)1 << (c & 31);
    }
}

// BSI plane fill, word-major (transposed) layout with built-in
// last-write-wins: scratch_t is (plane_words x n_planes) so one
// value's exists/sign/magnitude writes land in ONE cache line
// instead of n_planes planes 128KB apart (~2x on wide BSI columns);
// the caller transposes back to plane-major with a single vectorized
// copy.  Values are scanned in REVERSE; a column whose exists bit is
// already set was written by a later entry and is skipped, so
// callers need no sort-based dedup.  Layout per word:
// [exists, sign, bit0..bitN] (fragment.go BSI layout: bsiExistsBit,
// bsiSignBit, bsiOffsetBit).  n_planes is 2 + depth; magnitude bits
// at or beyond `depth` are dropped here as a hard bound (the Python
// caller raises on out-of-depth values BEFORE calling, but this
// kernel must never scribble past its scratch row even if handed a
// bad value).
void pt_bsi_fill_t(uint32_t *scratch_t, int64_t n_planes,
                   const int64_t *cols, const int64_t *vals,
                   int64_t n) {
    int64_t depth = n_planes - 2;
    for (int64_t j = n - 1; j >= 0; j--) {
        int64_t c = cols[j];
        uint32_t *cell = scratch_t + (c >> 5) * n_planes;
        uint32_t bit = (uint32_t)1 << (c & 31);
        if (cell[0] & bit) continue;  // a later write won
        int64_t v = vals[j];
        // unsigned negation: -v overflows (UB) at INT64_MIN, whose
        // magnitude 2^63 only exists in uint64
        uint64_t mag = v < 0 ? ~(uint64_t)v + 1 : (uint64_t)v;
        cell[0] |= bit;
        if (v < 0) cell[1] |= bit;
        while (mag) {
            int i = __builtin_ctzll(mag);
            if (i >= depth) break;  // bits ascend: all later ones OOB
            cell[2 + i] |= bit;
            mag &= mag - 1;
        }
    }
}

// Mutex/bool fill with built-in last-write-wins: rowidx[j] is the
// dense index (0..n_rows-1) of entry j's row id; scratch is
// (n_rows x plane_words) zeroed planes and written is one zeroed
// plane that ends up holding every touched column (the
// clear-then-set mask).  Reverse scan + skip gives last-write-wins
// without the np.unique sort.
void pt_mutex_fill(uint32_t *written, uint32_t *scratch,
                   int64_t plane_words, const int64_t *rowidx,
                   const int64_t *cols, int64_t n) {
    for (int64_t j = n - 1; j >= 0; j--) {
        int64_t c = cols[j];
        int64_t w = c >> 5;
        uint32_t bit = (uint32_t)1 << (c & 31);
        if (written[w] & bit) continue;  // a later write won
        written[w] |= bit;
        scratch[rowidx[j] * plane_words + w] |= bit;
    }
}

// One-pass GroupBy histogram over composed group codes (the host twin
// of ops/kernels.py groupby_onehot).  code_planes is (cb x w) packed
// bit-planes of the per-column group code; valid masks the columns
// belonging to some combo (AND of field unions, AND the filter); bsi
// (may be null) is the aggregate field's (2+depth x w) plane stack.
// Accumulates counts/nn (n_codes) and the sign-split per-plane
// popcount partials pos/neg (n_codes x depth) — identical layout to
// every other GroupBy path, so host combination stays bit-exact.
// Each input word is read exactly once regardless of combo count.
// Schedule: words are processed in PAIRS as uint64 lanes with every
// plane word hoisted into locals before the per-column loop — the
// hoist halves the loop setups and lets the compiler keep the plane
// bits in registers across the bit-scan (measured ~1.5x over the
// straightforward per-column gather on the dev box).
void pt_groupcode_hist(const uint32_t *__restrict code_planes,
                       int64_t cb,
                       const uint32_t *__restrict valid,
                       const uint32_t *__restrict bsi, int64_t depth,
                       int64_t sign_split,
                       int64_t w, int64_t n_codes,
                       int64_t *__restrict counts,
                       int64_t *__restrict nn,
                       int64_t *__restrict pos,
                       int64_t *__restrict neg) {
    uint64_t cpw[64], magw[64];
    if (cb > 64 || depth > 64) return;  // caller bounds both far lower
    for (int64_t i = 0; i < w; i += 2) {
        uint64_t hi_ok = (i + 1 < w);
        uint64_t v = valid[i] |
                     (hi_ok ? (uint64_t)valid[i + 1] << 32 : 0);
        if (!v) continue;
        for (int64_t b = 0; b < cb; b++) {
            const uint32_t *p = code_planes + b * w;
            cpw[b] = p[i] | (hi_ok ? (uint64_t)p[i + 1] << 32 : 0);
        }
        uint64_t ew = 0, sw = 0;
        if (bsi) {
            ew = bsi[i] | (hi_ok ? (uint64_t)bsi[i + 1] << 32 : 0);
            if (sign_split)
                sw = bsi[w + i] |
                     (hi_ok ? (uint64_t)bsi[w + i + 1] << 32 : 0);
            for (int64_t p = 0; p < depth; p++) {
                const uint32_t *m = bsi + (2 + p) * w;
                magw[p] = m[i] | (hi_ok ? (uint64_t)m[i + 1] << 32 : 0);
            }
        }
        while (v) {
            int j = __builtin_ctzll(v);
            v &= v - 1;
            int64_t code = 0;
            for (int64_t b = 0; b < cb; b++)
                code |= (int64_t)((cpw[b] >> j) & 1) << b;
            if (code >= n_codes) continue;  // padded digits: unreachable
            counts[code]++;
            if (!bsi || !((ew >> j) & 1)) continue;  // null value
            nn[code]++;
            int64_t *tgt = ((sw >> j) & 1) ? neg + code * depth
                                           : pos + code * depth;
            for (int64_t p = 0; p < depth; p++)
                tgt[p] += (magw[p] >> j) & 1;
        }
    }
}

// ---------------------------------------------------------------------
// Fresh stack pages, straight from the fragments' storage
// (memory/encode.py encode_lanes, through storage/native_ingest.py).
// A page is n_lanes rows of `width` columns; lane k is read where its
// fragment holds it: kinds[k] says how, addrs[k] where, sizes[k] how
// many (the sorted columns of a PT_COLS lane).  One call makes one
// page, and ctypes holds no interpreter lock while it runs.

enum { PT_NONE = 0, PT_CODES8 = 1, PT_CODES16 = 2, PT_COLS = 3,
       PT_WORDS = 4 };

}  // extern "C"

namespace {

// Eq8 / Eq16: one word (32 columns) of `codes == row`, column j of
// the 32 to bit j.  With SSE2 (every x86-64) a compare and a
// movemask take 16 codes at once; elsewhere, or with -DPT_PORTABLE
// (the tests build that too), eight or four codes a 64-bit word.
#if defined(__SSE2__) && !defined(PT_PORTABLE)

inline __m128i load128(const void *p) {
    return _mm_loadu_si128((const __m128i *)p);
}

struct Eq8 {
    __m128i pat;
    explicit Eq8(int64_t row) : pat(_mm_set1_epi8((char)row)) {}
    inline uint32_t word(const uint8_t *c) const {
        uint32_t lo = _mm_movemask_epi8(_mm_cmpeq_epi8(load128(c), pat));
        uint32_t hi = _mm_movemask_epi8(
            _mm_cmpeq_epi8(load128(c + 16), pat));
        return lo | hi << 16;
    }
};

struct Eq16 {
    __m128i pat;
    explicit Eq16(int64_t row) : pat(_mm_set1_epi16((short)row)) {}
    inline uint32_t half(const uint16_t *c) const {  // 16 codes
        return _mm_movemask_epi8(_mm_packs_epi16(
            _mm_cmpeq_epi16(load128(c), pat),
            _mm_cmpeq_epi16(load128(c + 8), pat)));
    }
    inline uint32_t word(const uint16_t *c) const {
        return half(c) | half(c + 16) << 16;
    }
};

#else

inline uint64_t load64(const void *p) {
    uint64_t x;
    __builtin_memcpy(&x, p, 8);
    return x;
}

const uint64_t LO7_8 = 0x7F7F7F7F7F7F7F7FULL;
const uint64_t LO15_16 = 0x7FFF7FFF7FFF7FFFULL;

struct Eq8 {
    uint64_t pat;
    explicit Eq8(int64_t row)
        : pat((uint64_t)(row & 0xFF) * 0x0101010101010101ULL) {}
    // 8 codes to 8 bits: 0x80 in every byte that equals pat's (the
    // add cannot carry out of a byte), gathered by one multiply
    inline uint32_t byte(const uint8_t *c) const {
        uint64_t y = load64(c) ^ pat;
        uint64_t m = ~(((y & LO7_8) + LO7_8) | y | LO7_8);
        return (uint32_t)(((m >> 7) * 0x0102040810204080ULL) >> 56);
    }
    inline uint32_t word(const uint8_t *c) const {
        return byte(c) | byte(c + 8) << 8 | byte(c + 16) << 16
             | byte(c + 24) << 24;
    }
};

struct Eq16 {
    uint64_t pat;
    explicit Eq16(int64_t row)
        : pat((uint64_t)(row & 0xFFFF) * 0x0001000100010001ULL) {}
    inline uint32_t nibble(const uint16_t *c) const {  // 4 codes
        uint64_t y = load64(c) ^ pat;
        uint64_t m = ~(((y & LO15_16) + LO15_16) | y | LO15_16);
        return (uint32_t)(((m >> 15) * 0x0001000200040008ULL) >> 48)
             & 15u;
    }
    inline uint32_t word(const uint16_t *c) const {
        uint32_t w = 0;
        for (int k = 0; k < 8; k++) w |= nibble(c + 4 * k) << (4 * k);
        return w;
    }
};

#endif

// Where a page's all-ones words lie, as memory/encode.py's run
// analysis counts them: how many, and in how many runs over the flat
// word space of the page.
struct Runs {
    int64_t n_full = 0, n_runs = 0, last = -2;
    inline void full(int64_t flat) {
        n_full++;
        if (flat != last + 1) n_runs++;
        last = flat;
    }
};

// The coordinates base + column of `codes == row` over one lane,
// appended at coords[n]; the new n, or -1 where they pass cap.
// `fill` is what the unwritten part of coords holds.
template <class Eq, class T>
int64_t lane_coords(const T *c, int64_t width, const Eq &eq,
                    uint32_t base, uint32_t *coords, int64_t n,
                    int64_t cap, uint32_t fill) {
    int64_t i = 0;
    // a row worth packing has a bit in one word of five, at random,
    // and a second in one of fifty: the first is stored without a
    // branch (a word with none stores over the next free place, which
    // is put right below), the others in a loop rarely entered
    for (; i < width && n + 32 <= cap; i += 32) {
        uint32_t m = eq.word(c + i);
        coords[n] = base + (uint32_t)(i + __builtin_ctz(m | 0x80000000u));
        n += m != 0;
        for (m &= m - 1; m; m &= m - 1)
            coords[n++] = base + (uint32_t)(i + __builtin_ctz(m));
    }
    if (n < cap) coords[n] = fill;
    for (; i < width; i += 32) {      // the last 32 places: counted
        uint32_t m = eq.word(c + i);
        if (n + __builtin_popcount(m) > cap) return -1;
        for (; m; m &= m - 1)
            coords[n++] = base + (uint32_t)(i + __builtin_ctz(m));
    }
    return n;
}

// The packed words of `codes == row` over one lane, `flat` the first
// word's place in the page.
template <class Eq, class T>
void lane_words(const T *c, int64_t w, const Eq &eq, uint32_t *out,
                int64_t flat, Runs &runs) {
    for (int64_t i = 0; i < w; i++) {
        uint32_t v = eq.word(c + 32 * i);
        out[i] = v;
        if (v == 0xFFFFFFFFu) runs.full(flat + i);
    }
}

}  // namespace

extern "C" {

// (a) The sorted coordinates `lane * width + column` of the row's bits
// into coords (cap entries, sentinels already there), each lane's
// count into lane_counts (n_lanes entries).  Returns how many were
// written, or -1 where they pass cap or a lane is held as words (the
// caller then fills the dense block): nothing past cap is written.
// A row no code of the lane's width can hold has no bit there.
int64_t pt_page_coords(const uint64_t *addrs, const int32_t *kinds,
                       const int64_t *sizes, int64_t n_lanes,
                       int64_t width, int64_t row,
                       uint32_t *coords, int64_t cap,
                       int64_t *lane_counts) {
    int64_t n = 0;
    const uint32_t fill = cap > 0 ? coords[cap - 1] : 0;
    for (int64_t k = 0; k < n_lanes; k++) {
        const int64_t before = n;
        const uint32_t base = (uint32_t)(k * width);
        const void *p = (const void *)(uintptr_t)addrs[k];
        switch (kinds[k]) {
        case PT_NONE:
            break;
        case PT_CODES8:
            if (row >= 0 && row < 0xFF)
                n = lane_coords((const uint8_t *)p, width, Eq8(row),
                                base, coords, n, cap, fill);
            break;
        case PT_CODES16:
            if (row >= 0 && row < 0xFFFF)
                n = lane_coords((const uint16_t *)p, width, Eq16(row),
                                base, coords, n, cap, fill);
            break;
        case PT_COLS: {
            const int64_t *cols = (const int64_t *)p;
            if (n + sizes[k] > cap) return -1;
            for (int64_t j = 0; j < sizes[k]; j++)
                coords[n++] = base + (uint32_t)cols[j];
            break;
        }
        default:
            return -1;
        }
        if (n < 0) return -1;
        lane_counts[k] = n - before;
    }
    return n;
}

// (b) The row's packed words into block: page_lanes lanes of width/32
// words, every one written (zeros past n_lanes and for a lane nobody
// holds).  stats[0], stats[1]: the all-ones words and their runs
// among the lanes made here from codes or columns; a PT_WORDS lane is
// one copy and is not looked at (stats[2] counts such lanes, and the
// caller analyses the block as it always did).
void pt_page_fill(const uint64_t *addrs, const int32_t *kinds,
                  const int64_t *sizes, int64_t n_lanes,
                  int64_t page_lanes, int64_t width, int64_t row,
                  uint32_t *block, int64_t *stats) {
    const int64_t w = width >> 5;
    Runs runs;
    int64_t copied = 0;
    for (int64_t k = 0; k < page_lanes; k++) {
        uint32_t *out = block + k * w;
        int kind = k < n_lanes ? kinds[k] : PT_NONE;
        const void *p = k < n_lanes
            ? (const void *)(uintptr_t)addrs[k] : nullptr;
        if ((kind == PT_CODES8 && (row < 0 || row >= 0xFF)) ||
            (kind == PT_CODES16 && (row < 0 || row >= 0xFFFF)))
            kind = PT_NONE;
        switch (kind) {
        case PT_CODES8:
            lane_words((const uint8_t *)p, w, Eq8(row), out, k * w,
                       runs);
            break;
        case PT_CODES16:
            lane_words((const uint16_t *)p, w, Eq16(row), out, k * w,
                       runs);
            break;
        case PT_COLS: {
            const int64_t *cols = (const int64_t *)p;
            __builtin_memset(out, 0, w * 4);
            for (int64_t j = 0; j < sizes[k]; j++) {
                int64_t col = cols[j];
                uint32_t v = out[col >> 5] |= (uint32_t)1 << (col & 31);
                // sorted distinct columns: a word is full when its
                // last column lands, and the words fill in order
                if (v == 0xFFFFFFFFu) runs.full(k * w + (col >> 5));
            }
            break;
        }
        case PT_WORDS:
            __builtin_memcpy(out, p, w * 4);
            copied++;
            break;
        default:
            __builtin_memset(out, 0, w * 4);
        }
    }
    stats[0] = runs.n_full;
    stats[1] = runs.n_runs;
    stats[2] = copied;
}

}  // extern "C"
